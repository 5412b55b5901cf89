"""Per-kernel probes: compile ONE Pallas kernel, check it against its XLA
reference, return a small dict of floats.

``KERNEL_PROBES`` runs every kernel on the smallest Mosaic-legal shapes
(D=128 lanes, page_size%16 sublanes, Hkv%8 for the flat page matmuls);
:func:`model_geometry_probes` runs the serving-path kernels at the shapes an
engine for a given model will actually ask for. ``chip_smoke.py`` calls both
on the TPU, where each kernel goes through Mosaic; on the CPU the same
probes run in Pallas interpret mode, so the test suite exercises them too.

Keep the registry in sync with the kernels: tests/test_probes.py asserts
every ops/ module that calls ``pl.pallas_call`` has at least one probe here.
"""

from __future__ import annotations

import functools
from typing import Callable

# which probes cover which pallas_call-bearing module; a test asserts this
# stays in sync with the set of modules that actually call pl.pallas_call,
# so a new kernel module cannot land without a probe.
PROBED_MODULES: dict[str, list[str]] = {
    "modal_examples_tpu.ops.flash_attention": [
        "flash_fwd", "flash_bwd", "flash_chunked",
    ],
    "modal_examples_tpu.ops.paged_attention": [
        "ragged_decode", "ragged_decode_gqa", "ragged_decode_gqa_flat",
        "ragged_decode_int8kv", "ragged_decode_gqa_int8kv",
        "ragged_decode_gqa_flat_int8kv", "ragged_decode_tp_shard_int8kv",
        "scatter_kv", "scatter_kv_int8",
    ],
    "modal_examples_tpu.ops.quantized_matmul": ["int8_matmul"],
    "modal_examples_tpu.ops.sparse_attention": ["selected_attention"],
    "modal_examples_tpu.ops.ssm_step": ["ssm_step"],
    "modal_examples_tpu.ops.expert_swiglu": ["expert_swiglu"],
}

#: every attention probe's bound against its reference: bf16 operands with
#: f32 accumulation on outputs of order 1 (int8-KV probes compare against
#: the DEQUANTIZED pages, so quantization noise is not in it)
ATTN_TOL = 0.06


def _err(a, b) -> float:
    import jax.numpy as jnp

    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


def _qkv(B, Hq, Hkv, S, D, Dv=None):
    import jax
    import jax.numpy as jnp

    q = jax.random.normal(jax.random.PRNGKey(0), (B, Hq, S, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, S, D), jnp.bfloat16)
    v = jax.random.normal(
        jax.random.PRNGKey(2), (B, Hkv, S, Dv or D), jnp.bfloat16
    )
    return q, k, v


def probe_flash_fwd(B=1, Hq=8, Hkv=4, S=256, D=128) -> dict:
    import jax

    from modal_examples_tpu import ops
    from modal_examples_tpu.ops import reference

    q, k, v = _qkv(B, Hq, Hkv, S, D)
    o = jax.jit(ops.flash_attention)(q, k, v)
    ref = jax.jit(reference.attention)(q, k, v)
    err = _err(o, ref)
    assert err < ATTN_TOL, err
    return {"max_err": round(err, 4)}


def probe_flash_bwd() -> dict:
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu import ops
    from modal_examples_tpu.ops import reference

    q, k, v = _qkv(1, 8, 4, 256, 128)

    def loss(fn):
        return lambda q, k, v: jax.numpy.sum(fn(q, k, v).astype(jnp.float32))

    g1 = jax.jit(jax.grad(loss(ops.flash_attention), argnums=(0, 1, 2)))(
        q, k, v
    )
    g2 = jax.jit(jax.grad(loss(reference.attention), argnums=(0, 1, 2)))(
        q, k, v
    )
    errs = [_err(a, b) for a, b in zip(g1, g2)]
    assert max(errs) < 0.5, errs  # sum-of-S grad scale
    return {"max_err": round(max(errs), 4)}


def probe_flash_chunked(
    B=1, Hq=8, Hkv=4, S=256, D=128, C=128, *, Dv=None, ref_kv_heads=None
) -> dict:
    """The last ``C`` query positions against the whole ``S``-long K/V —
    the engine's chunked-prefill shape. ``Dv``: values narrower than q/k
    (latent attention). The kernel runs every head; ``ref_kv_heads`` holds
    the XLA reference to the first so many KV heads and their query heads
    (its f32 scores for 128 heads of a 2048 x 4096 call are 4 GiB)."""
    import jax

    from modal_examples_tpu import ops
    from modal_examples_tpu.ops import reference

    off = S - C
    q, k, v = _qkv(B, Hq, Hkv, S, D, Dv)
    qc = q[:, :, off:, :]
    o = jax.jit(
        lambda qc, k, v: ops.flash_attention_chunked(qc, k, v, q_offset=off)
    )(qc, k, v)
    n_kv = ref_kv_heads or Hkv
    n_q = n_kv * (Hq // Hkv)
    ref = jax.jit(
        lambda qc, k, v: reference.attention_chunked(qc, k, v, q_offset=off)
    )(qc[:, :n_q], k[:, :n_kv], v[:, :n_kv])
    err = _err(o[:, :n_q], ref)
    assert err < ATTN_TOL, err
    return {"max_err": round(err, 4)}


def probe_int8_matmul() -> dict:
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu import ops

    M, K, N = 256, 512, 512
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    w_q, w_scale = ops.quantize_int8(w)
    o = jax.jit(ops.quantized_matmul)(x, w_q, w_scale)
    ref = jnp.dot(
        x.astype(jnp.float32), ops.dequantize_int8(w_q, w_scale)
    )
    err = _err(o, ref)
    rel = err / (float(jnp.max(jnp.abs(ref))) + 1e-6)
    assert rel < 0.05, (err, rel)
    return {"rel_err": round(rel, 4)}


def probe_ragged(
    Hq: int, Hkv: int, variant: str | None, *, int8: bool = False,
    L=2, B=2, D=128, ps=16, pp=4,
) -> dict:
    """Ragged decode (``variant`` None = the kernel's own choice for this
    Hkv and cache dtype) vs the XLA inflight reference over the same pages.
    Slot b's prefix ends mid-page inside its b-th share of the context, so
    short, long and page-straddling sequences all occur. int8 caches go
    quantized into the kernel and DEQUANTIZED into the reference, which
    isolates the kernel from quantization noise."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu import ops

    n_pages = B * pp + 1
    kp = jax.random.normal(
        jax.random.PRNGKey(0), (L, n_pages, ps, Hkv, D), jnp.bfloat16
    )
    vp = jax.random.normal(jax.random.PRNGKey(1), kp.shape, jnp.bfloat16)
    pt = (1 + jnp.arange(B * pp, dtype=jnp.int32)).reshape(B, pp)
    ctx = pp * ps
    prefix = jnp.array(
        [min(ctx - 1, (b + 1) * ctx // B - ps // 2 + 3) for b in range(B)],
        jnp.int32,
    )
    q = jax.random.normal(jax.random.PRNGKey(2), (B, Hq, D), jnp.bfloat16)
    k_new = jax.random.normal(jax.random.PRNGKey(3), (B, Hkv, D), jnp.bfloat16)
    v_new = jax.random.normal(jax.random.PRNGKey(4), (B, Hkv, D), jnp.bfloat16)
    if int8:
        kp, vp = ops.quantize_kv(kp), ops.quantize_kv(vp)
    o = jax.jit(functools.partial(
        ops.paged_decode_attention_ragged, variant=variant
    ))(q, kp, vp, jnp.int32(L - 1), pt, prefix, k_new, v_new)
    ref = jax.jit(
        lambda kp, vp: ops.paged_decode_attention_inflight(
            q, ops.kv_gather(kp, pt, layer=L - 1),
            ops.kv_gather(vp, pt, layer=L - 1), prefix, k_new, v_new,
        )
    )(kp, vp)
    err = _err(o, ref)
    assert err < ATTN_TOL, err
    return {"max_err": round(err, 4)}


def probe_scatter(
    Hkv: int, *, int8: bool = False, L=2, P=6, ps=16, D=128, B=3
) -> dict:
    """In-place strided HBM->HBM DMA scatter (plus, for int8 caches, the
    XLA scatter of the scale columns) vs ``.at[].set`` over the whole
    cache, every page no slot targets included."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu import ops

    kp = jax.random.normal(
        jax.random.PRNGKey(0), (L, P, ps, Hkv, D), jnp.bfloat16
    )
    vp = jax.random.normal(jax.random.PRNGKey(1), kp.shape, jnp.bfloat16)
    k_all = jax.random.normal(
        jax.random.PRNGKey(2), (L, B, Hkv, D), jnp.bfloat16
    )
    v_all = jax.random.normal(jax.random.PRNGKey(3), k_all.shape, jnp.bfloat16)
    # distinct (page, slot) targets on the odd pages; page 0 stays clean
    page_idx = (1 + 2 * jnp.arange(B, dtype=jnp.int32)) % P
    slot = (7 * jnp.arange(B, dtype=jnp.int32)) % ps
    if int8:
        kp, vp = ops.quantize_kv(kp), ops.quantize_kv(vp)
    # references BEFORE the call: kp/vp are donated through the jit
    ref = jax.jit(
        lambda kp, vp: (
            ops.kv_scatter(kp, k_all, page_idx, slot),
            ops.kv_scatter(vp, v_all, page_idx, slot),
        )
    )(kp, vp)
    out = jax.jit(ops.scatter_kv_pages, donate_argnums=(0, 1))(
        kp, vp, k_all, v_all, page_idx, slot
    )
    # plain caches: bit-exact. int8 caches quantize inside each program, so
    # a value may round the other way (one int8 step; a scale's last bits);
    # a misplaced column would be off by whole values, far beyond either
    errs = {"int8": 0.0, "other": 0.0}
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        kind = "int8" if a.dtype == jnp.int8 else "other"
        errs[kind] = max(errs[kind], _err(a, b))
    assert errs["int8"] <= 1.0 and errs["other"] <= (1e-6 if int8 else 0.0), errs
    return {"max_err": max(errs.values())}


def probe_selected_attention(H=4, C=256, S=512, D=256, k=64) -> dict:
    """The flash kernel under a selection mask (a learned sparse attention's
    prefill: ``k`` of each query's causal positions, keys in blocks of 128)
    against the masked softmax in XLA."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops import sparse_attention as sp

    q, keys, v = _qkv(1, H, H, S, D)
    q = q[:, :, S - C:]
    scores = jax.random.normal(jax.random.PRNGKey(3), (1, C, S))
    causal = jnp.arange(S - C, S)[None, :, None] >= jnp.arange(S)[None, None, :]
    mask = sp.select_mask(scores, causal, k)
    blocked = lambda a: a.reshape(1, H, S // 128, 128, -1).transpose(0, 2, 1, 3, 4)  # noqa: E731
    o, ref = (
        jax.jit(lambda *a, impl=impl: sp.selected_attention(*a, sm_scale=D**-0.5, impl=impl))(
            q, blocked(keys), blocked(v), mask
        )
        for impl in ("flash", "xla")
    )
    err = _err(o, ref)
    assert err < ATTN_TOL, err
    return {"max_err": round(err, 4)}


def probe_ssm_step(L=2, S=4, H=16, P=8, N=128, G=2) -> dict:
    """A Mamba-2 layer's decode state step in one pass (the last layer of a
    stacked float32 leaf, a slot that is not live, two groups) against XLA's
    update and reduction: the same float32 arithmetic, the 128-term sums in
    another order."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.ssm_step import ssm_step, ssm_step_xla

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    live = (jnp.arange(S) != 1)[:, None]
    ssm = jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32)
    decay = jnp.where(live, jax.random.uniform(ks[1], (S, H), jnp.float32, 0.5, 1.0), 1.0)
    dtx = jnp.where(live[..., None], jax.random.normal(ks[2], (S, H, P), jnp.float32), 0.0)
    B, C = (jax.random.normal(k, (S, G, N), jnp.float32) for k in ks[3:])
    (got_h, got_y), (want_h, want_y) = (
        jax.jit(step)(ssm, jnp.int32(L - 1), decay, dtx, B, C) for step in (ssm_step, ssm_step_xla)
    )
    errs = {"state_err": _err(got_h, want_h), "y_err": _err(got_y, want_y)}
    assert errs["state_err"] < 1e-5 and errs["y_err"] < 1e-3, errs
    return {k: round(v, 7) for k, v in errs.items()}


def probe_expert_swiglu(L=2, E=4, D=128, F=256, tile=16, tiles=6, live=4) -> dict:
    """The routed experts' grouped matmul (the last layer of int8 stacks, F in
    two blocks, two tiles of one expert in a row, two trips past the live
    tiles) against the same SwiGLU a tile in XLA: int8 -> bf16 into the
    products, float32 sums, scales after; the F blocks' float32 sum in
    another order."""
    import types

    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.expert_swiglu import expert_swiglu

    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    q = lambda k, din, dout: jax.random.randint(k, (L, E, din, dout), -127, 128, jnp.int8)  # noqa: E731
    scale = lambda k, din, dout: jax.random.uniform(  # noqa: E731
        k, (L, E, 1, dout), jnp.float32, 0.5, 1.5) * din**-0.5 / 73.0
    stacks = [(q(ks[0], D, F), scale(ks[1], D, F)), (q(ks[2], D, F), scale(ks[3], D, F)),
              (q(ks[4], F, D), scale(ks[5], F, D))]
    rows = jax.random.normal(ks[6], (tiles * tile, D), jnp.float32).astype(jnp.bfloat16)
    tile_expert = jnp.asarray([3, 3, 0, 2, 1, 1], jnp.int32)[:tiles]

    @jax.jit
    def kernel(stacks, rows):
        weights = [types.SimpleNamespace(q=q, scale=s) for q, s in stacks]
        return expert_swiglu(
            *weights, rows, tile_expert, jnp.int32(live), jnp.int32(L - 1), tile=tile, block_f=F // 2
        )

    @jax.jit
    def reference(stacks, rows):
        x = rows.reshape(tiles, tile, D)
        (gq, gs), (uq, us), (dq, ds) = [(q[L - 1][tile_expert], s[L - 1][tile_expert]) for q, s in stacks]
        mm = lambda h, w: jnp.einsum(  # noqa: E731
            "trd,tdf->trf", h, w.astype(h.dtype), preferred_element_type=jnp.float32)
        a, b = mm(x, gq) * gs, mm(x, uq) * us
        return (mm((jax.nn.silu(a) * b).astype(x.dtype), dq) * ds).reshape(tiles * tile, D)

    n = live * tile
    err = _err(kernel(stacks, rows)[:n], reference(stacks, rows)[:n])
    assert err < 1e-4, err
    return {"max_err": round(err, 7)}


#: probe name -> zero-argument callable, on the smallest legal shapes
KERNEL_PROBES: dict[str, Callable[[], dict]] = {
    "flash_fwd": probe_flash_fwd,
    "flash_bwd": probe_flash_bwd,
    "flash_chunked": probe_flash_chunked,
    "int8_matmul": probe_int8_matmul,
    "ragged_decode": functools.partial(probe_ragged, 16, 16, "flat"),
    # the "grouped" per-kv-head formulation at a GQA shape (Hkv=8, G=4): it
    # slices heads out of the (ps, Hkv, D) pages, so any Hkv
    "ragged_decode_gqa": functools.partial(probe_ragged, 32, 8, "grouped"),
    # the same shape as the plan runs it since PR 35: pages read in place as
    # (ps*Hkv, D) rows under a block-diagonal head mask
    "ragged_decode_gqa_flat": functools.partial(probe_ragged, 32, 8, "flat"),
    # int8 KV: int8 pages stream and the scale rows multiply the logits
    "ragged_decode_int8kv": functools.partial(
        probe_ragged, 32, 32, "flat", int8=True
    ),
    "ragged_decode_gqa_int8kv": functools.partial(
        probe_ragged, 32, 8, "grouped", int8=True
    ),
    "ragged_decode_gqa_flat_int8kv": functools.partial(
        probe_ragged, 32, 8, "flat", int8=True
    ),
    # the TP=2 shard of the 7B MHA head geometry (Hq=Hkv=16, G=1): what each
    # device compiles inside the shard_map dispatch (ops.sharded), in the
    # grouped form
    "ragged_decode_tp_shard_int8kv": functools.partial(
        probe_ragged, 16, 16, "grouped", int8=True
    ),
    "scatter_kv": functools.partial(probe_scatter, 16),
    "scatter_kv_int8": functools.partial(probe_scatter, 32, int8=True),
    "selected_attention": probe_selected_attention,
    "ssm_step": probe_ssm_step,
    "expert_swiglu": probe_expert_swiglu,
}


#: the docqa cells' chunk call at its real size: 2048 query rows at offset 2048
#: over Mistral's and Mixtral's heads (32 over 8, width 128) and DeepSeek-V2's
#: (128 heads, q/k 192 wide, values 128) — does Mosaic take the tiles the
#: forward chooses inside VMEM, and are they right? Too large for the
#: interpreter: ``chip_smoke.py``'s kernel leg runs them on the chip, and
#: tests/tpu_compile/test_llama.py compiles the same calls for the v5e.
CELL_FLASH_PROBES: dict[str, Callable[[], dict]] = {
    "docqa_flash_chunk_gqa": functools.partial(
        probe_flash_chunked, 1, 32, 8, 4096, 128, 2048
    ),
    "docqa_flash_chunk_mla": functools.partial(
        probe_flash_chunked, 1, 128, 128, 4096, 192, 2048, Dv=128,
        ref_kv_heads=8,
    ),
}


def model_geometry_probes(
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    n_layers: int,
    page_size: int,
    pages_per_seq: int,
    slots: int,
    prefill_batch: int,
    prefill_bucket: int,
) -> dict[str, Callable[[], dict]]:
    """The serving-path kernels at one engine's own shapes — what
    ``LLMEngine`` with ``paged_impl="pallas"``, ``scatter_impl="pallas"``
    asks Mosaic for: ragged decode (the variant the kernel picks for this
    Hkv) and the KV scatter over the full ``[L, P, ...]`` cache at bf16 and
    int8, flash prefill at ``prefill_bucket`` (pass the engine's largest),
    and a chunked-prefill step of half a bucket against a whole one.
    The registry's shapes are the smallest legal ones; these are the ones a
    model will really run."""
    geom = dict(L=n_layers, B=slots, D=head_dim, ps=page_size, pp=pages_per_seq)
    n_pages = 1 + slots * pages_per_seq
    probes: dict[str, Callable[[], dict]] = {}
    for int8 in (False, True):
        tag = "int8kv" if int8 else "bf16kv"
        probes[f"model_ragged_{tag}"] = functools.partial(
            probe_ragged, n_heads, n_kv_heads, None, int8=int8, **geom
        )
        probes[f"model_scatter_{tag}"] = functools.partial(
            probe_scatter, n_kv_heads, int8=int8, L=n_layers, P=n_pages,
            ps=page_size, D=head_dim, B=slots,
        )
    probes["model_flash_prefill"] = functools.partial(
        probe_flash_fwd, prefill_batch, n_heads, n_kv_heads, prefill_bucket,
        head_dim,
    )
    probes["model_flash_chunked"] = functools.partial(
        probe_flash_chunked, prefill_batch, n_heads, n_kv_heads,
        prefill_bucket, head_dim, prefill_bucket // 2,
    )
    return probes
