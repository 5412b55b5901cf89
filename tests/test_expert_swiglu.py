"""The routed experts' grouped matmul (ops.expert_swiglu), in interpret mode
on the CPU, against the two forms it must equal: ``moe_swiglu_sparse``'s XLA
loop (same sort, same layout, same combine: ``scan="xla"``) and every expert
on every token (``moe_swiglu_nodrop``, or its sum over the held experts where
a share is held). Plain and int8 stacks, with and without a layer axis, a
share of a wider router, a token mask, an expert with more pairs than a
tile, an expert with none, every token on one expert, fewer tiles in use than
the grid has, F in one block and in several."""

import numpy as np
import pytest

ATOL = 1e-5  # test_any_tile_gives_the_same_sum's


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


def _stacks(jax, *, layers, held, D, F, int8):
    """(gate, up, down) ``[layers, held, ...]`` (no layer axis for 0)."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import quantize_weight

    lead = (layers, held) if layers else (held,)
    ks = jax.random.split(jax.random.PRNGKey(40), 3)
    gate = jax.random.normal(ks[0], (*lead, D, F)) * D**-0.5
    up = jax.random.normal(ks[1], (*lead, D, F)) * D**-0.5
    down = jax.random.normal(ks[2], (*lead, F, D)) * F**-0.5
    if int8:
        gate, up, down = (quantize_weight(w) for w in (gate, up, down))
        assert gate.q.dtype == jnp.int8
    return gate, up, down


def _every_held_expert(jax, stacks, layer, x, ids, weights, offset, mask):
    """The held experts on every token, each under the weight the token gave
    it: float32, no tiles, no sort."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.layers import mm

    pick = (lambda a: a) if layer is None else (lambda a: a[layer])
    gate, up, down = (jax.tree.map(pick, w) for w in stacks)
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(gate.shape[0]):
        one = [jax.tree.map(lambda a: a[e], w) for w in (gate, up, down)]
        w = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), axis=-1)
        out = out + w[:, None] * mm(jax.nn.silu(mm(x, one[0])) * mm(x, one[1]), one[2])
    return out if mask is None else out * mask[:, None]


#: name -> (tokens, experts a token, router width, held, offset, layers, int8, masked, routing)
CASES = {
    "plain": (24, 2, 4, 4, 0, 0, False, False, "uniform"),
    "int8-in-a-layer-stack": (24, 2, 4, 4, 0, 3, True, False, "uniform"),
    "a-share-of-a-wider-router": (40, 3, 20, 5, 5, 2, True, False, "uniform"),
    "a-token-mask": (40, 3, 20, 5, 5, 0, False, True, "uniform"),
    "more-pairs-than-a-tile": (40, 2, 4, 4, 0, 2, True, False, "skewed"),
    "every-token-on-one-expert": (40, 1, 4, 4, 0, 0, False, False, "all-to-one"),
    "an-expert-with-none": (24, 2, 6, 6, 0, 2, True, True, "one-with-none"),
    "none-held": (16, 2, 20, 5, 5, 0, True, False, "none-held"),
    "three-tokens": (3, 2, 8, 8, 0, 2, True, False, "uniform"),
}


def _route(jax, T, k, width, offset, routing):
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    weights = jax.random.uniform(keys[0], (T, k), minval=0.1, maxval=1.0)
    rng = np.random.default_rng(0)
    if routing == "uniform":
        ids = jnp.argsort(jax.random.uniform(keys[1], (T, width)), axis=-1)[:, :k]
    elif routing == "skewed":  # expert 1 takes every token, the second choice is spread
        ids = jnp.stack([jnp.full((T,), 1), jnp.asarray(rng.choice([0, 2, 3], T))], axis=1)
    elif routing == "all-to-one":
        ids = jnp.full((T, k), 2)
    elif routing == "one-with-none":  # expert 3 idles
        ids = jnp.asarray(np.stack([rng.permutation([0, 1, 2, 4, 5])[:k] for _ in range(T)]))
    else:  # none-held: every pair on experts another chip holds
        ids = jnp.broadcast_to(jnp.asarray([0, 12]), (T, k))
    return ids.astype(jnp.int32), weights


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_gives_the_loops_sum_and_counts(jax, case):
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    T, k, width, held, offset, layers, int8, masked, routing = CASES[case]
    D, F = 32, 64
    stacks = _stacks(jax, layers=layers, held=held, D=D, F=F, int8=int8)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D))
    ids, weights = _route(jax, T, k, width, offset, routing)
    mask = (jnp.arange(T) < T - 7) if masked else None
    layer = jnp.int32(layers - 1) if layers else None

    def run(scan):
        return jax.jit(lambda *a: moe.moe_swiglu_sparse(
            *a, expert_offset=offset, token_mask=mask, layer=layer, scan=scan, tile=16,
        ))(*stacks, x, ids, weights)

    with jax.default_matmul_precision("highest"):
        got, counts = run("pallas")
        loop, loop_counts = run("xla")
        want = _every_held_expert(jax, stacks, layer, x, ids, weights, offset, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(loop), atol=ATOL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    assert counts.tolist() == loop_counts.tolist()
    n_held = int(np.asarray(
        (ids >= offset) & (ids < offset + held) & (True if mask is None else mask[:, None])
    ).sum())
    assert counts.tolist() == [n_held, (T if mask is None else T - 7) * k]
    if routing == "none-held":
        assert n_held == 0 and float(jnp.abs(got).max()) == 0.0
    if routing in ("skewed", "all-to-one"):
        assert T > 2 * 16  # one expert's pairs fill more than two tiles


@pytest.mark.parametrize("int8", [False, True], ids=["plain", "int8"])
def test_the_kernel_gives_every_experts_sum_under_the_router(jax, int8):
    """The whole routed layer (softmax, top 2 renormalised) through the
    kernel, against ``moe_swiglu_nodrop`` on the same weights."""
    from modal_examples_tpu.models import moe

    T, D, F, E, k = 96, 16, 32, 4, 2
    stacks = _stacks(jax, layers=0, held=E, D=D, F=F, int8=int8)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D))
    router = jax.random.normal(jax.random.PRNGKey(4), (D, E))
    with jax.default_matmul_precision("highest"):
        want, _ = moe.moe_swiglu_nodrop(router, *stacks, x, k)
        got, counts = moe.moe_swiglu_routed(router, *stacks, x, k, renormalize=True, scan="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    assert counts.tolist() == [T * k, T * k]


@pytest.mark.parametrize("block_f", [None, 128])
def test_tiles_past_the_live_ones_are_skipped_and_f_goes_in_blocks(jax, block_f):
    """The kernel alone: a grid of 6 tiles of which 3 are in use (two of one
    expert, one of another), F whole and in two blocks. A live tile's rows
    are its expert's SwiGLU; the sum over F blocks differs from the whole
    only by the order of float32 addition."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.layers import mm
    from modal_examples_tpu.ops.expert_swiglu import expert_swiglu

    L, E, D, F, tile, tiles, live = 2, 3, 128, 256, 16, 6, 3
    stacks = _stacks(jax, layers=L, held=E, D=D, F=F, int8=True)
    rows = jax.random.normal(jax.random.PRNGKey(5), (tiles * tile, D))
    tile_expert = jnp.asarray([2, 2, 0, 1, 1, 1], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = expert_swiglu(
            *stacks, rows, tile_expert, jnp.int32(live), jnp.int32(1), tile=tile, block_f=block_f
        )
        for i in range(live):
            one = [jax.tree.map(lambda a: a[1, int(tile_expert[i])], w) for w in stacks]
            xt = rows[i * tile:(i + 1) * tile]
            want = mm(jax.nn.silu(mm(xt, one[0])) * mm(xt, one[1]), one[2])
            np.testing.assert_allclose(
                np.asarray(got[i * tile:(i + 1) * tile]), np.asarray(want), atol=ATOL
            )
    assert got.shape == (tiles * tile, D) and got.dtype == jnp.float32


def test_the_kernels_shapes_and_blocks():
    """What Mosaic takes, and the F block an expert's three matrices get:
    LFM2's 9.4 MB whole, Mixtral's 176 MB in 28 blocks of 512."""
    import jax.numpy as jnp

    from modal_examples_tpu.ops.expert_swiglu import (
        BLOCK_BYTES, expert_swiglu_block, expert_swiglu_shapes_ok,
    )

    assert expert_swiglu_shapes_ok(2048, 1536, jnp.int8)
    assert expert_swiglu_shapes_ok(4096, 14336, jnp.int8)
    assert expert_swiglu_shapes_ok(5120, 1536, "bfloat16")
    assert not expert_swiglu_shapes_ok(2048, 1536, jnp.int4)  # the int4 control keeps the loop
    assert not expert_swiglu_shapes_ok(32, 64, jnp.float32)  # the tests' widths
    assert expert_swiglu_block(2048, 1536, jnp.int8) == 1536
    assert expert_swiglu_block(4096, 14336, jnp.int8) == 512
    assert 3 * 4096 * 512 <= BLOCK_BYTES < 3 * 4096 * 1024
    assert expert_swiglu_block(6144, 2048, jnp.int8) == 512  # GLM-5.2's
    assert expert_swiglu_block(32, 64, jnp.float32) == 64  # no lane-aligned divisor: whole


# -- who chooses ---------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend,tokens,D,F,dtype,want",
    [
        ("tpu", 64, 2048, 1536, "int8", "pallas"),  # LFM2's decode step
        ("tpu", 16, 4096, 14336, "int8", "pallas"),  # Mixtral's
        ("tpu", 16, 6144, 2048, "bfloat16", "pallas"),
        ("tpu", 127, 2048, 1536, "int8", "pallas"),
        ("tpu", 128, 2048, 1536, "int8", "xla"),  # a prefill bucket or chunk: 128-row tiles
        ("tpu", 2048, 4096, 14336, "int8", "xla"),
        ("tpu", 64, 2048, 1536, "int4", "xla"),  # a dtype the kernel refuses
        ("tpu", 4, 64, 32, "float32", "xla"),  # the tests' widths: no whole vregs
        ("cpu", 64, 2048, 1536, "int8", "xla"),  # the interpreter is no serving path
    ],
)
def test_the_form_follows_backend_call_and_shapes(jax, monkeypatch, backend, tokens, D, F, dtype, want):
    """``expert_scan_form`` chooses from what it can see; no argument or
    environment variable of the engine says otherwise."""
    from modal_examples_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe.expert_scan_form(tokens, D, F, dtype) == want


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_every_familys_plan_names_the_form(jax, monkeypatch, backend):
    """``expert_scan`` in each ``paged_impl_plan``: None for a model with no
    routed layer, the loop under tensor parallelism (the compiler partitions
    it over the experts' width; the kernel is one device's program), else
    ``expert_scan_form``'s choice at the model's widths."""
    from modal_examples_tpu.models import deepseek_v2, glm_dsa, granite_hybrid, lfm2, llama

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kernel = "pallas" if backend == "tpu" else "xla"
    mixtral = llama.LlamaConfig.mixtral_8x7b()
    assert llama.paged_impl_plan(llama.LlamaConfig.tiny(), 16)["expert_scan"] is None
    assert llama.paged_impl_plan(mixtral, 16)["expert_scan"] == kernel
    assert llama.paged_impl_plan(mixtral, 16, expert_dtype="int8")["expert_scan"] == kernel
    assert llama.paged_impl_plan(mixtral, 16, expert_dtype="int4")["expert_scan"] == "xla"
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    assert llama.paged_impl_plan(mixtral, 16, mesh=mesh)["expert_scan"] == kernel  # tp = 1
    assert deepseek_v2.paged_impl_plan(deepseek_v2.DeepseekV2Config(), 16)["expert_scan"] == kernel
    assert glm_dsa.paged_impl_plan(glm_dsa.GlmDsaConfig(), 16, expert_dtype="int8")["expert_scan"] == kernel
    assert lfm2.paged_impl_plan(lfm2.Lfm2Config(), 16)["expert_scan"] == kernel
    assert "expert_scan" not in granite_hybrid.paged_impl_plan(granite_hybrid.GraniteHybridConfig(), 16)


def test_the_engine_hands_the_plan_its_experts_dtype(jax):
    """``impl_plan["expert_scan"]`` and the info gauge's label, from the
    parameters the engine serves (here a tiny routed model on the CPU: the
    loop; a dense one: none)."""
    from modal_examples_tpu.models import llama, moe
    from modal_examples_tpu.models.quantize import quantize_llama
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving import LLMEngine
    from modal_examples_tpu.utils.prometheus import default_registry

    cfg = llama.LlamaConfig.tiny_moe()
    params = quantize_llama(llama.init_params(jax.random.PRNGKey(0), cfg))
    assert str(moe.expert_dtype(params)) == "int8"
    assert moe.expert_dtype(llama.init_params(jax.random.PRNGKey(0), llama.LlamaConfig.tiny())) is None
    eng = LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,))
    try:
        assert eng.impl_plan["expert_scan"] == "xla"
        assert "xla" in [labels["expert_scan"] for labels, _ in default_registry.series(C.DECODE_IMPL)]
    finally:
        eng.stop()
