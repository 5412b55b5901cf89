import re

import pytest

from .helpers import GIB, _kernel_scopes, _nbytes, _relaid_out


# -- SmallThinker-21BA3B's first 16 layers at their published widths (PR 41) -----------------


@pytest.fixture(scope="module")
def smallthinker(one_chip):
    """The engine's decode block and a chunk call for the benchmark's
    configuration (32 slots, 16384 pages of 16 for the 4 global layers, 8225
    for the 12 window layers' rings, 8192 positions, every expert of 16
    layers in int8, the head bf16), as shapes on the described chip: nothing
    is allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import smallthinker as L
    from modal_examples_tpu.models.quantize import quantize_llama
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = L.SmallThinkerConfig.from_hf_config(
        "benchmarks/serving/configs/smallthinker-21b-a3b-int8-1chip.json"
    )
    slots, n_pages, n_window_pages, page_size, pages_per_slot, ring = 32, 16384, 8225, 16, 512, 257
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    plain = jax.eval_shape(lambda k: L.init_params(k, cfg), jax.random.PRNGKey(0))
    quantised = jax.eval_shape(
        lambda k: quantize_llama(L.init_params(k, cfg), cfg.quant_targets), jax.random.PRNGKey(0)
    )
    quantised["lm_head"] = plain["lm_head"]  # the benchmark's tree keeps the head bf16
    params = jax.tree.map(lambda a: S(a.shape, a.dtype), quantised)
    pages = S((cfg.n_cache_layers, n_pages, page_size, 4, 128), jnp.bfloat16)
    ring_pages = S((12, n_window_pages, page_size, 4, 128), jnp.bfloat16)
    state = (ring_pages, ring_pages)
    eng = object.__new__(LLMEngine)  # the two program bodies, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = L, cfg, None, "flash"
    eng.paged_impl, eng.scatter_impl = None, "xla"
    eng._block_counts, eng.decode_block = ("expert_tile_rows",), 8
    eng._runtime_offset, eng._chunk_jits = True, {}
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    key = S((2,), jnp.uint32)

    def block():
        B = slots
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), key, f32(B), f32(B), i32(B), i32(B),
            state=state, window_tables=i32(B, ring),
        ).compile()

    def chunk(prefix):
        return eng._chunk_jit(prefix).lower(
            params, i32(1, 2048), pages, pages, i32(1, pages_per_slot), i32(1),
            key, f32(1), f32(1), i32(1), i32(1), i32(1), state=state,
            slot_ids=i32(1), q_offset=S((), jnp.int32), window_tables=i32(1, ring), cfg=cfg,
        ).compile()

    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"cfg": cfg, "block": block, "chunk": chunk,
               "weight_bytes": sum(_nbytes(a) for a in jax.tree.leaves(params)),
               "page_bytes": 2 * _nbytes(pages), "ring_bytes": 2 * _nbytes(ring_pages)}
    finally:
        jax.default_backend = backend


def test_smallthinker_decode_block_reads_both_page_groups_in_place_on_a_v5e(smallthinker):
    """The 32-slot decode block beside 6.3 GiB of weights and 5 GiB of pages
    in two groups: both groups aliased in and out, no temporary a copy of a
    paged leaf or of a layer's experts (``[64, 2560, 768]`` int8 is 126 MB a
    matrix). With nothing set the plan picks the ragged kernel's all-heads
    form for 4 K/V heads of 128 on the chip, in both groups (PR 42): the
    scan's body is one period of four layers with eight Mosaic calls, four
    grouped matmuls under ``mtpu.expert_scan``, one attention under
    ``mtpu.attention`` and three under ``mtpu.window_attention``. **The
    view is free**: a leaf ``[L, P, 16, 4, 128]`` (tiles of 4 rows) reaches
    the kernel as ``[L, P, 64, 128]`` rows (tiles of 8) by a ``bitcast``,
    no ``copy`` of either shape; the loop's gathered chunk ``[512 = 32 slots
    x 16 pages, 16, 4, 128]`` is gone with its gathers, and nothing of the
    block sits under ``mtpu.page_gather``."""
    compiled = smallthinker["block"]()
    mem = compiled.memory_analysis()
    assert 6.2 * GIB < smallthinker["weight_bytes"] < 6.4 * GIB  # 6.7 GB
    assert smallthinker["page_bytes"] == 2 * GIB and 3.0 * GIB < smallthinker["ring_bytes"] < 3.1 * GIB
    assert mem.alias_size_in_bytes >= smallthinker["page_bytes"] + smallthinker["ring_bytes"]
    assert mem.temp_size_in_bytes < 0.25 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 11.6 * GIB
    text = compiled.as_text()
    for leaf, rows in (
        ("bf16[4,16384,16,4,128]", "bf16[4,16384,64,128]"),
        ("bf16[12,8225,16,4,128]", "bf16[12,8225,64,128]"),
    ):
        assert leaf in text and f" copy({leaf}" not in text
        assert not _relaid_out(text, leaf) and not _relaid_out(text, rows)
        assert re.search(re.escape(rows) + r"\{[^}]*T\(8,128\)\(2,1\)\} bitcast\(", text)
    assert "bf16[512,16,4,128]" not in text  # the loop's gathered chunk
    assert "s8[16,64,2560,768]" in text  # the whole stack, an argument
    assert not re.search(r"s8\[(1,)?64,2560,768\]", text)  # never a layer's slice of it
    kernels = [k.rpartition("closed_call/")[2] for k in _kernel_scopes(text)]
    assert sorted(kernels) == sorted(
        ["mtpu.expert_scan/pallas_call"] * 4 + ["mtpu.attention/pallas_call"]
        + ["mtpu.window_attention/pallas_call"] * 3
    )
    assert "mtpu.page_gather" not in text


def test_smallthinker_third_chunk_call_compiles_under_the_window_on_a_v5e(smallthinker):
    """The cell's third chunk call (2048 rows at a run-time offset over a
    prefix bucket of 4096): a global layer's flash call over 6144 keys with
    ``k_first`` in SMEM, a window layer's over a window's worth of the ring
    and the chunk with a k grid that starts late; both groups written in
    place."""
    compiled = smallthinker["chunk"](4096)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= smallthinker["page_bytes"] + smallthinker["ring_bytes"]
    assert mem.temp_size_in_bytes < 1 * GIB
    text = compiled.as_text()
    assert "mtpu.window_attention" in text and "mtpu.attention" in text
    assert "mtpu.page_gather" in text and "mtpu.expert_scan" in text
    for leaf in ("bf16[4,16384,16,4,128]", "bf16[12,8225,16,4,128]"):
        assert f" copy({leaf}" not in text
