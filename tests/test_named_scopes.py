"""The names a device trace shows: every program the engine jits has a
stable name (none is ``jit__unknown``), and the operations inside carry the
``jax.named_scope`` of the layer's part they belong to (ops/scopes.py).
Lowered on the CPU at a tiny size; nothing runs."""

import re

import pytest

from modal_examples_tpu.ops import scopes


@pytest.fixture(scope="module", params=["dense", "moe"])
def engine(request):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    cfg = (
        llama.LlamaConfig.tiny() if request.param == "dense"
        else llama.LlamaConfig.tiny_moe()
    )
    return LLMEngine(
        cfg, max_slots=4, max_model_len=128, prefill_buckets=(32, 64),
    )


def _lowered(jitted, *args, **kwargs):
    text = jitted.lower(*args, **kwargs).as_text(debug_info=True)
    return re.search(r"module @(\S+)", text).group(1), text


def _block_args(eng):
    import jax.numpy as jnp

    B = eng.max_slots
    return (
        eng.params, eng.cache.k_pages, eng.cache.v_pages,
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, eng.pages_per_slot), jnp.int32), jnp.zeros((B,), bool),
        eng._next_key(), jnp.ones((B,), jnp.float32),
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.full((B,), -1, jnp.int32),
    )


def _mlp_scopes(eng):
    if eng.cfg.n_experts > 0:
        # the routed layer as the serving programs run it (moe_swiglu_routed):
        # the router, the sort into tiles and the combine, the tiles' matmuls
        return {scopes.ROUTER, scopes.EXPERT_DISPATCH, scopes.EXPERT_SCAN}
    return {scopes.DENSE_MLP}


def test_decode_block_program_is_named_and_scoped(engine):
    name, text = _lowered(engine._block_jit, *_block_args(engine))
    assert name == "jit__decode_block_fn"  # what programs.json's pattern finds
    wanted = {
        scopes.PAGE_GATHER, scopes.ATTENTION, scopes.KV_SCATTER,
        scopes.SAMPLING, *_mlp_scopes(engine),
    }
    found = set(re.findall(r"mtpu\.[a-z_]+", text))
    assert wanted <= found, wanted - found


def test_the_expert_kernel_sits_under_expert_scan_and_the_layout_under_dispatch(engine, monkeypatch):
    """The decode block of the routed model as a TPU lowers it (cross-platform
    lowering: nothing compiles, nothing runs): the grouped-matmul kernel is
    one Mosaic call a routed layer scan, under ``mtpu.expert_scan``, where a
    device trace's ``expert_scan_roofline`` looks for it; the sort, the row
    gather and the combine stay under ``mtpu.expert_dispatch``; the loop's
    float32 row buffer and its update a trip are gone. The dense model's block
    holds no kernel at these widths."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # read at trace time

    def block(*args):  # a function of its own: not the CPU trace of the test above
        return engine._decode_block_fn(*args)

    text = (
        jax.jit(block).trace(*_block_args(engine)).lower(lowering_platforms=("tpu",))
        .as_text(debug_info=True)
    )
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [locs[ref] for ref in re.findall(r"@tpu_custom_call\(.*loc\((#loc\d+)\)$", text, re.M)]
    if engine.cfg.n_experts == 0:  # and a 32-wide head is not the ragged kernel's
        assert kernels == []
        return
    assert len(kernels) == 1 and kernels[0].endswith(f"{scopes.EXPERT_SCAN}/pallas_call"), kernels
    dispatch = [name for name in locs.values() if scopes.EXPERT_DISPATCH in name]
    assert any("sort" in name for name in dispatch) and any("gather" in name for name in dispatch)
    assert any("dot_general" in name for name in dispatch)  # the combine
    assert not any("dynamic_update_slice" in name for name in dispatch)  # the loop's write a trip
    T, k, E = engine.max_slots, engine.cfg.top_k_experts, engine.cfg.n_experts
    assert f"tensor<{T * k + E * 15 + 1}x{engine.cfg.dim}xf32>" not in text  # ... and its buffer


def _chunk_args(eng, width, **at):
    """A chunk call's arguments: the chunk, the pages, the table, the
    sampler's six, then what the family's program takes beside them."""
    import jax.numpy as jnp

    one = lambda dtype: jnp.ones((1,), dtype)  # noqa: E731
    return (
        eng.params, jnp.zeros((1, width), jnp.int32),
        eng.cache.k_pages, eng.cache.v_pages,
        jnp.zeros((1, eng.pages_per_slot), jnp.int32), jnp.asarray([width], jnp.int32),
        eng._next_key(), one(jnp.float32), one(jnp.float32), one(jnp.int32),
        one(jnp.int32), one(jnp.int32),
    ), {**eng._state_args([0], 1), **at, "cfg": eng.cfg}


def test_chunk_program_is_named_and_scoped(engine):
    C = engine.prefill_buckets[-1]
    args, kwargs = _chunk_args(engine, C)
    name, text = _lowered(engine._chunk_jit(C), *args, **kwargs)
    # one program per chunk offset, and "prefill" leads: the benchmark's
    # pattern for prefill programs (^jit_+prefill) finds it
    assert name == f"jit_prefill_chunk_off{C}"
    wanted = {
        scopes.PAGE_GATHER, scopes.ATTENTION, scopes.KV_SCATTER,
        scopes.SAMPLING,  # the first token comes out of the last chunk's call
        *_mlp_scopes(engine),
    }
    found = set(re.findall(r"mtpu\.[a-z_]+", text))
    assert wanted <= found, wanted - found


@pytest.mark.parametrize("family", ["glm_dsa", "smallthinker"])
def test_a_run_time_offsets_chunk_program_is_named_for_its_prefix_and_samples(family):
    """The chunk program of a family that takes the offset as an argument
    (one program a prefix bucket): the name the benchmark's readers find
    prefill programs by, and the sampler inside, as in the static form."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import glm_dsa, smallthinker
    from modal_examples_tpu.serving import LLMEngine

    cfg = (
        glm_dsa.GlmDsaConfig.tiny(n_held_experts=8, expert_offset=4) if family == "glm_dsa"
        else smallthinker.SmallThinkerConfig.tiny()
    )
    eng = LLMEngine(
        cfg, max_slots=2, page_size=8, max_model_len=64, prefill_buckets=(16,),
        prefill_batch=2, decode_block=4, kv_dtype=jnp.bfloat16, enable_prefix_cache=False,
    )
    try:
        assert eng._runtime_offset
        prefix = eng._chunk_key(32)
        args, kwargs = _chunk_args(eng, 16, q_offset=jnp.int32(32))
        name, text = _lowered(eng._chunk_jit(prefix), *args, **kwargs)
        assert name == f"jit_prefill_chunk_pre{prefix}"
        assert scopes.SAMPLING in set(re.findall(r"mtpu\.[a-z_]+", text))
    finally:
        eng.stop()


def test_bucket_prefill_program_is_named_and_scoped(engine):
    import jax.numpy as jnp

    B, bucket = engine.prefill_batch, engine.prefill_buckets[0]
    name, text = _lowered(
        engine._prefill_jit((bucket, B)),
        engine.params, engine.cache.k_pages, engine.cache.v_pages,
        jnp.zeros((B, bucket), jnp.int32),
        jnp.zeros((B, engine.pages_per_slot), jnp.int32),
        jnp.ones((B,), jnp.int32), engine._next_key(),
        jnp.ones((B,), jnp.float32), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.full((B,), -1, jnp.int32),
    )
    assert name == "jit__prefill_and_sample"
    wanted = {
        scopes.ATTENTION, scopes.KV_SCATTER, scopes.SAMPLING,
        *_mlp_scopes(engine),
    }
    found = set(re.findall(r"mtpu\.[a-z_]+", text))
    assert wanted <= found, wanted - found


def test_every_scope_is_one_the_list_names():
    assert len(set(scopes.ALL)) == len(scopes.ALL) == 16  # PR 34: mtpu.indexer, mtpu.topk_select; PR 39: mtpu.conv_mix; PR 41: mtpu.window_attention
    assert all(s.startswith("mtpu.") for s in scopes.ALL)


def test_no_engine_program_is_left_unnamed():
    """``jax.jit`` of a ``functools.partial`` or a lambda is ``jit__unknown``
    / ``jit__lambda_`` in a trace: the engine jits named functions only."""
    import ast
    from pathlib import Path

    import modal_examples_tpu.serving.engine as engine_mod

    tree = ast.parse(Path(engine_mod.__file__).read_text())
    bad = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "jit"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "jax"
            and node.args
        ):
            target = node.args[0]
            if isinstance(target, ast.Lambda) or (
                isinstance(target, ast.Call)
                and "partial" in ast.unparse(target.func)
            ):
                bad.append(f"line {node.lineno}: jax.jit({ast.unparse(target)[:40]})")
    assert not bad, bad
