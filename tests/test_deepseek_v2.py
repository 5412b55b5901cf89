"""DeepSeek-V2 on the engine's normal path, at a tiny size on the CPU.

Seeded weights in float32 throughout, so every tolerance below is float32
rounding: the program reorders sums the reference takes in one piece (an
online softmax over chunks, ``W_kvb`` absorbed into the query instead of
applied to the keys, the routed sum taken tile by tile), and with logits of
size ~4 that is a few 1e-6; ``ATOL`` leaves it two orders of room and is four
orders under what bf16 would give (~2e-2).
"""

import json
import re
import sys
from pathlib import Path

import chunk_tail
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
ATOL = 2e-4


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def ds():
    from modal_examples_tpu.models import deepseek_v2

    return deepseek_v2


@pytest.fixture(scope="module")
def ref():
    from modal_examples_tpu.models import deepseek_v2_reference

    return deepseek_v2_reference


def _tiny(ds, **kw):
    return ds.DeepseekV2Config.tiny(dtype="float32", **kw)


@pytest.fixture(scope="module")
def model(jax, ds):
    """(cfg, params) of a share: experts 4..11 of the router's 16."""
    cfg = _tiny(ds, n_held_experts=8, expert_offset=4)
    return cfg, ds.init_params(jax.random.PRNGKey(0), cfg)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, size=n)


def _cache(jax, cfg, n_pages=16, page_size=16):
    import jax.numpy as jnp

    return tuple(
        jnp.zeros((cfg.n_layers, n_pages, page_size, *leaf), jnp.float32)
        for leaf in cfg.cache_leaf_shapes
    )


def _highest(jax):
    return jax.default_matmul_precision("highest")


# -- yarn and the softmax scale, by hand ----------------------------------------

PUBLISHED_YARN = {
    "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
    "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
}


@pytest.mark.parametrize("index,want", [
    (0, 1.0),  # below the low correction dim (10): the frequency as it is
    (10, 1e4 ** (-20 / 64)),
    (16, 1e4 ** (-32 / 64) * ((1 - 6 / 13) + (6 / 13) / 40)),  # ramp (16-10)/(23-10)
    (23, 1e4 ** (-46 / 64) / 40),  # at the high one (23): divided by the factor
    (31, 1e4 ** (-62 / 64) / 40),
])
def test_yarn_frequencies_by_hand(ref, index, want):
    got = ref.yarn_inv_freq(64, 10000.0, PUBLISHED_YARN)
    assert len(got) == 32
    assert got[index] == pytest.approx(want, rel=1e-12)


def test_softmax_scale_and_mscale_by_hand(ds, ref):
    cfg = ds.DeepseekV2Config(rope_scaling=tuple(sorted(PUBLISHED_YARN.items())))
    assert ref.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=5e-5)
    assert cfg.softmax_scale == pytest.approx(0.11472, abs=5e-6)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert ref.rope_mscale(PUBLISHED_YARN) == 1.0  # cos and sin are not scaled
    assert ds.DeepseekV2Config().softmax_scale == pytest.approx(192 ** -0.5)  # no yarn


# -- the program against the plain reference ---------------------------------------


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("share", [(16, 0), (8, 4), (4, 12)], ids=["uncut", "half", "quarter"])
def test_full_forward_matches_the_reference(jax, ds, ref, attn_impl, share):
    import jax.numpy as jnp

    cfg = _tiny(ds, n_held_experts=share[0], expert_offset=share[1])
    params = ds.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens(48)
    with _highest(jax):
        want, _margin = ref.forward(params, toks, cfg)
        got = ds.forward(params, jnp.asarray(toks)[None], cfg, attn_impl=attn_impl)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


@pytest.mark.parametrize("n_prompt", [5, 16, 30])
def test_prefill_then_absorbed_decode_matches_the_full_forward(jax, ds, ref, model, n_prompt):
    """Prefill expands the latents, decode absorbs ``W_kvb`` and attends
    over the latents themselves: both read one cache and give one answer."""
    import jax.numpy as jnp

    cfg, params = model
    toks = _tokens(n_prompt + 12, seed=n_prompt)
    kp, vp = _cache(jax, cfg)
    table = jnp.asarray([[3, 1, 4, 2]])
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n_prompt] = toks[:n_prompt]
    with _highest(jax):
        want, _ = ref.forward(params, toks, cfg)
        logits, kp, vp = ds.prefill(
            params, jnp.asarray(padded), kp, vp, table, jnp.asarray([n_prompt]), cfg
        )
        np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[n_prompt - 1]), atol=ATOL)
        for t in range(n_prompt, n_prompt + 12):
            logits, kp, vp, counts = ds.decode_step(
                params, jnp.asarray([toks[t]]), jnp.asarray([t]), kp, vp, table,
                jnp.asarray([True]), cfg, return_counts=True,
            )
            np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[t]), atol=ATOL)
            held, pairs = (int(c) for c in counts)
            assert pairs == cfg.top_k_experts * cfg.n_moe_layers and 0 <= held <= pairs


@pytest.mark.parametrize("offset,tail", [(32, 16), (32, 1), (64, 20)])
def test_a_chunk_over_cached_latents_matches_the_full_forward(jax, ds, ref, model, offset, tail):
    """A chunk at an offset attends to the prefix's *cached* latents,
    expanded again by ``W_kvb``."""
    import jax.numpy as jnp

    cfg, params = model
    toks = _tokens(offset + tail, seed=offset + tail)
    kp, vp = _cache(jax, cfg)
    table = jnp.asarray([[5, 6, 7, 8, 9, 10]])
    with _highest(jax):
        want, _ = ref.forward(params, toks, cfg)
        for start in range(0, offset, 32):
            _lg, kp, vp = ds.prefill_chunk(
                params, jnp.asarray(toks[None, start:start + 32]), kp, vp, table,
                jnp.asarray([32]), cfg, q_offset=start,
            )
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :tail] = toks[offset:]
        logits, kp, vp = ds.prefill_chunk(
            params, jnp.asarray(chunk), kp, vp, table, jnp.asarray([tail]), cfg, q_offset=offset
        )
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[-1]), atol=ATOL)


# -- the router --------------------------------------------------------------------


def _dense_weights(weights, ids, width):
    out = np.zeros((ids.shape[0], width), np.float32)
    np.put_along_axis(out, np.asarray(ids), np.asarray(weights), axis=1)
    return out


@pytest.mark.parametrize("case", ["random", "near-ties", "exact-ties"])
@pytest.mark.parametrize("groups", [(8, 3), (4, 2), (1, 1)], ids=["8of3", "4of2", "plain"])
def test_the_router_matches_the_reference(jax, ds, ref, groups, case):
    """Groups by their best expert, the best groups kept, top-k of what is
    left, the softmax's own values times the scale: not renormalised."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    n_group, topk_group = groups
    cfg = _tiny(ds, n_routed_experts=160, n_held_experts=160, n_group=n_group,
                topk_group=topk_group, top_k_experts=6, routed_scaling_factor=16.0)
    logits = jax.random.normal(jax.random.PRNGKey(4), (64, 160))
    if case == "near-ties":
        logits = jnp.round(logits * 4) / 4 + 1e-6 * jax.random.normal(
            jax.random.PRNGKey(5), logits.shape
        )
    elif case == "exact-ties":
        logits = jnp.round(logits * 2) / 2
    scores = jax.nn.softmax(logits, axis=-1)
    want_w, want_ids, margin = ref.route(scores, cfg)
    got_w, got_ids = moe.route_group_limited(
        scores, 6, n_group=n_group, topk_group=topk_group, scale=16.0
    )
    np.testing.assert_array_equal(
        _dense_weights(got_w, got_ids, 160), _dense_weights(want_w, want_ids, 160)
    )
    # unnormalised: the weights are the scores themselves, times 16
    np.testing.assert_allclose(
        np.asarray(got_w), 16.0 * np.take_along_axis(np.asarray(scores), np.asarray(got_ids), 1),
        rtol=1e-6,
    )
    assert float(jnp.sum(got_w, axis=-1).max()) < 16.0
    if n_group > 1:  # every chosen expert lies in one of the kept groups
        per_token = [len(set(row // (160 // n_group))) for row in np.asarray(got_ids)]
        assert max(per_token) <= topk_group
    assert np.all(np.asarray(margin) >= 0)
    if case == "exact-ties":
        assert float(jnp.min(margin)) == 0.0


def test_the_four_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer(jax, ds, ref):
    """``model-configs`` section 4: what every share computes of the routed
    sum, with what each computes alike (the shared experts) counted once,
    is the whole layer."""
    import jax.numpy as jnp

    whole = _tiny(ds)
    params = ds.init_params(jax.random.PRNGKey(2), whole)
    layer = jax.tree.map(lambda a: a[0], params["moe_layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (24, whole.dim))
    with _highest(jax):
        want, _ = ref.routed_mlp(h, layer, whole)
        shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        total = jnp.zeros_like(want)
        held = 0
        for offset in range(0, 16, 4):
            cfg = _tiny(ds, n_held_experts=4, expert_offset=offset)
            part = dict(layer, **{
                n: layer[n][offset:offset + 4] for n in ("moe_gate", "moe_up", "moe_down")
            })
            out, counts = ds._mlp(part, h, cfg, False, None)
            total = total + out - shared
            held += int(counts[0])
            assert int(counts[1]) == 24 * whole.top_k_experts
        total = total + shared
    assert held == 24 * whole.top_k_experts  # every pair lands in exactly one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=ATOL)


# -- the sparse dispatch --------------------------------------------------------------


def _every_expert(wg, wu, wd, x, ids, weights, offset):
    import jax
    import jax.numpy as jnp

    out = jnp.zeros((x.shape[0], wd.shape[-1]), jnp.float32)
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), axis=-1)
        out = out + w[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("tile", [None, 8])
@pytest.mark.parametrize("routing", ["uniform", "all-to-one", "one-with-none", "none-held"])
def test_sparse_dispatch_equals_every_expert_evaluation(jax, routing, tile):
    """Exact under skew: no capacity, so an expert every token chose gets
    them all (several tiles), and one no token chose is never run."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    T, D, F, E, width, k, offset = 40, 32, 48, 5, 20, 3, 5
    keys = jax.random.split(jax.random.PRNGKey(6), 6)
    wg = jax.random.normal(keys[0], (E, D, F)) * D ** -0.5
    wu = jax.random.normal(keys[1], (E, D, F)) * D ** -0.5
    wd = jax.random.normal(keys[2], (E, F, D)) * F ** -0.5
    x = jax.random.normal(keys[3], (T, D))
    weights = jax.random.uniform(keys[4], (T, k), minval=0.1, maxval=1.0)
    if routing == "uniform":
        ids = jnp.argsort(jax.random.uniform(keys[5], (T, width)), axis=-1)[:, :k]
    elif routing == "all-to-one":  # expert 7 (held) takes every token
        ids = jnp.broadcast_to(jnp.asarray([7, 0, 19]), (T, k))
    elif routing == "one-with-none":  # held experts 5, 6, 8, 9 only: 7 idles
        ids = jnp.asarray(np.random.default_rng(0).choice([5, 6, 8, 9, 1, 15], (T, k)))
    else:
        ids = jnp.broadcast_to(jnp.asarray([0, 1, 12]), (T, k))
    ids = ids.astype(jnp.int32)
    mask = jnp.arange(T) < 33  # the last tokens are padding: not computed, not counted
    with _highest(jax):
        got, counts = jax.jit(
            lambda *a: moe.moe_swiglu_sparse(*a, expert_offset=offset, token_mask=mask, tile=tile)
        )(wg, wu, wd, x, ids, weights)
        want = _every_expert(wg, wu, wd, x, ids, weights, offset) * mask[:, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    held = np.asarray((ids >= offset) & (ids < offset + E) & mask[:, None]).sum()
    assert [int(c) for c in counts] == [held, 33 * k]
    if routing == "none-held":
        assert held == 0 and float(jnp.abs(got).max()) == 0.0


# -- the cache ------------------------------------------------------------------------


def test_the_latent_cache_is_1152_bytes_a_token_and_layer(jax, ds):
    """Per token and layer: one latent of 512 and one rotated key of 64 in
    bf16, whatever the head count; both leaves keep their page axis at 1."""
    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    cfg = ds.DeepseekV2Config(n_layers=8, n_held_experts=40)
    assert cfg.cache_leaf_shapes == ((1, 512), (1, 64))
    cache = PagedKVCache.create(
        n_layers=cfg.n_layers, leaf_shapes=cfg.cache_leaf_shapes, n_pages=3, page_size=16,
        prefer_native=False,
    )
    assert cache.k_pages.shape == (8, 3, 16, 1, 512)
    assert cache.v_pages.shape == (8, 3, 16, 1, 64)
    assert cache.k_pages.shape[1] == cache.v_pages.shape[1] == cache.n_pages == 3
    assert cache.bytes() == 1152 * cfg.n_layers * 3 * 16
    per_head = 2 * cfg.n_heads * (cfg.qk_head_dim + cfg.v_head_dim)
    assert per_head == 81920  # what per-head K and V would take
    # the symmetric pair of every other model is unchanged
    plain = PagedKVCache.create(
        n_layers=2, n_kv_heads=2, head_dim=8, n_pages=3, page_size=16, prefer_native=False
    )
    assert plain.k_pages.shape == plain.v_pages.shape == (2, 3, 16, 2, 8)


def test_llama_declares_its_symmetric_leaves_and_its_module():
    from modal_examples_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    assert cfg.model is llama
    assert cfg.cache_leaf_shapes == ((2, 32), (2, 32))
    assert "moe_down" in cfg.quant_targets


# -- the engine, end to end -------------------------------------------------------------


def _engine(ds, cfg, params, **kw):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("prefill_buckets", (32,))
    return LLMEngine(
        cfg, params, max_slots=2, max_model_len=128, page_size=16, kv_dtype=jnp.float32,
        seed=0, **kw,
    )


def _served(eng, text, n=10):
    from modal_examples_tpu.serving import SamplingParams

    req = eng.submit(text, SamplingParams(max_tokens=n, temperature=0.0))
    "".join(eng.stream(req))
    return list(req.prompt_tokens), list(req.generated_tokens)


PROMPTS = {
    "short": "latent attention",  # one bucketed prefill call
    "chunked": "a document long enough to need a second chunk over cached latents",
}


@pytest.mark.parametrize("prefix_cache", [True, False], ids=["prefix-cache", "no-prefix-cache"])
@pytest.mark.parametrize("prompt", list(PROMPTS))
def test_engine_serves_the_references_argmax(jax, ds, ref, model, prompt, prefix_cache):
    """``LLMEngine`` end to end: greedy tokens are the reference's first
    choice wherever it is decided (its lead over the runner-up more than
    rounding could close). With the prefix cache on, the same prompt a
    second time runs over shared latent pages and serves the same tokens."""
    cfg, params = model
    eng = _engine(ds, cfg, params, enable_prefix_cache=prefix_cache)
    try:
        prompt_ids, served = _served(eng, PROMPTS[prompt])
        again_ids, again = _served(eng, PROMPTS[prompt])
        if prompt == "chunked":
            assert len(prompt_ids) > 32  # a chunk at offset 32 over cached latents
        assert (again_ids, again) == (prompt_ids, served)
        if prefix_cache and len(prompt_ids) >= 16:
            assert eng.prefix_cache.hits >= 1
    finally:
        eng.stop()
    assert len(served) == 10
    with _highest(jax):
        logits, _ = ref.forward(params, np.asarray(prompt_ids + served[:-1]), cfg)
    rows = np.asarray(logits)[len(prompt_ids) - 1:]
    top2 = np.sort(rows, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 10 * ATOL
    assert decided.sum() >= 8
    assert [int(t) for t in rows.argmax(-1)[decided]] == [
        t for t, d in zip(served, decided) if d
    ]


def test_the_engines_cache_and_counters_are_the_latent_ones(jax, ds, model):
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving.engine import MODEL_PRESETS
    from modal_examples_tpu.utils.prometheus import default_registry

    def value(name, **labels):
        return default_registry.value(name, labels or None) or 0.0

    cfg, params = model
    before = {
        "held": value(C.ROUTED_PAIRS_TOTAL, where="held"),
        "elsewhere": value(C.ROUTED_PAIRS_TOTAL, where="elsewhere"),
        "computed": value(C.PREFILL_POSITIONS_TOTAL, kind="computed"),
    }
    eng = _engine(ds, cfg, params)
    try:
        assert eng.cache.k_pages.shape[3:] == (1, cfg.kv_lora_rank)
        assert eng.cache.v_pages.shape[3:] == (1, cfg.qk_rope_head_dim)
        assert eng.impl_plan["attention"] == "xla-gather"
        assert MODEL_PRESETS["tiny-deepseek-v2"]().model is ds
        prompt_ids, _ = _served(eng, PROMPTS["chunked"], n=12)
    finally:
        eng.stop()
    held = value(C.ROUTED_PAIRS_TOTAL, where="held") - before["held"]
    elsewhere = value(C.ROUTED_PAIRS_TOTAL, where="elsewhere") - before["elsewhere"]
    # whole decode blocks of one live slot: 8 steps x 2 routed layers x 3 experts a token
    per_step = cfg.n_moe_layers * cfg.top_k_experts
    assert held + elsewhere >= 8 * per_step and (held + elsewhere) % per_step == 0
    assert 0 < held < held + elsewhere  # experts 4..11 of 16 are held
    # the chunk calls at offsets 32, 64, ... each attended to that many cached positions:
    # a program an offset, so the dispatches' shape keys name them, and each computed 32
    offsets = range(32, len(prompt_ids), 32)
    built = [r["shape_key"] for r in eng.profiler.perfetto_snapshot()["compiles"]
             if r["program"] == "prefill_chunk"]
    dispatched = sorted(int(re.fullmatch(r"off(\d+)w32", key).group(1)) for key in built)
    assert dispatched == [0, *offsets] and sum(dispatched) == sum(offsets) > 0
    computed = value(C.PREFILL_POSITIONS_TOTAL, kind="computed") - before["computed"]
    assert computed == 32 * len(dispatched)


@pytest.fixture(scope="module")
def tail_engine(jax, ds, model):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    eng = LLMEngine(
        cfg, params, max_slots=2, max_model_len=chunk_tail.MAX_MODEL_LEN, page_size=16,
        kv_dtype=jnp.float32, seed=0, prefill_buckets=chunk_tail.BUCKETS,
    )
    yield chunk_tail.warmed(eng)
    eng.stop()


@pytest.mark.parametrize("case", list(chunk_tail.CASES))
def test_the_tail_chunk_over_cached_latents_is_as_wide_as_what_is_left(tail_engine, case, monkeypatch):
    """The last chunk expands the cached latents again whatever its width;
    its own rows are the bucket that holds what is left (tests/chunk_tail.py)."""
    chunk_tail.check(tail_engine, case, monkeypatch)


REFUSED = {
    "int8 KV cache": dict(kv_dtype="int8"),
    "speculative decoding": dict(speculative=("ngram", 2)),
    "tensor parallelism": "mesh",
    "vision": dict(vision=(object(), None)),
    "disaggregated transfer": dict(tiered_prefix=True),
    "a Pallas paged_impl or scatter_impl": dict(paged_impl="pallas"),
}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_each_feature_the_model_lacks_is_refused_by_name(jax, ds, model, feature):
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    kw = REFUSED[feature]
    if kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tensor",)))
    assert feature in cfg.unsupported
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,), **kw)


def test_disaggregated_roles_lora_and_a_checkpoint_are_refused_too(jax, ds, model):
    import jax.numpy as jnp

    from modal_examples_tpu.scheduling.router import EngineReplica

    cfg, params = model
    eng = _engine(ds, cfg, params)
    try:
        with pytest.raises(NotImplementedError, match="disaggregated transfer"):
            EngineReplica(eng, "p0", role="prefill")
        with pytest.raises(NotImplementedError, match="disaggregated transfer"):
            eng.prefill_sync(eng.make_request("x"))
        assert EngineReplica(eng, "u0").role == "unified"
    finally:
        eng.stop()
    with pytest.raises(NotImplementedError, match="LoRA"):
        ds.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, lora={})
    with pytest.raises(NotImplementedError, match="checkpoint"):
        ds.load_hf_weights("/nowhere", cfg)
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        ds.partition_specs(cfg)


# -- int8 targets --------------------------------------------------------------------------


def test_every_matmul_leaf_is_an_int8_target(jax, ds, model):
    """A test counts them: every stacked matrix of the tree but the router
    (kept high precision, like the norms) is quantised, and nothing else."""
    from modal_examples_tpu.models.quantize import (
        DEEPSEEK_V2_TARGETS, QuantizedWeight, quantize_llama,
    )

    cfg, params = model
    assert cfg.quant_targets is DEEPSEEK_V2_TARGETS and len(DEEPSEEK_V2_TARGETS) == 14
    quantized = quantize_llama(params, cfg.quant_targets, bits=8)
    seen = set()
    for stack in ("dense_layers", "moe_layers"):
        for name, leaf in params[stack].items():
            is_matrix = leaf.ndim >= 3
            got = isinstance(quantized[stack][name], QuantizedWeight)
            assert got == (is_matrix and name != "router"), (stack, name)
            if got:
                seen.add(name)
    assert seen == set(DEEPSEEK_V2_TARGETS)
    assert isinstance(quantized["lm_head"], QuantizedWeight)
    assert not isinstance(quantized["embed"], QuantizedWeight)
    # and the int8 tree serves: the engine quantises a given tree itself
    from modal_examples_tpu.serving import LLMEngine

    eng = LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,),
                    quantization="int8", seed=0)
    try:
        assert isinstance(eng.params["moe_layers"]["wkv_b"], QuantizedWeight)
        _ids, served = _served(eng, "int8 weights", n=4)
        assert len(served) == 4
    finally:
        eng.stop()


# -- the two copies of the reference ----------------------------------------------------------

TINY_JSON = {
    "name": "tiny-deepseek-v2", "family": "deepseek_v2", "model_type": "deepseek_v2",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "expert_share": {"of": 16, "offset": 4, "chips_per_layer": 4}, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 4.0,
    "norm_topk_prob": False, "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "vocab_size": 512, "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": dict(PUBLISHED_YARN, original_max_position_embeddings=64),
    "quantization": "int8", "kv_dtype": "bfloat16",
}


@pytest.mark.parametrize("seed", [11, 2**31 + 5], ids=["seed-11", "seed-over-31-bits"])
def test_the_two_copies_of_the_reference_agree(jax, ds, ref, tmp_path, seed):
    """``benchmarks/serving/families/deepseek_v2.py`` imports nothing from
    the program; on its own seeded tree it gives what the repo's copy gives
    (float32 both, ``highest``: equal to rounding), margins included. Its
    int4 control is another, coarser model: the logits move by whole tenths.
    (The control is not compared bit for bit: int4 rounding has exact ties,
    ``q / 18`` where a column's largest ``|q|`` is 126, which fall either
    way with the order XLA takes the division in.)"""
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "benchmarks" / "serving"))
    import manifest as M

    from modal_examples_tpu.models.quantize import QuantizedWeight

    family = M.load_family(TINY_JSON)
    dims = family.dims_of(TINY_JSON)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY_JSON))
    cfg = family.program_config(str(path))
    assert (cfg.n_routed_experts, cfg.n_held_experts, cfg.expert_offset) == (16, 4, 4)
    params = jax.tree.map(
        lambda leaf: QuantizedWeight(**leaf) if isinstance(leaf, dict) else leaf,
        family.make_tree(seed, dims),
        is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "scale"},
    )
    toks = _tokens(40, seed=3).tolist()
    rows = list(range(20, 40))
    logits, margins, clock = family.logits_at(seed, dims, [np.asarray(toks)], [rows], 8)
    want, want_margin = ref.forward(params, np.asarray(toks), cfg)
    np.testing.assert_allclose(logits[0], np.asarray(want)[rows], atol=ATOL)
    np.testing.assert_allclose(margins[0], np.asarray(want_margin)[rows], atol=1e-4)
    assert set(clock) == {"weights_s", "layers_s"} and np.isfinite(margins[0]).all()
    assert jnp.abs(want).max() > 1.0  # logits of unit size, not a vanishing model
    control, _m, _c = family.logits_at(seed, dims, [np.asarray(toks)], [rows], 4)
    assert np.abs(control[0] - logits[0]).mean() > 0.05
