"""LFM2 (``lfm2_moe``) on the engine's normal path, at a tiny size on the CPU
with seeded random weights: logits, not tokens, each tolerance with its reason.

The program (``models/lfm2.py``: a per-slot window beside a paged cache, a
prefill form and a one-token form of the gated short convolution, rotary GQA
with q/k norms, a sigmoid router with a selection bias over tiles of routed
pairs) is held to the plain reference (``models/lfm2_reference.py``: float32
``highest``, shifted sums over the whole sequence, dense attention, every
expert for every token, no cache). The tiny model has every kind of block:
two dense layers (a convolution and an attention layer), then attention and
runs of convolution over 8 experts, 2 a token.

Tolerances. With float32 weights and activations the two differ only by the
order of float32 sums and by the renormalisation's epsilon (1e-20 against
the reference's published 1e-6: 5e-7 of a weight): ``F32_ATOL`` = 2e-4 on
logits of size ~1-4 (observed <= 2e-5 through 6 layers). In bf16 (the served
precision) the rounding of weights is shared (the reference sees the bf16
values) and what is left is bf16 activations and windows: ``BF16_ATOL`` =
0.12 (observed <= 0.06 where no route flips). The controls (the router in
bf16, the weights rounded to 4 bits, the window dropped) are compared in
float32 arithmetic so that the tolerance they have to break is the tight one.
"""

import json
from pathlib import Path

import chunk_tail
import numpy as np
import pytest

F32_ATOL = 2e-4
BF16_ATOL = 0.12
ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = ROOT / "benchmarks/serving/configs/lfm2-24b-a2b-int8-1chip.json"


@pytest.fixture(scope="module")
def jax():
    import jax

    return jax


@pytest.fixture(scope="module")
def L():
    from modal_examples_tpu.models import lfm2

    return lfm2


@pytest.fixture(scope="module")
def ref():
    from modal_examples_tpu.models import lfm2_reference

    return lfm2_reference


def _params(jax, L, cfg, seed=0):
    """Seeded weights with every leaf away from its trivial value (the norms,
    the per-head q and k norms among them); the embedding stays at the
    model's own ``hidden^-0.5``, which gives logits of size 1-4 (a larger
    one makes a tied head repeat its input: the last test of the section)."""
    import jax.numpy as jnp

    params = L.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    for stack in ("conv_layers", "attention_layers", "dense_layers", "moe_layers"):
        for name in ("mixer_norm", "mlp_norm", "q_norm", "k_norm"):
            if name in params[stack]:
                leaf = params[stack][name]
                noise = 0.2 * jax.random.normal(next(keys), leaf.shape, jnp.float32)
                params[stack][name] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
    return params


@pytest.fixture(scope="module")
def model(jax, L):
    cfg = L.Lfm2Config.tiny(dtype="float32")
    return cfg, _params(jax, L, cfg)


def _ref_logits(jax, ref, params, ids, cfg):
    import jax.numpy as jnp

    return np.asarray(ref.forward(params, jnp.asarray(ids), cfg))


def _cache(jax, cfg, slots=4, n_pages=40, page_size=8, dtype=None):
    import jax.numpy as jnp

    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    return PagedKVCache.create(
        n_layers=cfg.n_cache_layers, leaf_shapes=cfg.cache_leaf_shapes, n_pages=n_pages,
        page_size=page_size, kv_dtype=dtype or jnp.float32, prefer_native=False,
        state_leaves=cfg.state_leaves, max_slots=slots,
    )


def _tables(rows, pages_per_seq=8, first=1):
    """Page tables for ``rows`` sequences: disjoint runs of pages from 1 on."""
    t = np.zeros((rows, pages_per_seq), np.int32)
    for r in range(rows):
        t[r] = first + r * pages_per_seq + np.arange(pages_per_seq)
    return t


# -- the configuration ------------------------------------------------------------------


def test_the_published_config_gives_the_published_shapes(L, tmp_path):
    """The benchmark's file (the published keys, the first 18 layers, half
    the vocabulary) and the same file uncut: the kinds of layer, the runs the
    programs scan, the leaves the cache is asked for, and the parameters."""
    cut = L.Lfm2Config.from_hf_config(PUBLISHED)
    assert (cut.dim, cut.n_heads, cut.n_kv_heads, cut.head_dim) == (2048, 32, 8, 64)
    assert (cut.ffn_dim, cut.moe_ffn_dim, cut.n_experts, cut.top_k_experts) == (11776, 1536, 64, 4)
    assert (cut.conv_L_cache, cut.n_dense_layers, cut.rope_theta) == (3, 2, 1e6)
    assert cut.n_layers == 18 and cut.n_moe_layers == 16 and cut.vocab_size == 32768
    assert [i for i, t in enumerate(cut.layer_types) if t == L.ATTENTION] == [2, 6, 10, 14]
    # (mixer, its first row, dense?, the feed-forward's first row, layers)
    assert cut.segments[:3] == (
        (L.CONV, 0, True, 0, 2), (L.ATTENTION, 0, False, 0, 1), (L.CONV, 2, False, 1, 3),
    )
    assert len(cut.segments) == 9 and sum(s[4] for s in cut.segments) == 18
    assert cut.state_leaves == ((14, (2, 2048), "bfloat16"),)  # 8 KB a layer and slot
    assert cut.kv_fold == 2 and cut.cache_leaf_shapes == ((4, 128), (4, 128))
    assert cut.n_cache_layers == 4
    assert round(cut.param_count / 1e9, 2) == 10.15  # 10.09 G int8 + 67 M of embedding: 10.2 GB
    whole = dict(json.loads(PUBLISHED.read_text()), num_hidden_layers=40, vocab_size=65536)
    (tmp_path / "config.json").write_text(json.dumps(whole))
    full = L.Lfm2Config.from_hf_config(tmp_path / "config.json")
    assert full.layer_types.count(L.ATTENTION) == 10 and full.n_moe_layers == 38
    assert round(full.param_count / 1e9, 1) == 23.8
    (tmp_path / "config.json").write_text(json.dumps(dict(whole, conv_bias=True)))
    with pytest.raises(NotImplementedError, match="conv_bias"):
        L.Lfm2Config.from_hf_config(tmp_path / "config.json")


# -- the route ------------------------------------------------------------------------------


def test_the_route_selects_by_the_biased_score_and_weighs_by_the_unbiased(jax, L, ref, model):
    """Chosen by ``s + b``, weighed by ``s`` renormalised over the chosen, in
    float32; with a bias that moves the choice at most positions, so that a
    program choosing by ``s`` alone would choose otherwise. The program's
    1e-20 against the reference's (published) 1e-6 in the renormalisation is
    5e-7 of a weight."""
    import jax.numpy as jnp

    cfg, params = model
    layer = dict(jax.tree.map(lambda a: a[1], params["moe_layers"]))
    layer["router_bias"] = 3.0 * layer["router_bias"]
    x = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        weights, ids = L.route(layer, x, cfg)
        s = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
        full = np.asarray(ref.route(layer, x, cfg))
    weights, ids = np.asarray(weights), np.asarray(ids)
    want_ids = np.argsort(-(s + np.asarray(layer["router_bias"])), axis=-1)[:, :2]
    assert (np.sort(ids, -1) == np.sort(want_ids, -1)).all()
    unbiased = np.argsort(-s, axis=-1)[:, :2]
    assert (np.sort(unbiased, -1) != np.sort(want_ids, -1)).any(axis=-1).mean() > 0.2
    chosen = np.take_along_axis(s, ids, -1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.take_along_axis(full, ids, -1), weights, rtol=2e-6)
    assert ((full > 0).sum(-1) == 2).all()


@pytest.mark.parametrize(
    "ids,mask,tile,want",
    [
        # 3 tokens x 2: experts 0 (3 pairs), 1 (2), 5 (1) -> 3 tiles of 16 rows
        ([[0, 1], [0, 1], [0, 5]], None, 16, (6, 48)),
        # the last token does not count: experts 0 (2), 1 (2)
        ([[0, 1], [0, 1], [0, 5]], [True, True, False], 16, (4, 32)),
        # 5 pairs on one expert and a tile of 4 rows: two tiles
        ([[2], [2], [2], [2], [2]], None, 4, (5, 8)),
        ([[0, 1]], [False], 16, (0, 0)),
    ],
    ids=["three-experts", "a-masked-token", "a-second-tile", "nothing-live"],
)
def test_the_tile_count_is_the_pairs_and_the_rows_of_their_tiles(jax, L, ids, mask, tile, want):
    import jax.numpy as jnp

    got = L.tile_rows(
        jnp.asarray(ids, jnp.int32), None if mask is None else jnp.asarray(mask), 8, tile
    )
    assert tuple(int(v) for v in got) == want


# -- prefill then decode through the cache, against the reference's full pass ------------


def _serve(jax, L, cfg, params, prompts, n_decode, *, bucket=32, slots=4, feed=None,
           dtype=None, drop_window=False):
    """Prefill ``prompts`` (one bucket call, rows in slots 0..), then
    ``n_decode`` decode steps feeding ``feed[row]`` (teacher forcing).
    Returns the logits [rows, 1 + n_decode, vocab] and the state.
    ``drop_window``: every decode step starts from an empty window."""
    import jax.numpy as jnp

    cache = _cache(jax, cfg, slots=slots, dtype=dtype)
    rows = len(prompts)
    toks = np.zeros((rows, bucket), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    for r, p in enumerate(prompts):
        toks[r, : len(p)] = p
    tables = _tables(slots)
    with jax.default_matmul_precision("highest"):
        logits, kp, vp, state = L.prefill(
            params, jnp.asarray(toks), cache.k_pages, cache.v_pages,
            jnp.asarray(tables[:rows]), jnp.asarray(lens), cfg, attn_impl="xla",
            state=cache.state, slot_ids=jnp.arange(rows),
        )
        out = [np.asarray(logits, np.float32)]
        active = np.zeros((slots,), bool)
        active[:rows] = True
        positions = np.zeros((slots,), np.int32)
        positions[:rows] = lens
        for step in range(n_decode):
            tok = np.zeros((slots,), np.int32)
            tok[:rows] = [feed[r][step] for r in range(rows)]
            if drop_window:
                state = jax.tree.map(jnp.zeros_like, state)
            logits, kp, vp, state = L.decode_step(
                params, jnp.asarray(tok), jnp.asarray(positions), kp, vp, jnp.asarray(tables),
                jnp.asarray(active), cfg, state=state,
            )
            out.append(np.asarray(logits, np.float32)[:rows])
            positions[:rows] += 1
    return np.stack(out, axis=1), state


def _case(seed=1, lengths=(21, 9), n=6):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, 512, size=k).tolist() for k in lengths]
    return prompts, [rng.integers(3, 512, size=n).tolist() for _ in prompts]


def test_prefill_then_decode_is_the_references_full_pass(jax, L, ref, model):
    """Two requests of different lengths in one prefill call and one decode
    batch (slots 2 and 3 empty): at every served position the logits are the
    reference's over prompt + fed tokens, to float32 rounding. The second
    prompt is shorter than its bucket and the first decode step of each reads
    the window its prefill left at the last real token."""
    cfg, params = model
    prompts, feed = _case()
    got, state = _serve(jax, L, cfg, params, prompts, 6, feed=feed)
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(got[r], want, atol=F32_ATOL)
    (windows,) = state
    assert windows.shape == (4, 4, 2, cfg.dim)  # [convolution layers, slots, K - 1, D]
    assert float(abs(windows[:, :2]).max(axis=(2, 3)).min()) > 0.0  # every layer's window of both rows
    assert float(abs(windows[:, 2:]).max()) == 0.0  # the slots no row filled


def test_a_padded_rows_window_is_its_last_real_tokens(jax, L, ref, model):
    """A row shorter than its bucket, beside a longer one: its window is the
    reference mixer's last two gated inputs of the prompt alone, layer by
    layer (the same prompt in a bucket of exactly its length), and padding
    never enters it."""
    import jax.numpy as jnp

    cfg, params = model
    rng = np.random.default_rng(2)
    short, long = rng.integers(3, 512, size=11).tolist(), rng.integers(3, 512, size=30).tolist()
    _, padded = _serve(jax, L, cfg, params, [long, short], 0)
    _, alone = _serve(jax, L, cfg, params, [short], 0, bucket=11)
    np.testing.assert_allclose(padded[0][:, 1], alone[0][:, 0], atol=1e-5)
    assert float(jnp.abs(padded[0][:, 1]).max()) > 0.01
    assert float(jnp.abs(padded[0][:, 2:]).max()) == 0.0
    # the first convolution layer's window against the reference's mixer by hand
    with jax.default_matmul_precision("highest"):
        _, _, layer = ref.layer_at(params, cfg, 0)
        x = params["embed"][jnp.asarray(short)]
        _, want = ref.conv_mixer(layer, ref.rms_norm(x, layer["mixer_norm"], cfg.norm_eps))
    np.testing.assert_allclose(padded[0][0, 1], want, atol=1e-5)
    # a prompt of one token: the window is (0, g_0)
    _, one = _serve(jax, L, cfg, params, [short[:1]], 0)
    assert float(jnp.abs(one[0][:, 0, 0]).max()) == 0.0 and float(jnp.abs(one[0][:, 0, 1]).max()) > 0


def test_a_prompt_in_two_chunk_calls_is_the_prompt_in_one(jax, L, model):
    """``prefill_chunk`` at offset 0 and at offset 16 (the window carried in
    the slot across the boundary, the attention layers over the cached
    prefix) against one call: the last logits and the window agree to float32
    rounding; a second chunk of one token reads both rows of the window."""
    import jax.numpy as jnp

    cfg, params = model
    prompt = np.random.default_rng(3).integers(3, 512, size=27).astype(np.int32)
    cache = _cache(jax, cfg)
    tables = jnp.asarray(_tables(1))
    slot = jnp.asarray([2], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for total, pieces in ((27, ((0, 16), (16, 11))), (17, ((0, 16), (16, 1)))):
            whole = np.zeros((1, 32), np.int32)
            whole[0, :total] = prompt[:total]
            want, _, _, state_one = L.prefill(
                params, jnp.asarray(whole), cache.k_pages, cache.v_pages, tables,
                jnp.asarray([total]), cfg, attn_impl="xla", state=cache.state, slot_ids=slot,
            )
            kp, vp, state = cache.k_pages, cache.v_pages, cache.state
            for offset, n in pieces:
                chunk = np.zeros((1, 16), np.int32)
                chunk[0, :n] = prompt[offset:offset + n]
                got, kp, vp, state = L.prefill_chunk(
                    params, jnp.asarray(chunk), kp, vp, tables, jnp.asarray([n]), cfg,
                    q_offset=offset, attn_impl="xla", state=state, slot_ids=slot,
                )
            np.testing.assert_allclose(got, want, atol=F32_ATOL)
            np.testing.assert_allclose(state[0], state_one[0], atol=1e-5)
            assert float(jnp.abs(state[0][:, [0, 1, 3]]).max()) == 0.0  # only slot 2 was written
    # ... and a chunk that forgot the window would not: from zeros the logits differ
    got0, *_ = L.prefill_chunk(
        params, jnp.asarray(chunk), kp, vp, tables, jnp.asarray([1]), cfg,
        q_offset=16, attn_impl="xla", state=cache.state, slot_ids=slot,
    )
    assert np.abs(np.asarray(got0) - np.asarray(want)).max() > 100 * F32_ATOL


def test_a_slot_that_is_not_active_keeps_its_window(jax, L, model):
    """Between a prefill and its first decode step (the first token not
    harvested yet) other slots' decode steps run over every row: the waiting
    slot's window stands still, and the active slot's shifts by one."""
    import jax.numpy as jnp

    cfg, params = model
    prompt = np.random.default_rng(4).integers(3, 512, size=13).tolist()
    _, state = _serve(jax, L, cfg, params, [prompt], 0)
    cache = _cache(jax, cfg)
    active = jnp.asarray([False, True, False, False])
    _, _, _, after = L.decode_step(
        params, jnp.asarray([5, 7, 0, 0]), jnp.asarray([13, 4, 0, 0]), cache.k_pages,
        cache.v_pages, jnp.asarray(_tables(4)), active, cfg, state=state,
    )
    np.testing.assert_array_equal(after[0][:, 0], state[0][:, 0])
    assert float(jnp.abs(after[0][:, 1, 1]).max()) > 0.0  # the active slot took its g
    np.testing.assert_array_equal(after[0][:, 1, 0], state[0][:, 1, 1])  # ... and shifted
    assert float(jnp.abs(after[0][:, 2:]).max()) == 0.0
    # a routed pair of a slot that is not active is not counted either
    *_, counts = L.decode_step(
        params, jnp.asarray([5, 7, 0, 0]), jnp.asarray([13, 4, 0, 0]), cache.k_pages,
        cache.v_pages, jnp.asarray(_tables(4)), active, cfg, state=state, return_counts=True,
    )
    # one live token: 2 pairs a routed layer, each alone in a tile of 16 rows
    assert [int(c) for c in counts] == [2 * cfg.n_moe_layers, 2 * 16 * cfg.n_moe_layers]


def test_in_bf16_the_program_keeps_to_the_reference(jax, L, ref):
    """The served precision: bf16 weights, activations and windows, float32
    sums and router. The reference sees the same bf16 weights in float32.
    A position where bf16 settled a near-tie of a route the other way moves
    the logits by more than rounding does and is left out (at most a few)."""
    cfg = L.Lfm2Config.tiny()
    params = _params(jax, L, cfg)
    prompts, feed = _case(seed=5)
    got, _ = _serve(jax, L, cfg, params, prompts, 6, feed=feed, dtype="bfloat16")
    errs = []
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        errs.extend(np.abs(got[r] - want).max(axis=-1))
    errs = np.sort(errs)
    assert errs[:-3].max() < BF16_ATOL and errs.max() < 1.0


def test_an_int8_tree_runs_the_quantised_tiles(jax, L, ref, model):
    """The tree as the benchmark serves it: every matmul weight a
    ``QuantizedWeight`` (the experts' stacks indexed ``[layer, expert]``
    inside the tile loop), against the reference over the dequantised tree."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import (
        QuantizedWeight, dequantize_weight, quantize_llama,
    )

    cfg, params = model
    q = quantize_llama(params, cfg.quant_targets, bits=8)
    assert isinstance(q["moe_layers"]["moe_gate"], QuantizedWeight)
    assert isinstance(q["conv_layers"]["in_proj"], QuantizedWeight)
    assert not isinstance(q["moe_layers"]["router"], QuantizedWeight)
    assert not isinstance(q["conv_layers"]["conv_w"], QuantizedWeight)
    plain = jax.tree.map(
        lambda a: dequantize_weight(a, jnp.float32) if isinstance(a, QuantizedWeight) else a,
        q, is_leaf=lambda a: isinstance(a, QuantizedWeight),
    )
    prompts, feed = _case(seed=6)
    got, _ = _serve(jax, L, cfg, q, prompts, 4, feed=feed)
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, plain, p + feed[r][:4], cfg)[len(p) - 1:]
        np.testing.assert_allclose(got[r], want, atol=F32_ATOL)


def _bf16_router(L, monkeypatch):
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    def route(layer, x, cfg):
        logits = jnp.einsum(
            "td,de->te", x.astype(jnp.bfloat16), layer["router"].astype(jnp.bfloat16)
        )
        return moe.route_group_limited(
            jax.nn.sigmoid(logits).astype(jnp.float32), cfg.top_k_experts,
            scale=cfg.routed_scaling_factor, renormalize=True, bias=layer["router_bias"],
        )

    monkeypatch.setattr(L, "route", route)


@pytest.mark.parametrize("control", ["bf16-router", "int4-weights", "dropped-window", "unbiased-route"])
def test_a_coarser_or_a_broken_computation_breaks_the_tolerance(jax, L, ref, model, control, monkeypatch):
    """What the comparison has to see: the router's scores in bf16, the
    matmul weights rounded to 4 bits, a decode step that starts from an
    empty window, a selection without the bias. Each in float32 arithmetic
    otherwise, each beyond the float32 tolerance at some served position by
    a wide factor (and the sound program, above, inside it)."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import (
        QuantizedWeight, dequantize_weight, quantize_llama,
    )

    cfg, params = model
    served, drop = params, False
    if control == "bf16-router":
        _bf16_router(L, monkeypatch)
    elif control == "int4-weights":
        served = jax.tree.map(
            lambda a: dequantize_weight(a, jnp.float32) if isinstance(a, QuantizedWeight) else a,
            quantize_llama(params, cfg.quant_targets, bits=4),
            is_leaf=lambda a: isinstance(a, QuantizedWeight),
        )
    elif control == "dropped-window":
        drop = True
    else:
        served = dict(params, moe_layers=dict(
            params["moe_layers"], router_bias=jnp.zeros_like(params["moe_layers"]["router_bias"])
        ))
    prompts, feed = _case(seed=8)
    got, _ = _serve(jax, L, cfg, served, prompts, 6, feed=feed, drop_window=drop)
    worst = 0.0
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        if control == "dropped-window":  # prefill is sound: the fault is decode's
            np.testing.assert_allclose(got[r][0], want[0], atol=F32_ATOL)
        worst = max(worst, float(np.abs(got[r] - want).max()))
    assert worst > 10 * F32_ATOL


def test_a_tied_head_does_not_repeat_the_last_token(jax, L, model):
    """PR 31's first blind check: with the output head tied to an embedding
    that is large beside the rest of the stream, every greedy answer repeats
    its last token. At the model's own scale of the embedding (``hidden^-0.5``,
    the scale the benchmark's seeded tree has) the first choice at a position
    is its own input token no more often than chance allows; six times larger,
    at half the positions."""
    import jax.numpy as jnp

    cfg, params = model
    tokens = np.random.default_rng(9).integers(3, 512, size=(4, 48))

    def repeats(tree):
        logits = np.asarray(L.forward(tree, jnp.asarray(tokens), cfg, attn_impl="xla"))
        return (logits.argmax(-1) == tokens).mean()

    assert repeats(params) < 0.05
    assert repeats(dict(params, embed=6 * params["embed"])) > 0.3


# -- the engine, end to end -----------------------------------------------------------------


def _engine(cfg, params, **kw):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("max_slots", 3)
    return LLMEngine(
        cfg, params, max_model_len=128, page_size=8, kv_dtype=jnp.float32, seed=0,
        enable_prefix_cache=False, prefill_batch=2, decode_block=4, **kw,
    )


def _submit(eng, text, n=10):
    from modal_examples_tpu.serving import SamplingParams

    return eng.submit(text, SamplingParams(max_tokens=n, temperature=0.0))


def _tokens(eng, req):
    "".join(eng.stream(req))
    return list(req.prompt_tokens), list(req.generated_tokens)


def _assert_decided_tokens_are_the_references(jax, ref, params, cfg, prompt_ids, served):
    """Greedy tokens are the reference's first choice wherever it is decided
    (its lead over the runner-up more than rounding could close)."""
    logits = _ref_logits(jax, ref, params, prompt_ids + served[:-1], cfg)[len(prompt_ids) - 1:]
    top2 = np.sort(logits, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 10 * F32_ATOL
    assert decided.sum() >= len(served) - 2
    assert [int(t) for t in logits.argmax(-1)[decided]] == [
        t for t, d in zip(served, decided) if d
    ]


@pytest.fixture(scope="module")
def tail_engine(model):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    eng = LLMEngine(
        cfg, params, max_slots=2, max_model_len=chunk_tail.MAX_MODEL_LEN, page_size=8,
        kv_dtype=jnp.float32, seed=0, enable_prefix_cache=False, decode_block=4,
        prefill_buckets=chunk_tail.BUCKETS,
    )
    yield chunk_tail.warmed(eng)
    eng.stop()


@pytest.mark.parametrize("case", list(chunk_tail.CASES))
def test_a_narrower_tail_chunk_leaves_the_window_at_the_last_real_token(tail_engine, case, monkeypatch):
    """The decode steps after a tail chunk of the bucket that holds what is
    left start from the window the chunk padded to the largest bucket left:
    the same greedy tokens (tests/chunk_tail.py)."""
    chunk_tail.check(tail_engine, case, monkeypatch)


PROMPTS = {
    "short": "a window of two",  # one bucketed call, padded
    "chunked": "a prompt long enough to need a second and a third chunk call over a carried window",
}


def test_the_engine_serves_the_references_first_choice_and_reuses_slots(jax, L, ref, model):
    """Through ``LLMEngine``: a bucketed prompt and a chunked one (three
    chunk calls, the window carried in the slot between them), five requests
    over three slots so that every slot is taken a second time (its window
    zeroed by the prefill, not inherited), requests of different lengths in
    one decode batch. A slot's second tenant is served what a fresh engine
    serves it. The engine's plan and its ``mtpu_decode_impl`` series name the
    forms that served, and the decode blocks' tile counts reach the registry
    with the blocks' tokens."""
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.utils.prometheus import default_registry

    def value(name, **labels):
        return default_registry.value(name, labels) or 0.0

    cfg, params = model
    texts = [PROMPTS["short"], PROMPTS["chunked"], "third", PROMPTS["chunked"][::-1], "fifth one"]
    before = {
        k: value(C.EXPERT_TILE_ROWS_TOTAL, kind=k) for k in ("pairs", "rows")
    }
    stepped0 = value(C.STATE_ROWS_TOTAL, kind="stepped")
    eng = _engine(cfg, params)
    try:
        assert len(eng.cache.state) == 1 and eng.cache.k_pages.shape[0] == cfg.n_cache_layers
        assert eng.cache.state[0].shape == (4, 3, 2, cfg.dim)  # [conv layers, slots, K - 1, D]
        assert default_registry.value(C.STATE_BYTES) == eng.cache.state_bytes() > 0
        assert eng.impl_plan["attention"] == "xla-gather" and eng.impl_plan["state_step"] == "xla"
        assert "xla" in [labels["state_step"] for labels, _ in default_registry.series(C.DECODE_IMPL)]
        served = [_tokens(eng, r) for r in [_submit(eng, t) for t in texts]]
    finally:
        eng.stop()
    assert not eng.error_log
    assert len(served[1][0]) > 64  # chunk calls at offsets 0, 32 and 64
    for prompt_ids, out in served:
        assert len(out) == 10
        _assert_decided_tokens_are_the_references(jax, ref, params, cfg, prompt_ids, out)
    pairs = value(C.EXPERT_TILE_ROWS_TOTAL, kind="pairs") - before["pairs"]
    rows = value(C.EXPERT_TILE_ROWS_TOTAL, kind="rows") - before["rows"]
    # a live token routes 2 pairs in each of the 4 routed layers; 3 slots fit one
    # tile of 16 rows an expert, so the rows are 16 for each expert reached
    assert pairs > 0 and pairs % (2 * cfg.n_moe_layers) == 0
    assert rows % 16 == 0 and pairs / 2 <= rows / 16 <= pairs
    assert value(C.STATE_ROWS_TOTAL, kind="stepped") - stepped0 > 0
    fresh = _engine(cfg, params)
    try:
        assert _tokens(fresh, _submit(fresh, texts[4])) == served[4]
    finally:
        fresh.stop()


def test_a_request_requeued_for_want_of_pages_is_served_as_undisturbed(jax, L, ref, model):
    """With pages for one request at a time, the second is put back in the
    queue until the first has finished, then prefilled from its first token:
    its tokens are those of a run that had the pages at once."""
    cfg, params = model
    texts = ["the first takes all the pages", "the second waits for them"]
    tight = _engine(cfg, params, n_pages=1 + 16)  # 128 positions: one request's claim
    try:
        reqs = [_submit(tight, t, n=8) for t in texts]
        got = [_tokens(tight, r) for r in reqs]
    finally:
        tight.stop()
    assert not tight.error_log
    roomy = _engine(cfg, params)
    try:
        want = [_tokens(roomy, _submit(roomy, t, n=8)) for t in texts]
    finally:
        roomy.stop()
    assert got == want
    _assert_decided_tokens_are_the_references(jax, ref, params, cfg, *got[1])


# -- what is refused, the plan, and the checkpoint -----------------------------------------

REFUSED = {
    "prefix caching": dict(enable_prefix_cache=True),
    "int8 KV cache": dict(kv_dtype="int8"),
    "speculative decoding": dict(speculative=("ngram", 2)),
    "tensor parallelism": "mesh",
    "vision": dict(vision=(object(), None)),
    "disaggregated transfer": dict(tiered_prefix=True),
    "a Pallas paged_impl or scatter_impl": dict(paged_impl="pallas"),
}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_each_feature_the_model_lacks_is_refused_by_name(jax, L, model, feature):
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    kw = REFUSED[feature]
    if kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tensor",)))
    kw = {"enable_prefix_cache": False, **kw}
    assert feature in cfg.unsupported
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,), **kw)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_plan_names_the_forms_it_picked_and_no_option_picks_another(jax, L, monkeypatch, backend):
    """``paged_impl_plan`` says what runs: the chunk loop over the pages (a
    64-wide head is not the ragged kernel's, on either backend), XLA's
    window step, and for a decode step's expert tiles the grouped-matmul
    kernel on a TPU (int8 or bf16 experts of 2048 x 1536; the int4 control's
    keep the loop) and XLA's loop on the CPU; asking for another form is
    refused by name."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = L.Lfm2Config.from_hf_config(PUBLISHED)
    plan = L.paged_impl_plan(cfg, 16)
    assert plan["attention"] == "xla-gather" and plan["scatter"] == "xla"
    assert plan["state_step"] == "xla" and plan["ragged_variant"] is None
    kernel = "pallas" if backend == "tpu" else "xla"
    assert plan["expert_scan"] == L.paged_impl_plan(cfg, 16, expert_dtype="int8")["expert_scan"] == kernel
    assert L.paged_impl_plan(cfg, 16, expert_dtype="int4")["expert_scan"] == "xla"
    assert L.paged_impl_plan(L.Lfm2Config.tiny(), 16)["expert_scan"] == "xla"  # no whole vregs
    for kw in (dict(impl="pallas"), dict(impl="ragged"), dict(scatter_impl="pallas")):
        with pytest.raises(NotImplementedError, match="a Pallas paged_impl or scatter_impl"):
            L.paged_impl_plan(cfg, 16, **kw)
    with pytest.raises(NotImplementedError, match="int8 KV cache"):
        L.paged_impl_plan(cfg, 16, kv_dtype="int8")


def test_disaggregated_roles_and_lora_are_refused_too(jax, L, model):
    import jax.numpy as jnp

    from modal_examples_tpu.scheduling.router import EngineReplica
    from modal_examples_tpu.serving.engine import MODEL_PRESETS

    cfg, params = model
    assert MODEL_PRESETS["tiny-lfm2"]().model is L
    eng = _engine(cfg, params)
    try:
        with pytest.raises(NotImplementedError, match="disaggregated transfer"):
            EngineReplica(eng, "p0", role="prefill")
        assert EngineReplica(eng, "u0").role == "unified"
    finally:
        eng.stop()
    with pytest.raises(NotImplementedError, match="LoRA"):
        L.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, lora={})
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        L.partition_specs(cfg)


def test_load_hf_weights_maps_the_published_names(jax, L, ref, model, tmp_path):
    """A made-up tiny checkpoint under the published tensor names (torch's
    ``[out, in]`` matrices, ``conv.conv.weight`` ``[dim, 1, taps]``, the
    experts one tensor each, ``in_proj`` whole) loads as the tree it was
    written from, and gives its logits; an int8 load quantises the targets."""
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    from modal_examples_tpu.models.quantize import QuantizedWeight

    cfg, params = model
    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.embedding_norm.weight": np.asarray(params["final_norm"]),
    }
    for i in range(cfg.n_layers):
        kind, dense, layer = ref.layer_at(params, cfg, i)
        for ours, a in layer.items():
            theirs = f"model.layers.{i}." + L.HF_LAYER_NAMES[ours]
            a = np.asarray(a)
            if ours == "conv_w":
                tensors[theirs] = np.ascontiguousarray(a.T[:, None, :])
            elif ours in ("moe_gate", "moe_up", "moe_down"):
                for e in range(cfg.n_experts):
                    tensors[theirs.format(e=e)] = np.ascontiguousarray(a[e].T)
            else:
                tensors[theirs] = np.ascontiguousarray(a.T) if a.ndim == 2 else a
    assert "model.layers.0.conv.in_proj.weight" in tensors
    assert "model.layers.1.self_attn.q_layernorm.weight" in tensors
    assert "model.layers.2.feed_forward.experts.7.w2.weight" in tensors
    assert "model.layers.2.feed_forward.expert_bias" in tensors
    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded = L.load_hf_weights(tmp_path, cfg, dtype="float32")
    flat, want = jax.tree.leaves_with_path(loaded), dict(jax.tree.leaves_with_path(params))
    assert len(flat) == len(want)
    for path, leaf in flat:
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want[path]), err_msg=str(path))
    ids = np.random.default_rng(11).integers(3, 512, size=12)
    np.testing.assert_allclose(
        np.asarray(L.forward(loaded, jnp.asarray(ids)[None], cfg, attn_impl="xla"))[0],
        _ref_logits(jax, ref, params, ids, cfg), atol=F32_ATOL,
    )
    q = L.load_hf_weights(tmp_path, cfg, quantization="int8")
    assert isinstance(q["moe_layers"]["moe_down"], QuantizedWeight)
    assert q["moe_layers"]["moe_down"].q.shape == (4, 8, 32, 64)
    assert q["moe_layers"]["router_bias"].dtype == jnp.float32


# -- the models there were get the programs they had ----------------------------------------

#: sha256 (16 hex) of the lowered text at the parent commit (0ea440e, PR 37):
#: ``python tests/lowered_text.py`` in a checkout of it (with this PR's
#: ``lowered_text.py``, which knows GLM's run-time chunk offset); every
#: ``chunk`` is PR 43's, whose chunk program samples
PARENT_PROGRAMS = {
    "llama": {"decode_step": "e1811a7588348e55", "block": "b9e8a274d64690dc",
              "bucket": "83033cbdc7920805", "chunk": "75df27ad29b5c8a0"},
    "deepseek_v2": {"decode_step": "47f2d3be0ccb4262", "block": "24a13b4726bd7df2",
                    "bucket": "fa5a05d32ed93dcd", "chunk": "5ad332426a8b933e"},
    "granite_hybrid": {"decode_step": "d50c82c1cfe7adba", "block": "a8bbe663d06cdbb1",
                       "bucket": "3f6c0ce6b17158e7", "chunk": "713a762fa06a0fd9"},
    "glm_dsa": {"decode_step": "7298397e7c20935a", "block": "5e05375bd9521504",
                "bucket": "2b463136643d0057", "chunk": "f9620f0ab5ca3191"},
}


@pytest.mark.parametrize("family", list(PARENT_PROGRAMS))
def test_the_lowered_text_of_the_other_families_programs_is_the_parents(jax, family):
    """The engine's second device count and the new family change no program
    of a model that was there: ``decode_step``, the decode block, the
    bucketed prefill call and the chunk call, by hash."""
    import lowered_text

    assert lowered_text.hashes(family) == PARENT_PROGRAMS[family]
