"""GLM-5.2 (``glm_moe_dsa``) on the engine's normal path, at a tiny size on the
CPU with seeded random weights: logits, not tokens, each tolerance with its
reason.

The program (``models/glm_dsa.py``: latent attention over the positions a
lightning indexer selects, IndexShare, a third paged leaf for the indexer's
keys, a sigmoid router with a selection bias, a chunk program that takes its
offset at run time) is held to the plain reference
(``models/glm_dsa_reference.py``: float32 ``highest``, dense ``[T, T]`` index
scores, ``lax.top_k``, a masked softmax, no cache).

Float32 weights and activations throughout, so what separates the two is the
order of float32 sums (an online softmax over tiles, ``W_kvb`` absorbed into
the query, the routed sum taken tile by tile): a few 1e-6 on logits of size
~4; ``ATOL`` = 2e-4 leaves two orders of room and is two orders under what
bf16 gives. The tiny model keeps 8 of up to 100 positions, so the selection
is never the identity past the eighth token. Where the program's and the
reference's selections differ, the rows at fault have to be near-ties of the
index scores, and the logits are then compared with the reference attending
to the program's selection (``selected=``).
"""

import json

import numpy as np
import pytest

ATOL = 2e-4


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def G():
    from modal_examples_tpu.models import glm_dsa

    return glm_dsa


@pytest.fixture(scope="module")
def J(jax, G):
    """The model's entry points under ``jit``, as the engine calls them (an
    eager call compiles every scan and cond as a program of its own)."""
    import types

    return types.SimpleNamespace(
        forward=jax.jit(G.forward, static_argnames=("cfg", "attn_impl", "return_selected")),
        prefill=jax.jit(G.prefill, static_argnames=("cfg", "attn_impl")),
        decode_step=jax.jit(G.decode_step, static_argnames=("cfg", "return_counts")),
    )


@pytest.fixture(scope="module")
def ref():
    from modal_examples_tpu.models import glm_dsa_reference

    return glm_dsa_reference


def _tiny(G, **kw):
    return G.GlmDsaConfig.tiny(dtype="float32", **kw)


@pytest.fixture(scope="module")
def model(jax, G):
    """(cfg, params) of a share: experts 4..11 of the router's 16."""
    cfg = _tiny(G, n_held_experts=8, expert_offset=4)
    return cfg, G.init_params(jax.random.PRNGKey(0), cfg)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, size=n)


def _highest(jax):
    return jax.default_matmul_precision("highest")


def _leaves(jax, cfg, n_pages=32, page_size=8):
    """The three paged leaves, empty."""
    import jax.numpy as jnp

    return [
        jnp.zeros((layers, n_pages, page_size, *leaf), jnp.float32)
        for layers, leaf in zip(cfg.cache_leaf_layers, cfg.cache_leaf_shapes)
    ]


TABLE = [[3, 1, 4, 2, 9, 8, 7, 6, 5, 10, 11, 12, 13, 14]]  # 14 pages of 8: 112 positions


def _chunked_prefill(jax, G, cfg, params, toks, n_prompt, *, width=16, prefix_len=None,
                     runtime=True, attn_impl="xla"):
    """``n_prompt`` tokens through ``prefill_chunk`` calls of ``width``, the
    offset an argument (over a prefix bucket of ``prefix_len``) or static.
    Returns the last logits and the leaves."""
    import jax.numpy as jnp

    kp, vp, ip = _leaves(jax, cfg)
    table = jnp.asarray(TABLE)
    static = jax.jit(G.prefill_chunk, static_argnames=("cfg", "q_offset", "attn_impl"))
    at_run_time = jax.jit(G.prefill_chunk, static_argnames=("cfg", "prefix_len", "attn_impl"))
    for start in range(0, n_prompt, width):
        n = min(width, n_prompt - start)
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n] = toks[start:start + n]
        call, at = static, {"q_offset": start}
        if runtime and start:
            call, at = at_run_time, {"q_offset": jnp.int32(start), "prefix_len": prefix_len}
        logits, kp, vp, (ip,) = call(
            params, jnp.asarray(chunk), kp, vp, table, jnp.asarray([n]), cfg=cfg,
            attn_impl=attn_impl, state=(ip,), **at,
        )
    return logits, kp, vp, ip


# -- the configuration ------------------------------------------------------------------


def test_the_published_config_gives_the_published_shapes(G, tmp_path):
    cfg = G.GlmDsaConfig()
    kinds = cfg.layer_kinds
    assert len(kinds) == 78 and kinds[:8] == ("full",) * 3 + ("shared",) * 3 + ("full", "shared")
    assert kinds.count("full") == 21 and cfg.full_layers[:5] == (0, 1, 2, 6, 10)
    assert cfg.mlp_kinds == ("dense",) * 3 + ("sparse",) * 75
    assert (cfg.qk_head_dim, cfg.v_head_dim, cfg.index_topk, cfg.n_routed_experts) == (256, 256, 2048, 256)
    assert cfg.cache_leaf_shapes == ((1, 512), (1, 64), (1, 128))
    assert cfg.cache_leaf_layers == (78, 78, 21)
    assert cfg.chunk_offset_runtime and cfg.counts_routed_pairs and cfg.model is G
    # the pattern is read from the lists, whatever they are: the benchmark's cut
    cut = G.GlmDsaConfig.from_hf_config("benchmarks/serving/configs/glm-5.2-int8-ep16.json")
    assert cut.layer_kinds == ("full", "shared", "shared", "shared") * 2
    assert cut.mlp_kinds == ("dense",) + ("sparse",) * 7 and cut.cache_leaf_layers == (8, 8, 2)
    assert (cut.n_routed_experts, cut.n_held_experts, cut.expert_offset) == (256, 16, 0)
    # ... which keeps the published 78-entry lists and names the stretch it runs
    # (published layers 2-9); without the lists the published rule gives the same
    raw = json.loads(open("benchmarks/serving/configs/glm-5.2-int8-ep16.json").read())
    assert raw["layer_range"] == [2, 10] and len(raw["indexer_types"]) == 78
    ruled = tmp_path / "ruled.json"
    ruled.write_text(json.dumps(
        {k: v for k, v in raw.items() if k not in ("indexer_types", "mlp_layer_types")}
    ))
    by_rule = G.GlmDsaConfig.from_hf_config(ruled)
    assert (by_rule.layer_kinds, by_rule.mlp_kinds) == (cut.layer_kinds, cut.mlp_kinds)
    short = tmp_path / "short.json"
    short.write_text(json.dumps(raw | {"indexer_types": raw["indexer_types"][:8]}))
    with pytest.raises(ValueError, match="names 8 layers"):
        G.GlmDsaConfig.from_hf_config(short)
    assert cut.rope_theta == 8e6 and cut.routed_scaling_factor == 2.5 and cut.norm_topk_prob
    # per layer: attention 165.0M, an indexer 9.4M, dense FFN 226.5M, an expert 37.75M
    per_layer = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 + 64 * 256 * 6144
    assert per_layer == 165_019_648
    assert 6.2e9 < cut.param_count < 6.6e9  # ISSUE 34's cut: 6.4 GB of int8
    for key, value, feature in (("scoring_func", "softmax", "scoring_func"),
                                ("num_nextn_predict_layers", 1, "multi-token-prediction"),
                                ("rope_parameters", {"rope_type": "yarn"}, "rope_parameters")):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps(raw | {key: value}))
        with pytest.raises(NotImplementedError, match=feature):
            G.GlmDsaConfig.from_hf_config(path)
    with pytest.raises(ValueError, match="first layer"):
        _tiny(G, indexer_types=("shared", "full", "full", "full", "full"))


# -- (a) the program against the plain reference ---------------------------------------------


def _reference_logits(ref, params, toks, cfg, masks):
    """The reference's logits for ``toks`` and whether it selected the sets
    the program did (``masks`` [L, T, T]). Where it did not, every row at
    fault has to be a near-tie at the k-th index score of its layer (the
    reference's own scores, over the stream the program's selection gives),
    and the logits are the reference's attending to the program's selection."""
    import jax.numpy as jnp

    want, _margin, used = ref.forward(params, toks, cfg)
    masks = np.asarray(masks)
    if np.array_equal(masks, np.asarray(used)):
        return want, True
    assert (masks.sum(-1) == np.asarray(used).sum(-1)).all()  # as many positions, others
    for li in cfg.full_layers:
        for t in np.nonzero((masks[li] != np.asarray(used[li])).any(-1))[0]:
            # a swap at a near-tie trades positions one for one
            assert (masks[li, t] != np.asarray(used[li, t])).sum() <= 4, (li, t)
    want, _m, _u = ref.forward(params, toks, cfg, selected=jnp.asarray(masks))
    return want, False


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("share", [(16, 0), (8, 4), (4, 12)], ids=["uncut", "half", "quarter"])
def test_full_forward_matches_the_reference(jax, G, J, ref, attn_impl, share):
    """48 tokens through five layers (full, shared, shared, full, shared; a
    dense one, then routed ones), the selection 8 of up to 48 positions."""
    import jax.numpy as jnp

    cfg = _tiny(G, n_held_experts=share[0], expert_offset=share[1])
    params = G.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens(48)
    with _highest(jax):
        got, masks = J.forward(
            params, jnp.asarray(toks)[None], cfg, attn_impl=attn_impl, return_selected=True
        )
        want, same = _reference_logits(ref, params, toks, cfg, masks[:, 0])
    assert same  # at these seeds no near-tie: the two select the same sets
    assert int(masks[0, 0].sum(-1).max()) == cfg.index_topk < 48  # not the identity
    assert np.abs(np.asarray(want)).max() > 1.0
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=ATOL)


def test_a_selection_that_differs_is_told_from_an_attention_that_differs(jax, G, J, ref, model):
    """The reference given the program's ``selected=`` gives the program's
    logits; given another selection of as many positions it does not: so a
    test can tell "other positions at a near-tie" from "other arithmetic"."""
    import jax.numpy as jnp

    cfg, params = model
    toks = _tokens(40, seed=3)
    with _highest(jax):
        got, masks = J.forward(params, jnp.asarray(toks)[None], cfg, return_selected=True)
        same, _, used = ref.forward(params, toks, cfg, selected=masks[:, 0])
        other = jnp.roll(masks[:, 0], 1, axis=-1) & (jnp.arange(40)[:, None] >= jnp.arange(40)[None, :])
        other = other.at[:, jnp.arange(40), jnp.arange(40)].set(True)  # every row keeps itself
        moved, _, _ = ref.forward(params, toks, cfg, selected=other)
        every, _, _ = ref.forward(params, toks, cfg, select_all=True)
    np.testing.assert_array_equal(np.asarray(used), np.asarray(masks[:, 0]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(same), atol=ATOL)
    assert float(jnp.abs(moved - same).max()) > 100 * ATOL
    assert float(jnp.abs(every - same).max()) > 100 * ATOL  # selection off is another model


@pytest.mark.parametrize("n_prompt", [5, 16, 30])
def test_prefill_then_decode_through_the_cache_matches_the_full_forward(jax, G, J, ref, model, n_prompt):
    """One bucketed prefill call, then 14 decode steps over the three
    leaves: decode scores the slot's live pages of the third leaf, takes the
    top 8 and attends to the gathered latents alone."""
    import jax.numpy as jnp

    cfg, params = model
    toks = _tokens(n_prompt + 14, seed=n_prompt)
    kp, vp, ip = _leaves(jax, cfg)
    table = jnp.asarray(TABLE)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n_prompt] = toks[:n_prompt]
    with _highest(jax):
        want, _, _ = ref.forward(params, toks, cfg)
        logits, kp, vp, (ip,) = J.prefill(
            params, jnp.asarray(padded), kp, vp, table, jnp.asarray([n_prompt]), cfg,
            attn_impl="xla", state=(ip,),
        )
        np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[n_prompt - 1]), atol=ATOL)
        for t in range(n_prompt, n_prompt + 14):
            logits, kp, vp, (ip,), counts = J.decode_step(
                params, jnp.asarray([toks[t]]), jnp.asarray([t]), kp, vp, table,
                jnp.asarray([True]), cfg, return_counts=True, state=(ip,),
            )
            np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[t]), atol=ATOL)
            held, pairs = (int(c) for c in counts)
            assert pairs == cfg.top_k_experts * cfg.n_moe_layers and 0 <= held <= pairs


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("n_prompt,prefix_len", [(60, 48), (77, 64), (33, 32)])
def test_a_chunked_prompt_longer_than_the_top_k_then_decode_matches(jax, G, J, ref, model, n_prompt,
                                                                  prefix_len, attn_impl):
    """A prompt in chunks of 16 at run-time offsets over a prefix bucket (its
    rows past the offset are no one's), 8 of up to 77 positions kept a query,
    then decode steps over the same leaves: the reference's full pass."""
    import jax.numpy as jnp

    cfg, params = model
    toks = _tokens(n_prompt + 6, seed=n_prompt)
    with _highest(jax):
        want, _, _ = ref.forward(params, toks, cfg)
        logits, kp, vp, ip = _chunked_prefill(
            jax, G, cfg, params, toks, n_prompt, prefix_len=prefix_len, attn_impl=attn_impl
        )
        np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[n_prompt - 1]), atol=ATOL)
        table = jnp.asarray(TABLE)
        for t in range(n_prompt, n_prompt + 6):
            logits, kp, vp, (ip,) = J.decode_step(
                params, jnp.asarray([toks[t]]), jnp.asarray([t]), kp, vp, table,
                jnp.asarray([True]), cfg, state=(ip,),
            )
            np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[t]), atol=ATOL)


# -- (b) index scores and the selected sets alone ----------------------------------------------


def _indexer_inputs(jax, G, cfg, params, toks):
    """h, c_q of layer 0 (the embedding normed) and its indexer."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import layers

    layer = jax.tree.map(lambda a: a[0], params["dense_layers"])
    ip = jax.tree.map(lambda a: a[0], params["indexer_layers"])
    h = layers.rms_norm(params["embed"][jnp.asarray(toks)], layer["attn_norm"], cfg.norm_eps)
    c_q = layers.rms_norm(layers.mm(h, layer["wq_a"]), layer["q_norm"], cfg.norm_eps)
    return h, c_q, ip


def test_index_scores_and_the_selected_sets_are_the_references(jax, G, ref, model):
    import jax.numpy as jnp

    from modal_examples_tpu.models import deepseek_v2 as mla
    from modal_examples_tpu.ops import sparse_attention as sp

    cfg, params = model
    T = 90
    toks = _tokens(T, seed=11)
    with _highest(jax):
        h, c_q, ip = _indexer_inputs(jax, G, cfg, params, toks)
        want = ref.index_scores(h, c_q, ip, cfg)
        cos, sin = mla._rope_tables(jnp.arange(T)[None], cfg)
        q_idx, w, k_idx = G._index_project(ip, h[None], c_q[None], cos, sin, cfg)
        got = sp.index_scores(q_idx, w, k_idx)
        blocked = sp.index_scores(  # 128 keys, four blocks of 32
            q_idx, w, jnp.pad(k_idx, ((0, 0), (0, 38), (0, 0))), block_k=32
        )[:, :, :T]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(blocked[0]), np.asarray(want), atol=1e-5)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    mask = sp.select_mask(got, causal[None], cfg.index_topk)[0]
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(ref.select(want, cfg.index_topk)))
    assert np.asarray(mask).sum(-1).tolist() == [min(t + 1, 8) for t in range(T)]
    # decode's form over the same scores: positions, the same sets
    masked = jnp.where(causal, got[0], -jnp.inf)
    idx, counts = sp.select_positions(masked, cfg.index_topk)
    sets = np.zeros((T, T), bool)
    for t in range(T):
        sets[t, np.asarray(idx[t])[np.asarray(counts[t])]] = True
    np.testing.assert_array_equal(sets, np.asarray(mask))


@pytest.mark.parametrize("case", ["ties", "all-equal", "negative", "fewer-than-k"])
def test_the_selection_is_exact_and_a_tie_goes_to_the_lowest_position(jax, case):
    """``select_mask`` (no sort: the k-th value's bits by counting) against
    ``lax.top_k`` (stable: the lower index first among equals) on scores with
    many equal values, of either sign, and on rows with fewer than k valid."""
    import jax.numpy as jnp

    from modal_examples_tpu.ops import sparse_attention as sp

    rng = np.random.default_rng(5)
    S, k = 64, 8
    scores = rng.normal(size=(3, 16, S)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores * 2) / 2
    elif case == "all-equal":
        scores[:] = 0.25
    elif case == "negative":
        scores = -np.abs(np.round(scores * 4) / 4) - 1.0
    valid = np.ones((3, 16, S), bool)
    if case == "fewer-than-k":
        valid[:] = np.arange(S) < 5
    valid[0, 3] = np.arange(S) % 3 == 0
    mask = np.asarray(sp.select_mask(jnp.asarray(scores), jnp.asarray(valid), k))
    _, idx = jax.lax.top_k(jnp.where(jnp.asarray(valid), jnp.asarray(scores), -jnp.inf), k)
    want = np.zeros_like(valid)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(mask, want & valid)
    assert (mask.sum(-1) == np.minimum(valid.sum(-1), k)).all()


# -- (c) IndexShare ----------------------------------------------------------------------------


def test_a_shared_layer_attends_to_the_carried_selection_and_a_full_layer_replaces_it(jax, G, J, model):
    """Layers full, shared, shared, full, shared: two indexers in the tree
    and two layers of index keys in the cache; layers 1 and 2 attend to layer
    0's selection, layer 3 selects anew and layer 4 follows it. Another second
    indexer moves layers 3 and 4 alone."""
    import jax.numpy as jnp

    cfg, params = model
    assert cfg.full_layers == (0, 3) and cfg.cache_leaf_layers == (5, 5, 2)
    assert all(leaf.shape[0] == 2 for leaf in jax.tree.leaves(params["indexer_layers"]))
    assert not {"wq_idx", "wk_idx", "w_idx"} & (set(params["dense_layers"]) | set(params["moe_layers"]))
    toks = jnp.asarray(_tokens(40, seed=4))[None]
    with _highest(jax):
        _, masks = J.forward(params, toks, cfg, return_selected=True)
        other = dict(params, indexer_layers=jax.tree.map(
            lambda a: a.at[1].set(jnp.roll(a[1], 1, axis=0)), params["indexer_layers"]
        ))
        _, moved = J.forward(other, toks, cfg, return_selected=True)
    masks, moved = np.asarray(masks[:, 0]), np.asarray(moved[:, 0])
    np.testing.assert_array_equal(masks[1], masks[0])
    np.testing.assert_array_equal(masks[2], masks[0])
    np.testing.assert_array_equal(masks[4], masks[3])
    assert (masks[3] != masks[0]).any()  # the carry was replaced
    np.testing.assert_array_equal(moved[:3], masks[:3])
    assert (moved[3] != masks[3]).any()
    np.testing.assert_array_equal(moved[4], moved[3])


# -- (d) the router ----------------------------------------------------------------------------


def _dense_weights(weights, ids, width):
    out = np.zeros((ids.shape[0], width), np.float32)
    np.put_along_axis(out, np.asarray(ids), np.asarray(weights), axis=1)
    return out


@pytest.mark.parametrize("case", ["random", "near-ties", "exact-ties"])
def test_the_router_scores_by_sigmoid_chooses_by_the_biased_score_and_weighs_by_the_unbiased(jax, G, ref, case):
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    cfg = _tiny(G, n_routed_experts=256, n_held_experts=256, top_k_experts=8)
    logits = jax.random.normal(jax.random.PRNGKey(4), (64, 256))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (256,))
    if case == "near-ties":
        logits = jnp.round(logits * 4) / 4 + 1e-6 * jax.random.normal(jax.random.PRNGKey(5), logits.shape)
    elif case == "exact-ties":
        logits, bias = jnp.round(logits * 2) / 2, jnp.round(bias * 8) / 8
    p = jax.nn.sigmoid(logits)
    want_w, want_ids, margin = ref.route(p, bias, cfg)
    got_w, got_ids = moe.route_group_limited(p, 8, scale=2.5, renormalize=True, bias=bias)
    np.testing.assert_array_equal(
        _dense_weights(got_w, got_ids, 256), _dense_weights(want_w, want_ids, 256)
    )
    # the chosen are the top 8 of p + bias; their weights the unbiased p, renormalised, times 2.5
    top = np.sort(np.argsort(-np.asarray(p + bias), axis=-1, kind="stable")[:, :8], axis=-1)
    np.testing.assert_array_equal(np.sort(np.asarray(got_ids), axis=-1), top)
    chosen = np.take_along_axis(np.asarray(p), np.asarray(got_ids), 1)
    np.testing.assert_allclose(np.asarray(got_w), 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 2.5, rtol=1e-6)
    assert np.all(np.asarray(margin) >= 0)
    # a selection by the unbiased score chooses other experts for most tokens
    _, plain_ids = moe.route_group_limited(p, 8, scale=2.5, renormalize=True)
    differ = (np.sort(np.asarray(plain_ids), -1) != np.sort(np.asarray(got_ids), -1)).any(-1).mean()
    assert differ > 0.5
    with pytest.raises(NotImplementedError, match="group limit"):
        moe.route_group_limited(p, 8, n_group=4, topk_group=2, bias=bias)


def test_a_program_that_selects_by_the_unbiased_score_fails(jax, G, J, ref, model):
    """The seeded bias is not zero: the same tree with the bias left out of
    the selection (zeroed) leaves the tolerance by a wide margin."""
    import jax.numpy as jnp

    cfg, params = model
    assert float(jnp.abs(params["moe_layers"]["router_bias"]).mean()) > 0.05
    toks = _tokens(32, seed=8)
    unbiased = dict(params, moe_layers=dict(
        params["moe_layers"], router_bias=jnp.zeros_like(params["moe_layers"]["router_bias"])
    ))
    with _highest(jax):
        want, _, _ = ref.forward(params, toks, cfg)
        got = J.forward(unbiased, jnp.asarray(toks)[None], cfg)[0]
    assert float(jnp.abs(got - want).max()) > 100 * ATOL


# -- (e) the share ties to the model -------------------------------------------------------------


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(jax, G, ref):
    """``model-configs`` section 4: what every share computes of the routed
    sum, with what each computes alike (the shared expert) counted once, is
    the whole layer."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import deepseek_v2 as mla

    whole = _tiny(G)
    params = G.init_params(jax.random.PRNGKey(2), whole)
    layer = jax.tree.map(lambda a: a[0], params["moe_layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (24, whole.dim))
    with _highest(jax):
        want, _ = ref.routed_mlp(h, layer, whole)
        shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        total = jnp.zeros_like(want)
        held = 0
        for offset in range(0, 16, 4):
            cfg = _tiny(G, n_held_experts=4, expert_offset=offset)
            part = dict(layer, **{
                n: layer[n][offset:offset + 4] for n in ("moe_gate", "moe_up", "moe_down")
            })
            out, counts = mla._mlp(part, h, cfg, False, None)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref.routed_mlp(h, part, cfg)[0]), atol=ATOL
            )
            total = total + out - shared
            held += int(counts[0])
            assert int(counts[1]) == 24 * whole.top_k_experts
        total = total + shared
    assert held == 24 * whole.top_k_experts  # every pair lands in exactly one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=ATOL)


# -- (f) the third leaf -----------------------------------------------------------------------------


def test_the_cache_keeps_a_third_paged_leaf_over_the_full_layers(jax, G):
    """Per token: 8 layers of a 512 latent and a 64 rotated key, and 2 layers
    of a 128 index key, in bf16: 9728 bytes. One page table, one allocator."""
    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    cfg = G.GlmDsaConfig.from_hf_config("benchmarks/serving/configs/glm-5.2-int8-ep16.json")
    cache = PagedKVCache.create(
        n_layers=cfg.n_layers, leaf_shapes=cfg.cache_leaf_shapes,
        leaf_layers=cfg.cache_leaf_layers, n_pages=3, page_size=16, prefer_native=False,
    )
    assert cache.k_pages.shape == (8, 3, 16, 1, 512) and cache.v_pages.shape == (8, 3, 16, 1, 64)
    assert [leaf.shape for leaf in cache.more_pages] == [(2, 3, 16, 1, 128)]
    assert cache.beside == cache.more_pages and cache.state == ()
    assert cache.bytes() == 9728 * 3 * 16
    used = cache.allocator.alloc(2)
    assert cache.occupancy()["bytes_used"] == 2 * 16 * 9728  # a page of all three leaves
    cache.allocator.free(used)
    assert cache.occupancy()["bytes_used"] == 0
    # the two-leaf models' caches are as they were
    plain = PagedKVCache.create(
        n_layers=2, n_kv_heads=2, head_dim=8, n_pages=3, page_size=16, prefer_native=False
    )
    assert plain.more_pages == () and plain.beside == ()
    assert plain.k_pages.shape == plain.v_pages.shape == (2, 3, 16, 2, 8)
    with pytest.raises(ValueError, match="layer counts"):
        PagedKVCache.create(n_layers=2, leaf_shapes=((1, 8),) * 3, leaf_layers=(2, 2), n_pages=3)


def _engine(cfg, params, **kw):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("max_model_len", 160)
    return LLMEngine(
        cfg, params, max_slots=2, page_size=8, kv_dtype=jnp.float32, seed=0, decode_block=4, **kw,
    )


def _served(eng, text, n=10):
    from modal_examples_tpu.serving import SamplingParams

    req = eng.submit(text, SamplingParams(max_tokens=n, temperature=0.0))
    "".join(eng.stream(req))
    return req, list(req.prompt_tokens), list(req.generated_tokens)


PROMPTS = {
    "short": "sparse attention",  # one bucketed prefill call
    "chunked": "a document long enough to need a second chunk over cached latents and index "
               "keys, and a third, a fourth and a fifth",
}


def _value(name, **labels):
    from modal_examples_tpu.utils.prometheus import default_registry

    return default_registry.value(name, labels or None) or 0.0


@pytest.mark.parametrize("prefix_cache", [True, False], ids=["prefix-cache", "no-prefix-cache"])
@pytest.mark.parametrize("prompt", list(PROMPTS))
def test_engine_serves_the_references_argmax(jax, G, ref, model, prompt, prefix_cache):
    """``LLMEngine`` end to end: greedy tokens are the reference's first
    choice wherever it is decided. With the prefix cache on, the same prompt
    a second time runs over shared pages, the indexer's keys among them, and
    serves the same tokens."""
    cfg, params = model
    eng = _engine(cfg, params, enable_prefix_cache=prefix_cache)
    try:
        assert len(eng.cache.more_pages) == 1 and eng.cache.state == ()
        first, prompt_ids, served = _served(eng, PROMPTS[prompt])
        again, again_ids, again_served = _served(eng, PROMPTS[prompt])
        if prompt == "chunked":
            assert len(prompt_ids) > 96  # chunks at offsets 16 .. 96, over three prefix buckets
        assert (again_ids, again_served) == (prompt_ids, served)
        if prefix_cache and len(prompt_ids) >= 16:
            assert eng.prefix_cache.hits >= 1 and again.cached_prompt_tokens >= 8
            # the shared pages hold the indexer's keys of both full layers
            keys = np.asarray(eng.cache.more_pages[0])  # [2 full layers, pages, 8, 1, 16]
            written = np.abs(keys).sum(axis=(2, 3, 4)) > 0
            assert keys.shape[0] == 2 and (written[0] == written[1]).all() and written.sum() >= 2
    finally:
        eng.stop()
    assert not eng.error_log and len(served) == 10
    with _highest(jax):
        logits, _, _ = ref.forward(params, np.asarray(prompt_ids + served[:-1]), cfg)
    rows = np.asarray(logits)[len(prompt_ids) - 1:]
    top2 = np.sort(rows, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 10 * ATOL
    assert decided.sum() >= 8
    assert [int(t) for t in rows.argmax(-1)[decided]] == [t for t, d in zip(served, decided) if d]


def test_the_engines_counters_count_what_was_scored_selected_and_attended(jax, G, model):
    """``mtpu_sparse_positions_total``: from the positions the host hands the
    programs. A chunked prompt of n tokens scores n (n + 1) / 2 pairs in each
    of the 2 full layers; every layer selects min(t + 1, 8) a query; prefill
    attends under a mask (every causal pair), decode to the selected alone."""
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving.engine import MODEL_PRESETS

    cfg, params = model
    kinds = ("scored", "selected", "attended")
    before = {k: _value(C.SPARSE_POSITIONS_TOTAL, kind=k) for k in kinds}
    pairs0 = _value(C.ROUTED_PAIRS_TOTAL, where="held") + _value(C.ROUTED_PAIRS_TOTAL, where="elsewhere")
    assert MODEL_PRESETS["tiny-glm-dsa"]().model is G
    eng = _engine(cfg, params, enable_prefix_cache=False)
    try:
        assert eng.impl_plan["attention"] == "xla-gather"
        _req, prompt_ids, served = _served(eng, PROMPTS["chunked"], n=9)
    finally:
        eng.stop()
    n = len(prompt_ids)
    got = {k: _value(C.SPARSE_POSITIONS_TOTAL, kind=k) - before[k] for k in kinds}
    # decode blocks of 4 steps from position n on: two for the 8 further tokens, and a
    # third where the pipeline dispatched it before the last token was read
    prefill = {"scored": 2 * n * (n + 1) // 2, "selected": 5 * sum(min(t + 1, 8) for t in range(n)),
               "attended": 5 * n * (n + 1) // 2}
    blocks = round((got["selected"] - prefill["selected"]) / (5 * 8 * 4))
    assert blocks in (2, 3)
    steps = np.arange(n, n + 4 * blocks) + 1  # positions s <= t of each step's query
    assert got["scored"] == prefill["scored"] + 2 * steps.sum()
    assert got["selected"] == prefill["selected"] + 5 * 8 * 4 * blocks
    assert got["attended"] == prefill["attended"] + 5 * 8 * 4 * blocks  # gathered: the selected
    pairs = (_value(C.ROUTED_PAIRS_TOTAL, where="held")
             + _value(C.ROUTED_PAIRS_TOTAL, where="elsewhere") - pairs0)
    # one live slot; a block's pairs are read with its tokens, the dispatch is counted at once
    assert pairs in {4 * b * cfg.n_moe_layers * cfg.top_k_experts for b in (blocks - 1, blocks)}


# -- (g) the models there were get the programs they had ------------------------------------------

#: sha256 (16 hex) of the lowered text at the parent commit (b37a1de, PR 33):
#: ``python tests/lowered_text.py`` in a checkout of it. Granite's
#: ``decode_step`` and ``block`` are PR 37's: the state step's XLA form moved
#: into ``ops/ssm_step.py::ssm_step_xla``, the same operations traced in
#: another order (9f3ed3041dcbd8e9 and 0f55fd87662ffe27 before it); its
#: bucketed prefill call and the other two families' decode step, block and
#: bucket are still PR 33's; every ``chunk`` is PR 43's, whose chunk program
#: samples (``tests/test_chunk_sampler.py`` holds its token to the eager call's)
PARENT_PROGRAMS = {
    "llama": {"decode_step": "e1811a7588348e55", "block": "b9e8a274d64690dc",
              "bucket": "83033cbdc7920805", "chunk": "75df27ad29b5c8a0"},
    "deepseek_v2": {"decode_step": "47f2d3be0ccb4262", "block": "24a13b4726bd7df2",
                    "bucket": "fa5a05d32ed93dcd", "chunk": "5ad332426a8b933e"},
    "granite_hybrid": {"decode_step": "d50c82c1cfe7adba", "block": "a8bbe663d06cdbb1",
                       "bucket": "3f6c0ce6b17158e7", "chunk": "713a762fa06a0fd9"},
}


@pytest.mark.parametrize("family", list(PARENT_PROGRAMS))
def test_the_lowered_text_of_the_other_families_programs_is_the_parents(jax, family):
    """A model that declares two paged leaves and no run-time chunk offset is
    lowered to the text it was lowered to before: ``decode_step``, the decode
    block, the bucketed prefill call and the chunk call, by hash."""
    import lowered_text

    assert lowered_text.hashes(family) == PARENT_PROGRAMS[family]


# -- (h) the chunk program's run-time offset -------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [40, 64])
def test_the_run_time_offset_gives_the_static_offsets_logits_and_cache(jax, G, model, n_prompt):
    """Chunks at offsets 16, 32, 48 through one program over a prefix bucket
    of 48 (the offset an argument) against a program an offset: the same
    logits and the same rows in all three leaves."""
    cfg, params = model
    toks = _tokens(n_prompt, seed=n_prompt)
    with _highest(jax):
        want = _chunked_prefill(jax, G, cfg, params, toks, n_prompt, runtime=False)
        got = _chunked_prefill(jax, G, cfg, params, toks, n_prompt, prefix_len=48)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=ATOL)
    pages = np.asarray(TABLE[0][: -(-n_prompt // 8)])
    for a, b in zip(got[1:], want[1:]):
        rows = lambda x: np.asarray(x)[:, pages].reshape(x.shape[0], -1, x.shape[-1])[:, :n_prompt]  # noqa: E731
        np.testing.assert_allclose(rows(a), rows(b), atol=1e-5)
        assert np.abs(rows(a)).max() > 0.01


def test_a_boot_at_nine_chunks_builds_a_program_a_prefix_bucket(jax, G, model):
    """``max_model_len`` of nine chunks: eight offsets past the first fall
    into three prefix buckets, so ``warmup()`` builds 1 + 3 chunk programs
    where a program an offset and width would be 1 + 8 x 2: one a bucket, of
    the whole width (a tail chunk is padded to it: a program over a long
    prefix is too long to build for every width), each named for its bucket,
    under the prefill programs' pattern."""
    import re

    from modal_examples_tpu.serving.engine import _PREFIX_BUCKETS

    cfg, params = model
    eng = _engine(cfg, params, prefill_buckets=(8, 16), max_model_len=9 * 16 + 1)
    try:
        offsets = range(0, 9 * 16, 16)
        keys = [eng._chunk_key(o) for o in offsets]
        assert keys == [0, 48, 48, 48, 96, 96, 96, 128, 128] and _PREFIX_BUCKETS == 3
        assert all(k >= o for k, o in zip(keys, offsets))  # the gathered prefix covers the offset
        eng.warmup()
        built = {(key, width) for key, width, draft in eng._chunk_programs if not draft}
        assert built == {(k, 16) for k in (0, 48, 96, 128)}
        assert len(built) <= len((8, 16)) * _PREFIX_BUCKETS  # ISSUE 34's bound: widths x buckets
        names = {eng._chunk_jit(k).__wrapped__.__name__ for k in (0, 48, 96, 128)}
        assert names == {"prefill_chunk_pre0", "prefill_chunk_pre48", "prefill_chunk_pre96",
                         "prefill_chunk_pre128"}
        pattern = re.compile(json.loads(open("benchmarks/serving/layers/programs.json").read())["prefill"])
        assert all(pattern.search("jit_" + n) for n in names)
        _req, prompt_ids, served = _served(eng, "x" * 139, n=4)  # every bucket, a tail of 12
        assert len(prompt_ids) == 140 and len(served) == 4 and not eng.error_log
        assert {(key, width) for key, width, draft in eng._chunk_programs} == built  # no other width
    finally:
        eng.stop()


# -- what is refused, and the checkpoint -------------------------------------------------------------

REFUSED = {
    "int8 KV cache": dict(kv_dtype="int8"),
    "speculative decoding": dict(speculative=("ngram", 2)),
    "tensor parallelism": "mesh",
    "vision": dict(vision=(object(), None)),
    "disaggregated transfer": dict(tiered_prefix=True),
    "a Pallas paged_impl or scatter_impl": dict(paged_impl="pallas"),
}


@pytest.mark.parametrize("feature", list(REFUSED))
def test_each_feature_the_model_lacks_is_refused_by_name(jax, G, model, feature):
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    kw = REFUSED[feature]
    if kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tensor",)))
    assert feature in cfg.unsupported
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,), **kw)


def test_lora_and_partition_specs_are_refused_and_the_int8_targets_are_counted(jax, G, model):
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import DEEPSEEK_V2_TARGETS, QuantizedWeight, quantize_llama

    cfg, params = model
    with pytest.raises(NotImplementedError, match="LoRA"):
        G.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, lora={})
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        G.partition_specs(cfg)
    assert cfg.quant_targets == DEEPSEEK_V2_TARGETS + ("wq_idx", "wk_idx")
    quantized = quantize_llama(params, cfg.quant_targets, bits=8)
    index = quantized["indexer_layers"]
    assert isinstance(index["wq_idx"], QuantizedWeight) and isinstance(index["wk_idx"], QuantizedWeight)
    assert not isinstance(index["w_idx"], QuantizedWeight)  # the heads' weights stay, as the router
    assert not isinstance(quantized["moe_layers"]["router"], QuantizedWeight)
    assert quantized["moe_layers"]["router_bias"].dtype == jnp.float32


def test_load_hf_weights_maps_the_published_names(jax, G, J, ref, model, tmp_path):
    """A made-up tiny checkpoint under the published tensor names (torch's
    ``[out, in]`` matrices, an expert a tensor, the indexer under
    ``self_attn.indexer``) loads as the tree it was written from."""
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    cfg, params = model
    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    n_full = 0
    for i in range(cfg.n_layers):
        dense = i < cfg.n_dense_layers
        stack = params["dense_layers" if dense else "moe_layers"]
        layer = jax.tree.map(lambda a: np.asarray(a[i if dense else i - cfg.n_dense_layers]), stack)
        if cfg.layer_kinds[i] == "full":
            layer.update(jax.tree.map(lambda a: np.asarray(a[n_full]), params["indexer_layers"]))
            n_full += 1
        for ours, leaf in layer.items():
            theirs = f"model.layers.{i}." + G.HF_LAYER_NAMES[ours]
            if "{e}" in theirs:  # published experts are numbered over the router's width
                for e in range(cfg.n_held_experts):
                    tensors[theirs.format(e=e + cfg.expert_offset)] = leaf[e].T
            else:
                tensors[theirs] = leaf.T if leaf.ndim == 2 else leaf
    save_file({k: np.ascontiguousarray(v) for k, v in tensors.items()},
              str(tmp_path / "model.safetensors"))
    loaded = G.load_hf_weights(tmp_path, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ids = _tokens(20, seed=6)
    with _highest(jax):
        got = J.forward(loaded, jnp.asarray(ids)[None], cfg, attn_impl="xla")[0]
        want, _, _ = ref.forward(params, ids, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    quantized = G.load_hf_weights(tmp_path, cfg, quantization="int8")
    assert type(quantized["indexer_layers"]["wq_idx"]).__name__ == "QuantizedWeight"
    with pytest.raises(FileNotFoundError):
        G.load_hf_weights(tmp_path / "nothing", cfg)
