"""Granite-4.0-H-Small's block on the engine's normal path, at a tiny size on
the CPU with seeded random weights: a shared SwiGLU beside routed experts
behind every Mamba-2 and attention mixer, the experts' stacks indexed by
layer where the mixers' are indexed by kind, a chip's share of the experts.
Logits, not tokens, each tolerance with its reason.

The program (``models/granite_hybrid.py`` with ``n_experts > 0``) is held to
the plain reference (``models/granite_hybrid_reference.py``: float32
``highest``, every held expert's product written out for every token, no
sort, no tiles). The dense model's tests are ``tests/test_granite_hybrid.py``;
its programs' lowered text is pinned to the parent's in
``tests/test_glm_dsa.py`` (``PARENT_PROGRAMS["granite_hybrid"]``), which this
change leaves as it was.

Tolerances. With float32 weights and activations the two differ by the order
of float32 sums: ``F32_ATOL`` = 2e-4 on logits of size ~1-3, as the dense
model's. A route decided by less than that (the last chosen logit within 1e-6
of the first left out) could go either way; the seeds here have none. In bf16
rounding of the weights is shared and what is left is bf16 activations
through the tile matmuls: ``BF16_ATOL`` = 0.08.
"""

import json

import numpy as np
import pytest

F32_ATOL = 2e-4
BF16_ATOL = 0.08


@pytest.fixture(scope="module")
def jax():
    import jax

    return jax


@pytest.fixture(scope="module")
def G():
    from modal_examples_tpu.models import granite_hybrid

    return granite_hybrid


@pytest.fixture(scope="module")
def ref():
    from modal_examples_tpu.models import granite_hybrid_reference

    return granite_hybrid_reference


def _params(jax, G, cfg, seed=0):
    """The dense model's seeded weights (every leaf away from its trivial
    value, an embedding large enough for logits of size 1), a router wide
    enough that the chosen experts' weights differ, and the routed half as
    large as the shared one."""
    import jax.numpy as jnp
    import test_granite_hybrid as dense

    params = dense._params(jax, G, cfg, seed)
    scale = {"router": 3, "moe_gate": 2, "moe_up": 2, "moe_down": 2}
    params["moe_layers"] = {
        name: (leaf.astype(jnp.float32) * scale[name]).astype(leaf.dtype)
        for name, leaf in params["moe_layers"].items()
    }
    return params


def _share(jax, params, first, count):
    """The tree of the chip that holds experts ``first .. first + count - 1``."""
    moe = params["moe_layers"]
    held = {k: moe[k][:, first:first + count] for k in ("moe_gate", "moe_up", "moe_down")}
    return {**params, "moe_layers": {"router": moe["router"], **held}}


@pytest.fixture(scope="module")
def model(jax, G):
    """Every expert held: (cfg, params)."""
    cfg = G.GraniteHybridConfig.tiny_moe(dtype="float32")
    return cfg, _params(jax, G, cfg)


@pytest.fixture(scope="module")
def shares(jax, G, model):
    """The two chips of an EP2 layer: [(cfg, params)] holding experts 0-3 and 4-7."""
    cfg, params = model
    return [
        (G.GraniteHybridConfig.tiny_moe(dtype="float32", n_held_experts=4, expert_offset=o),
         _share(jax, params, o, 4))
        for o in (0, 4)
    ]


def _ref_logits(ref, params, ids, cfg, **kw):
    import jax.numpy as jnp

    return np.asarray(ref.forward(params, jnp.asarray(ids), cfg, **kw))


def _force_forms(monkeypatch, G, form):
    """Both Mosaic calls of a decode step's layer in the form ``form``,
    whatever the backend: on the CPU ``"pallas"`` runs the state step's and
    the grouped matmul's kernels in the interpreter."""
    from modal_examples_tpu.models import moe

    plan = G.paged_impl_plan
    monkeypatch.setattr(G, "paged_impl_plan", lambda *a, **kw: {
        **plan(*a, **kw), "state_step": form, "expert_scan": form})
    monkeypatch.setattr(moe, "expert_scan_form", lambda n, *a: form if n < 128 else "xla")


# -- the configuration ------------------------------------------------------------------


PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 4096, "num_attention_heads": 32,
    "num_key_value_heads": 8, "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "num_hidden_layers": 40, "intermediate_size": 768, "shared_intermediate_size": 1536,
    "num_local_experts": 72, "num_experts_per_tok": 10, "mamba_n_heads": 128, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 256, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0078125, "logits_scaling": 16, "max_position_embeddings": 131072,
}


def test_the_published_config_gives_the_published_model(G, tmp_path):
    """Granite-4.0-H-Small's ``config.json`` (the catalog's row): 32.2 B
    parameters, a Mamba layer 801 M and an attention layer 741 M (ISSUE 45)."""
    path = tmp_path / "config.json"
    path.write_text(json.dumps(PUBLISHED))
    cfg = G.GraniteHybridConfig.from_hf_config(path)
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.head_dim, cfg.kv_fold) == (40, 4, 128, 1)
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset, cfg.top_k, cfg.expert_dim,
            cfg.ffn_dim) == (72, 72, 0, 10, 768, 1536)
    assert cfg.state_leaves == ((36, (128, 64, 128), "float32"), (36, (3, 8448), "bfloat16"))
    assert cfg.cache_leaf_shapes == ((8, 128), (8, 128))
    assert cfg.counts_routed_pairs and cfg.counts_expert_tile_rows
    assert 32.1e9 < cfg.param_count < 32.3e9
    mamba = 4096 * (8192 + 8448 + 128) + 8192 * 4096
    assert 102.2e6 < mamba < 102.4e6  # a Mamba mixer's two projections
    expert, shared, router = 3 * 4096 * 768, 3 * 4096 * 1536, 4096 * 72
    assert round((mamba + shared + router + 72 * expert) / 1e6) == 801
    assert round((4096 * 6144 + 4096 * 4096 + shared + router + 72 * expert) / 1e6) == 741


def test_a_file_that_states_a_share_and_a_depth_is_read_as_the_benchmarks_is(G, tmp_path):
    """``num_hidden_layers`` layers of a ``layer_types`` kept whole,
    ``num_local_experts`` experts held of ``expert_share.of`` from
    ``expert_share.offset`` on (the form DeepSeek-V2's file has): one period
    on one chip of two is 4.55 B parameters = 9.10 GB in bf16, and a slot's
    state 38.2 MB (ISSUE 45's cut)."""
    path = tmp_path / "config.json"
    cut = PUBLISHED | {
        "num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 25088,
        "expert_share": {"of": 72, "offset": 36, "chips_per_layer": 2},
    }
    path.write_text(json.dumps(cut))
    cfg = G.GraniteHybridConfig.from_hf_config(path)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert cfg.segments == (("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4))
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset) == (72, 36, 36)
    layers = cfg.param_count - 25088 * 4096
    assert 4.54e9 < layers < 4.56e9 and round(2 * layers / 1e9, 2) == 9.10
    per_slot = sum(n * int(np.prod(shape)) * np.dtype(dt if dt != "bfloat16" else "float16").itemsize
                   for n, shape, dt in cfg.state_leaves)
    assert round(per_slot / 1e6, 1) == 38.2
    # a dense file (H-Micro's) states no expert and is read as it was
    path.write_text(json.dumps(PUBLISHED | {"num_local_experts": 0, "num_experts_per_tok": 0}))
    dense = G.GraniteHybridConfig.from_hf_config(path)
    assert (dense.n_experts, dense.top_k, dense.expert_dim) == (0, 0, 0)
    assert not dense.counts_routed_pairs and "expert_scan" not in G.paged_impl_plan(dense, 16)
    with pytest.raises(ValueError, match="lie outside"):
        G.GraniteHybridConfig.tiny_moe(n_held_experts=4, expert_offset=5)
    with pytest.raises(ValueError, match="top_k"):
        G.GraniteHybridConfig.tiny(n_experts=8)
    path.write_text(json.dumps(cut | {"num_hidden_layers": 41}))
    with pytest.raises(ValueError, match="names 40 layers of 41"):
        G.GraniteHybridConfig.from_hf_config(path)


@pytest.mark.parametrize("backend,want", [("tpu", "pallas"), ("cpu", "xla")])
def test_the_plan_names_both_forms_from_the_shapes(jax, G, backend, want, monkeypatch):
    """At the published widths on a TPU a decode step's Mamba layer holds two
    Mosaic calls, the state step at 128 heads and the grouped matmul over
    bf16 experts of 4096 x 768 (F in 2 blocks of 384 under ``BLOCK_BYTES``);
    elsewhere XLA's forms. No option chooses."""
    from modal_examples_tpu.ops.expert_swiglu import expert_swiglu_block
    from modal_examples_tpu.ops.ssm_step import ssm_step_tile

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = G.GraniteHybridConfig(
        dim=4096, mamba_n_heads=128, ffn_dim=1536, n_experts=72, n_held_experts=36, top_k=10,
        expert_dim=768, layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    )
    plan = G.paged_impl_plan(cfg, 16)
    assert (plan["state_step"], plan["expert_scan"], plan["attention"]) == (want, want, "xla-gather")
    assert G.paged_impl_plan(cfg, 16, expert_dtype="int4")["expert_scan"] == "xla"
    assert expert_swiglu_block(4096, 768, "bfloat16") == 384
    assert ssm_step_tile(64, 128, 64, 128) == (1, 64)  # two head tiles of 2 MiB a slot
    with pytest.raises(NotImplementedError, match="Pallas paged_impl"):
        G.paged_impl_plan(cfg, 16, "pallas")


# -- the layer and the route ----------------------------------------------------------------


def test_the_route_is_top_k_of_the_logits_and_a_softmax_over_the_chosen(jax, G, ref, model):
    """The program's route (softmax over all, top-k, renormalised) against
    the published one (top-k of the logits, softmax over those), with exact
    ties: a router whose columns repeat gives equal logits, and both take the
    lower id."""
    import jax.numpy as jnp

    cfg, _ = model
    rng = np.random.default_rng(0)
    router = rng.normal(size=(64, 8)).astype(np.float32)
    router[:, 5] = router[:, 2]  # experts 2 and 5 tie on every token
    router[:, 7] = router[:, 0]
    x = rng.normal(size=(50, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        w, ids = G.route(jnp.asarray(router), jnp.asarray(x), cfg)
        want_ids, want_w = ref.route(jnp.asarray(x) @ jnp.asarray(router), cfg.top_k)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    tied = np.asarray(ids)
    assert ((tied == 5).sum(-1) <= (tied == 2).sum(-1)).all()  # 5 never without 2
    assert (tied == 2).any() and (tied == 5).any()


def _mlp_halves(jax, G, cfg, params, x, index, kind="mamba", row=0):
    """(shared + routed as the program's ``_mlp`` adds them, before the
    residual; the layer's counts)."""
    import jax.numpy as jnp

    layer = G._row(params[f"{kind}_layers"], row)
    with jax.default_matmul_precision("highest"):
        out, counts = G._mlp(layer, x, cfg, params["moe_layers"], jnp.int32(index))
    return (np.asarray(out) - np.asarray(x)) / cfg.residual_multiplier, counts


def test_the_layer_is_the_references_shared_plus_routed(jax, G, ref, model):
    """One layer's second half on a normed stream, a Mamba layer's and an
    attention layer's (their rows in the stacks of their kinds differ from
    their layer index, which picks the experts)."""
    import jax.numpy as jnp

    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    for index, kind, row in ((3, "mamba", 2), (4, "attention", 1), (0, "mamba", 0)):
        got, (pairs, tiles) = _mlp_halves(jax, G, cfg, params, x, index, kind, row)
        _, layer = ref.layer_at(params, cfg, index)
        with jax.default_matmul_precision("highest"):
            u = ref.rms_norm(x.reshape(18, 64), layer["mlp_norm"], cfg.norm_eps)
            want = ref.mlp(layer, u) + ref.routed(ref.moe_at(params, index), u, cfg.top_k)
        np.testing.assert_allclose(got.reshape(18, 64), want, atol=2e-5)
        assert np.abs(want).max() > 0.1
        assert pairs.tolist() == [54, 54] and int(tiles[0]) == 54  # 18 tokens x 3, all held


def test_two_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(
        jax, G, ref, model, shares):
    """The cut ties to the model (``model-configs`` section 4): what the two
    chips of an EP2 layer each compute of the routed sum, added, with the
    shared expert (which every chip computes alike) counted once, is the
    uncut reference's layer. In the program and in the reference's own
    shares; and the pairs the two count as held are all the pairs."""
    import jax.numpy as jnp

    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64), jnp.float32)
    for index, kind, row in ((1, "mamba", 1), (2, "attention", 0)):
        _, layer = ref.layer_at(params, cfg, index)
        with jax.default_matmul_precision("highest"):
            u = ref.rms_norm(x[0], layer["mlp_norm"], cfg.norm_eps)
            shared = np.asarray(ref.mlp(layer, u))
            whole = shared + np.asarray(ref.routed(ref.moe_at(params, index), u, cfg.top_k))
            ref_parts = [
                np.asarray(ref.routed(ref.moe_at(p, index), u, c.top_k, (c.expert_offset, 4)))
                for c, p in shares
            ]
        np.testing.assert_allclose(shared + sum(ref_parts), whole, atol=2e-5)
        got = [_mlp_halves(jax, G, c, p, x, index, kind, row) for c, p in shares]
        parts = [out[0] - shared for out, _ in got]
        np.testing.assert_allclose(shared + sum(parts), whole, atol=2e-5)
        for part, want in zip(parts, ref_parts):
            np.testing.assert_allclose(part, want, atol=2e-5)
            assert np.abs(want).max() > 0.05  # each share adds something
        held = [int(counts[0][0]) for _, counts in got]
        assert sum(held) == 24 * cfg.top_k and all(0 < h < 24 * cfg.top_k for h in held)
        assert all(counts[0].tolist()[1] == 24 * cfg.top_k for _, counts in got)


@pytest.mark.parametrize("which", ["whole", "share0", "share1"])
def test_the_whole_forward_is_the_references(jax, G, ref, model, shares, which):
    """The program's full-sequence forward (chunked scan, the tile loop,
    both indices in the scans' bodies) against the reference, every expert
    held and either chip's share (whose logits are their own: what the
    absent experts would add is left out of both)."""
    import jax.numpy as jnp

    cfg, params = {"whole": model, "share0": shares[0], "share1": shares[1]}[which]
    ids = np.random.default_rng(5).integers(3, 512, size=(2, 37)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(G.forward(params, jnp.asarray(ids), cfg, attn_impl="xla"))
    for row in range(2):
        want = _ref_logits(ref, params, ids[row], cfg)
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(got[row], want, atol=F32_ATOL)
    if which != "whole":  # and a share's logits are not the whole model's
        whole = _ref_logits(ref, model[1], ids[0], model[0])
        assert np.abs(whole - _ref_logits(ref, params, ids[0], cfg)).max() > 0.05
    # the benchmark's two controls move the reference by far more than rounding
    want = _ref_logits(ref, params, ids[0], cfg)
    for control in (dict(top_k=cfg.top_k - 1), dict(shared=False)):
        assert np.abs(_ref_logits(ref, params, ids[0], cfg, **control) - want).max() > 0.05


# -- prefill then decode through the cache, against the reference's full pass ------------


def _cache(cfg, slots=4, pages_per_seq=20, page_size=8, dtype="float32"):
    import jax.numpy as jnp

    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    return PagedKVCache.create(
        n_layers=cfg.n_cache_layers, leaf_shapes=cfg.cache_leaf_shapes,
        n_pages=1 + slots * pages_per_seq, page_size=page_size, kv_dtype=jnp.dtype(dtype),
        prefer_native=False, state_leaves=cfg.state_leaves, max_slots=slots,
    )


def _tables(rows, pages_per_seq=20):
    return (1 + np.arange(rows)[:, None] * pages_per_seq + np.arange(pages_per_seq)).astype(np.int32)


def _decode(jax, G, cfg, params, kp, vp, state, lens, feed, n, slots=4, active=None):
    """``n`` jitted decode steps feeding ``feed[row][step]``; returns the
    logits [rows, n, vocab], the state and the summed counts."""
    import jax.numpy as jnp

    rows = len(feed)
    tables = jnp.asarray(_tables(slots))
    live = np.zeros((slots,), bool)
    live[:rows] = True if active is None else active
    step = jax.jit(lambda tok, pos, kp, vp, state: G.decode_step(
        params, tok, pos, kp, vp, tables, jnp.asarray(live), cfg, state=state,
        return_counts=True,
    ))
    positions = np.zeros((slots,), np.int32)
    positions[:rows] = lens
    out, pairs, tiles = [], np.zeros(2, np.int64), np.zeros(2, np.int64)
    with jax.default_matmul_precision("highest"):
        for s in range(n):
            tok = np.zeros((slots,), np.int32)
            tok[:rows] = [f[s] for f in feed]
            logits, kp, vp, state, p, t = step(jnp.asarray(tok), jnp.asarray(positions), kp, vp, state)
            out.append(np.asarray(logits)[:rows])
            pairs, tiles = pairs + np.asarray(p), tiles + np.asarray(t)
            positions[:rows] += live[:rows]
    return np.stack(out, axis=1), (kp, vp, state), (pairs, tiles)


def _bucket(jax, G, cfg, params, prompts, cache, bucket=32, slot_ids=None):
    import jax.numpy as jnp

    rows = len(prompts)
    toks = np.zeros((rows, bucket), np.int32)
    for r, p in enumerate(prompts):
        toks[r, : len(p)] = p
    slot_ids = list(range(rows)) if slot_ids is None else slot_ids
    with jax.default_matmul_precision("highest"):
        return G.prefill(
            params, jnp.asarray(toks), cache[0], cache[1], jnp.asarray(_tables(4)[slot_ids]),
            jnp.asarray([len(p) for p in prompts]), cfg, attn_impl="xla", state=cache[2],
            slot_ids=jnp.asarray(slot_ids, jnp.int32),
        )


@pytest.mark.parametrize("which,forms,steps", [
    ("whole", "xla", 100), ("share1", "xla", 24), ("whole", "pallas", 12),
])
def test_bucketed_prefill_then_decode_is_the_references_full_pass(
        jax, G, ref, model, shares, which, forms, steps, monkeypatch):
    """Two requests of different lengths in one bucket call and then
    ``steps`` decode steps in one batch (slots 2 and 3 empty): at every
    served position the logits are the reference's over prompt + fed tokens,
    to float32 rounding; every expert held, a chip's share, and both kernels
    in the interpreter. The steps count their pairs: all of them ``live x
    top_k x layers`` a step, held all of those or the share's part."""
    _force_forms(monkeypatch, G, forms)
    cfg, params = {"whole": model, "share1": shares[1]}[which]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, 512, size=n).tolist() for n in (21, 9)]
    feed = [rng.integers(3, 512, size=steps).tolist() for _ in prompts]
    c = _cache(cfg)
    first, kp, vp, state = _bucket(jax, G, cfg, params, prompts, (c.k_pages, c.v_pages, c.state))
    got, _, (pairs, tiles) = _decode(
        jax, G, cfg, params, kp, vp, state, [21, 9], feed, steps)
    got = np.concatenate([np.asarray(first)[:, None], got], axis=1)
    for r, p in enumerate(prompts):
        want = _ref_logits(ref, params, p + feed[r], cfg)[len(p) - 1:]
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(got[r][:-1], want[:-1], atol=F32_ATOL)
    every = 2 * cfg.top_k * cfg.n_layers * steps
    assert pairs[1] == every
    assert pairs[0] == every if which == "whole" else 0 < pairs[0] < every
    assert tiles[0] == pairs[0] and tiles[1] >= tiles[0] and tiles[1] % 16 == 0


def test_chunked_prefill_then_decode_a_refilled_slot_and_a_slot_that_waits(jax, G, ref, model):
    """A prompt in two chunk calls (the state carried in slot 2, the
    attention layers over the cached prefix), then 100 decode steps of it
    beside a second tenant of slot 0 (a first request served there before:
    its pages and state overwritten from zeros), while slot 1, prefilled and
    not yet decoding, waits: the logits are the reference's, the waiting
    slot's state stands still and it routes no pair."""
    import jax.numpy as jnp

    cfg, params = model
    rng = np.random.default_rng(2)
    long, old, new, waiting = (rng.integers(3, 512, size=n).tolist() for n in (27, 30, 12, 17))
    c = _cache(cfg)
    cache = (c.k_pages, c.v_pages, c.state)
    # slot 0's first tenant, served 5 steps and gone
    _, *cache = _bucket(jax, G, cfg, params, [old], cache)
    _, cache, _ = _decode(jax, G, cfg, params, *cache, [30], [[7] * 5], 5)
    # the chunked prompt into slot 2
    tables, slot = jnp.asarray(_tables(4)[[2]]), jnp.asarray([2], jnp.int32)
    kp, vp, state = cache
    with jax.default_matmul_precision("highest"):
        for offset, n in ((0, 16), (16, 11)):
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = long[offset:offset + n]
            first_long, kp, vp, state = G.prefill_chunk(
                params, jnp.asarray(chunk), kp, vp, tables, jnp.asarray([n]), cfg,
                q_offset=offset, attn_impl="xla", state=state, slot_ids=slot,
            )
    # slot 0's second tenant and the waiting slot 1, one bucket call
    first, kp, vp, state = _bucket(jax, G, cfg, params, [new, waiting], (kp, vp, state))
    before = [np.asarray(leaf[:, 1]) for leaf in state]
    feed = [rng.integers(3, 512, size=100).tolist() for _ in range(3)]
    got, (_, _, state), (pairs, _) = _decode(
        jax, G, cfg, params, kp, vp, state, [12, 17, 27], feed, 100,
        active=[True, False, True],
    )
    for leaf, was in zip(state, before):
        np.testing.assert_array_equal(np.asarray(leaf[:, 1]), was)
    assert pairs.tolist() == [2 * cfg.top_k * cfg.n_layers * 100] * 2  # two live slots
    for r, (prompt, head) in {0: (new, np.asarray(first)[0]), 2: (long, np.asarray(first_long)[0])}.items():
        want = _ref_logits(ref, params, prompt + feed[r], cfg)[len(prompt) - 1:]
        np.testing.assert_allclose(head, want[0], atol=F32_ATOL)
        np.testing.assert_allclose(got[r][:-1], want[1:-1], atol=F32_ATOL)


def test_in_bf16_the_program_keeps_to_the_reference(jax, G, ref):
    """The served precision: bf16 weights and activations into the tile
    matmuls, float32 state, routes and sums, against the float32 reference
    of the same bf16 weights; a chip's share."""
    cfg = G.GraniteHybridConfig.tiny_moe(n_held_experts=4, expert_offset=2)
    params = _share(jax, _params(jax, G, G.GraniteHybridConfig.tiny_moe(), seed=5), 2, 4)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, 512, size=n).tolist() for n in (26, 14)]
    feed = [rng.integers(3, 512, size=5).tolist() for _ in prompts]
    c = _cache(cfg, dtype="bfloat16")
    first, kp, vp, state = _bucket(jax, G, cfg, params, prompts, (c.k_pages, c.v_pages, c.state))
    got, _, _ = _decode(jax, G, cfg, params, kp, vp, state, [26, 14], feed, 5)
    assert state[0].dtype == np.float32 and str(state[1].dtype) == "bfloat16"
    for r, p in enumerate(prompts):
        want = _ref_logits(ref, params, p + feed[r], cfg)[len(p) - 1:]
        near = np.abs(np.concatenate([np.asarray(first)[r][None], got[r][:-1]]) - want[:-1])
        # a route that bf16 settles the other way moves a position's logits by
        # more: at most one position in twelve here
        assert np.quantile(near.max(-1), 0.9) < BF16_ATOL


def test_int8_weights_reach_the_experts_and_serve_near_the_reference(jax, G, ref, model):
    """``quantization="int8"`` quantises the experts' stacks with the mixers
    (``GRANITE_HYBRID_TARGETS``; the router stays), the tile loop reads them
    as LFM2's and Mixtral's are read, and the plan names the loop's form for
    them. Per-channel int8 of float32 weights through 6 layers: logits
    within 0.05 of the float32 reference's (observed 0.007-0.010 a position;
    of size up to 7), where a route one expert short moves them by over 0.05
    (``test_the_whole_forward_is_the_references``)."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import QuantizedWeight, quantize_llama

    cfg, params = model
    quantized = quantize_llama(params, cfg.quant_targets)
    assert all(isinstance(quantized["moe_layers"][n], QuantizedWeight)
               for n in ("moe_gate", "moe_up", "moe_down"))
    assert not isinstance(quantized["moe_layers"]["router"], QuantizedWeight)
    assert isinstance(quantized["mamba_layers"]["gate"], QuantizedWeight)
    assert G.paged_impl_plan(cfg, 8, expert_dtype="int8")["expert_scan"] == "xla"  # the CPU
    ids = np.random.default_rng(7).integers(3, 512, size=(1, 32)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(G.forward(quantized, jnp.asarray(ids), cfg, attn_impl="xla"))[0]
    want = _ref_logits(ref, params, ids[0], cfg)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 0.05


# -- the engine, end to end -----------------------------------------------------------------


def test_the_engine_serves_the_share_and_counts_its_pairs_and_tile_rows(jax, G, ref, shares):
    """Through ``LLMEngine`` under the preset's name: a bucketed and a
    chunked prompt, five requests over three slots, the greedy tokens the
    reference's first choice where it is decided; the decode blocks hand
    both device counts to the counters (``mtpu_routed_pairs_total`` held and
    elsewhere, ``mtpu_expert_tile_rows_total`` pairs and rows) and the plan
    names both forms."""
    import test_granite_hybrid as dense

    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving.engine import MODEL_PRESETS
    from modal_examples_tpu.utils.prometheus import default_registry

    assert MODEL_PRESETS["tiny-granite-moe"]().n_experts == 8
    cfg, params = shares[0]

    def read():
        value = lambda name, **labels: default_registry.value(name, labels) or 0.0  # noqa: E731
        return np.array([
            value(C.ROUTED_PAIRS_TOTAL, where="held"), value(C.ROUTED_PAIRS_TOTAL, where="elsewhere"),
            value(C.EXPERT_TILE_ROWS_TOTAL, kind="pairs"), value(C.EXPERT_TILE_ROWS_TOTAL, kind="rows"),
        ])

    before = read()
    texts = [dense.PROMPTS["short"], dense.PROMPTS["chunked"], "third", "a fourth one", "fifth"]
    eng = dense._engine(cfg, params)
    try:
        assert eng._block_counts == ("routed_pairs", "expert_tile_rows")
        assert eng.impl_plan["expert_scan"] == "xla" and eng.impl_plan["state_step"] == "xla"
        served = [dense._tokens(eng, r) for r in [dense._submit(eng, t) for t in texts]]
    finally:
        eng.stop()
    assert not eng.error_log
    assert len(served[1][0]) > 64  # chunk calls at offsets 0, 32 and 64
    for prompt_ids, out in served:
        assert len(out) == 10
        dense._assert_decided_tokens_are_the_references(jax, ref, params, cfg, prompt_ids, out)
    held, elsewhere, pairs, rows = read() - before
    assert held > 0 and elsewhere > 0 and (held + elsewhere) % (cfg.top_k * cfg.n_layers) == 0
    assert 0.25 < held / (held + elsewhere) < 0.75  # half the experts, about half the pairs
    assert pairs == held and rows >= pairs and rows % 16 == 0


@pytest.mark.parametrize("feature,kw", [
    ("prefix caching", dict(enable_prefix_cache=True)),
    ("int8 KV cache", dict(kv_dtype="int8")),
    ("speculative decoding", dict(speculative=("ngram", 2))),
])
def test_what_the_routed_model_lacks_is_still_refused_by_name(model, feature, kw):
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    kw.setdefault("enable_prefix_cache", False)
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine(cfg, params, max_slots=2, max_model_len=64, page_size=8, **kw)


# -- the checkpoint -----------------------------------------------------------------------


def test_load_hf_weights_maps_the_published_layout_of_the_routed_half(jax, G, ref, model, tmp_path):
    """A seeded tree written in the published layout (``block_sparse_moe``:
    one ``input_linear`` ``[experts, 2 F, D]`` and one ``output_linear``
    ``[experts, D, F]`` for all experts, ``router.layer`` ``[experts, D]``;
    ``shared_mlp`` as the dense model's) loads as the tree it was written
    from; a configuration that states a share, a depth and a vocabulary
    slice loads its experts, its layers and its rows of it."""
    import jax.numpy as jnp
    import test_granite_hybrid as dense

    cfg, params = model
    dense._write_published_checkpoint(jax, cfg, params, tmp_path)
    loaded = G.load_hf_weights(tmp_path, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the cut: the first three layers, experts 2-5, the first 256 rows
    cut = G.GraniteHybridConfig.tiny_moe(
        dtype="float32", vocab_size=256, layer_types=cfg.layer_types[:3], n_held_experts=4,
        expert_offset=2,
    )
    part = G.load_hf_weights(tmp_path, cut)
    assert part["embed"].shape == (256, 64) and part["moe_layers"]["router"].shape == (3, 64, 8)
    np.testing.assert_array_equal(
        part["moe_layers"]["moe_down"], params["moe_layers"]["moe_down"][:3, 2:6])
    np.testing.assert_array_equal(
        part["moe_layers"]["moe_up"], params["moe_layers"]["moe_up"][:3, 2:6])
    assert part["mamba_layers"]["in_z"].shape[0] == 2 and part["attention_layers"]["wq"].shape[0] == 1
    ids = np.random.default_rng(6).integers(3, 256, size=12)
    with jax.default_matmul_precision("highest"):
        got = G.forward(part, jnp.asarray(ids)[None], cut, attn_impl="xla")[0]
    np.testing.assert_allclose(got, _ref_logits(ref, part, ids, cut), atol=F32_ATOL)
