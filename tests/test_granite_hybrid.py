"""Granite-4.0-H on the engine's normal path, at a tiny size on the CPU with
seeded random weights: logits, not tokens, each tolerance with its reason.

The program (``models/granite_hybrid.py``: per-slot recurrent state beside a
paged cache, a chunked Mamba-2 scan for prefill, a state step for decode) is
held to the plain reference (``models/granite_hybrid_reference.py``: float32
``highest``, the recurrence a token at a time, dense attention, no cache).

Tolerances. With float32 weights and activations the two differ only by the
order of float32 sums: ``F32_ATOL`` = 2e-4 on logits of size ~1-3 (observed
<= 3e-5 through 6 layers). In bf16 (the served precision) rounding of weights
is shared (the reference sees the bf16 values) and what is left is bf16
activations: ``BF16_ATOL`` = 0.06 (observed <= 0.03). The lower-precision
controls (the SSM state in bf16; the weights rounded to 8 bits) are compared
in float32 arithmetic so that the tolerance they have to break is the tight
one.
"""

import json

import chunk_tail
import numpy as np
import pytest

F32_ATOL = 2e-4
BF16_ATOL = 0.06


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
    """Seeded weights with every leaf away from its trivial value (norms,
    convolution bias, D) and an embedding large enough for logits of size 1."""
    import jax.numpy as jnp

    params = G.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    for stack in ("mamba_layers", "attention_layers"):
        for name in ("mixer_norm", "mlp_norm", "gate_norm", "conv_b", "D"):
            if name in params[stack]:
                leaf = params[stack][name]
                noise = 0.2 * jax.random.normal(next(keys), leaf.shape, jnp.float32)
                params[stack][name] = (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)
    params["embed"] = (params["embed"].astype(jnp.float32) * 40).astype(params["embed"].dtype)
    return params


@pytest.fixture(scope="module")
def model(jax, G):
    cfg = G.GraniteHybridConfig.tiny(dtype="float32")
    return cfg, _params(jax, G, cfg)


def _ref_logits(jax, ref, params, ids, cfg):
    import jax.numpy as jnp

    return np.asarray(ref.forward(params, jnp.asarray(ids), cfg))


def _cache(jax, cfg, slots=4, n_pages=32, page_size=8):
    import jax.numpy as jnp

    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    return PagedKVCache.create(
        n_layers=cfg.n_cache_layers, leaf_shapes=cfg.cache_leaf_shapes, n_pages=n_pages,
        page_size=page_size, kv_dtype=jnp.float32, prefer_native=False,
        state_leaves=cfg.state_leaves, max_slots=slots,
    )


def _tables(rows, pages_per_seq=8, first=1):
    """Page tables for ``rows`` sequences: disjoint runs of pages from 1 on."""
    t = np.zeros((rows, pages_per_seq), np.int32)
    for r in range(rows):
        t[r] = first + r * pages_per_seq + np.arange(pages_per_seq)
    return t


def _force_state_step(monkeypatch, G, form):
    """Make ``paged_impl_plan`` name ``form`` for the Mamba layers' state
    step, whatever the backend and the shapes: on the CPU ``"pallas"`` runs
    the kernel in the interpreter, which takes any shape."""
    plan = G.paged_impl_plan
    monkeypatch.setattr(G, "paged_impl_plan", lambda *a, **kw: {**plan(*a, **kw), "state_step": form})


# -- the configuration ------------------------------------------------------------------


def test_the_published_config_gives_the_published_shapes(G, tmp_path):
    cfg = G.GraniteHybridConfig()
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.head_dim, cfg.d_inner, cfg.conv_dim,
            cfg.in_proj_dim) == (40, 4, 64, 4096, 4352, 8512)
    assert [s for s in cfg.segments if s[0] == "attention"] == [
        ("attention", 0, 1), ("attention", 1, 1), ("attention", 2, 1), ("attention", 3, 1)]
    assert [s[1:] for s in cfg.segments if s[0] == "mamba"] == [
        (0, 5), (5, 9), (14, 9), (23, 9), (32, 4)]
    assert cfg.state_leaves == ((36, (64, 64, 128), "float32"), (36, (3, 4352), "bfloat16"))
    assert cfg.cache_leaf_shapes == ((4, 128), (4, 128))  # two K/V heads of 64 to a row
    assert 3.19e9 < cfg.param_count < 3.20e9  # ISSUE 31: 3.19 G parameters
    # the pattern is read from layer_types, whatever it is
    path = tmp_path / "config.json"
    path.write_text(json.dumps({
        "vocab_size": 512, "hidden_size": 64, "layer_types": ["attention", "mamba", "mamba"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "shared_intermediate_size": 128,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 2,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 8, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.25, "logits_scaling": 8,
    }))
    got = G.GraniteHybridConfig.from_hf_config(path)
    assert got.segments == (("attention", 0, 1), ("mamba", 0, 2))
    assert got.mamba_n_groups == 2 and got.model is G
    bad = json.loads(path.read_text()) | {"position_embedding_type": "rope"}
    path.write_text(json.dumps(bad))
    with pytest.raises(NotImplementedError, match="position_embedding_type"):
        G.GraniteHybridConfig.from_hf_config(path)


# -- the chunked scan against the sequential recurrence ---------------------------------


def _ssd_sequential(jax, x, dt, A, B, C, h0):
    """The recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t (x_t outer B_t)``,
    ``y_t = h_t C_t`` a token at a time: what ``ssd_chunked`` is held to."""
    import jax.numpy as jnp

    H, G = x.shape[2], B.shape[2]

    def step(h, t):
        x_t, dt_t, b_t, c_t = t  # [b, H, P], [b, H], [b, G, N] x2
        b_t, c_t = (jnp.repeat(a, H // G, axis=1) for a in (b_t, c_t))
        h = jnp.exp(dt_t * A)[..., None, None] * h + (
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), h


@pytest.mark.parametrize("T,groups", [(8, 1), (24, 1), (29, 1), (5, 1), (19, 2)],
                         ids=["one-chunk", "three-chunks", "ragged-29", "short-5", "two-groups"])
def test_the_chunked_scan_is_the_sequential_recurrence(jax, G, T, groups):
    """Lengths that are and are not multiples of the chunk (8), from a state
    that is not zero. float32 both ways: they differ by the order of sums
    (1e-4 of values of size ~1)."""
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(T), 6)
    b, H, P, N = 2, 4, 8, 16
    x = jax.random.normal(k[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (b, T, groups, N))
    C = jax.random.normal(k[4], (b, T, groups, N))
    h0 = jax.random.normal(k[5], (b, H, P, N))
    with jax.default_matmul_precision("highest"):
        y, h = G.ssd_chunked(x, dt, A, B, C, h0, 8)
        y_seq, h_seq = _ssd_sequential(jax, x, dt, A, B, C, h0)
    np.testing.assert_allclose(y, y_seq, atol=1e-4)
    np.testing.assert_allclose(h, h_seq, atol=1e-4)
    # dt = 0 from a position on: the state stands still from there
    cut = T // 2
    dt_cut = dt.at[:, cut:].set(0.0)
    with jax.default_matmul_precision("highest"):
        _, h_cut = G.ssd_chunked(x, dt_cut, A, B, C, h0, 8)
        _, h_half = _ssd_sequential(jax, x[:, :cut], dt[:, :cut], A, B[:, :cut], C[:, :cut], h0)
    np.testing.assert_allclose(h_cut, h_half, atol=1e-4)


# -- prefill then decode through the cache, against the reference's full pass ------------


def _serve(jax, G, cfg, params, prompts, n_decode, *, bucket=32, slots=4, feed=None):
    """Prefill ``prompts`` (one bucket call, rows in slots 0..), then
    ``n_decode`` decode steps feeding ``feed[row]`` (teacher forcing).
    Returns the logits [rows, 1 + n_decode, vocab] and the cache."""
    import jax.numpy as jnp

    cache = _cache(jax, cfg, slots=slots)
    rows = len(prompts)
    toks = np.zeros((rows, bucket), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    for r, p in enumerate(prompts):
        toks[r, : len(p)] = p
    tables = _tables(slots)
    with jax.default_matmul_precision("highest"):
        logits, kp, vp, state = G.prefill(
            params, jnp.asarray(toks), cache.k_pages, cache.v_pages,
            jnp.asarray(tables[:rows]), jnp.asarray(lens), cfg, attn_impl="xla",
            state=cache.state, slot_ids=jnp.arange(rows),
        )
        out = [np.asarray(logits)]
        active = np.zeros((slots,), bool)
        active[:rows] = True
        positions = np.zeros((slots,), np.int32)
        positions[:rows] = lens
        for step in range(n_decode):
            tok = np.zeros((slots,), np.int32)
            tok[:rows] = [feed[r][step] for r in range(rows)]
            logits, kp, vp, state = G.decode_step(
                params, jnp.asarray(tok), jnp.asarray(positions), kp, vp, jnp.asarray(tables),
                jnp.asarray(active), cfg, state=state,
            )
            out.append(np.asarray(logits)[:rows])
            positions[:rows] += 1
    return np.stack(out, axis=1), state


@pytest.mark.parametrize("state_step", ["xla", "pallas"])
def test_prefill_then_decode_is_the_references_full_pass(jax, G, ref, model, state_step, monkeypatch):
    """Two requests of different lengths in one prefill call and one decode
    batch (slots 2 and 3 empty): at every served position the logits are the
    reference's over prompt + fed tokens, to float32 rounding, under either
    form of the state step."""
    _force_state_step(monkeypatch, G, state_step)
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, 512, size=n).tolist() for n in (21, 9)]
    feed = [rng.integers(3, 512, size=6).tolist() for _ in prompts]
    got, _ = _serve(jax, G, cfg, params, prompts, 6, feed=feed)
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(got[r], want, atol=F32_ATOL)


def test_a_padded_bucket_row_leaves_the_state_of_the_prompt_alone(jax, G, ref, model):
    """A row shorter than its bucket, beside a longer one: its state and
    convolution tail are what the same prompt gives alone at its own length
    (the reference's mixer, layer by layer, is not needed: the prompt alone
    in a bucket of exactly its length has no padding at all)."""
    import jax.numpy as jnp

    cfg, params = model
    rng = np.random.default_rng(2)
    short, long = rng.integers(3, 512, size=11).tolist(), rng.integers(3, 512, size=30).tolist()
    _, padded = _serve(jax, G, cfg, params, [long, short], 0)
    _, alone = _serve(jax, G, cfg, params, [short], 0, bucket=11)
    np.testing.assert_allclose(padded[0][:, 1], alone[0][:, 0], atol=1e-5)  # SSM state
    np.testing.assert_allclose(padded[1][:, 1], alone[1][:, 0], atol=1e-6)  # convolution tail
    assert float(jnp.abs(padded[0][:, 1]).max()) > 0.01
    # and the slots no row filled are untouched
    assert float(jnp.abs(padded[0][:, 2:]).max()) == 0.0


def test_a_prompt_in_two_chunk_calls_is_the_prompt_in_one(jax, G, model):
    """``prefill_chunk`` at offset 0 and at offset 16 (the state carried in
    the slot, the attention layers over the cached prefix) against one call:
    the last logits and the state agree to float32 rounding."""
    import jax.numpy as jnp

    cfg, params = model
    prompt = np.random.default_rng(3).integers(3, 512, size=27).astype(np.int32)
    cache = _cache(jax, cfg)
    tables = jnp.asarray(_tables(1))
    slot = jnp.asarray([2], jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = np.zeros((1, 32), np.int32)
        whole[0, :27] = prompt
        want, _, _, state_one = G.prefill(
            params, jnp.asarray(whole), cache.k_pages, cache.v_pages, tables,
            jnp.asarray([27]), cfg, attn_impl="xla", state=cache.state, slot_ids=slot,
        )
        kp, vp, state = cache.k_pages, cache.v_pages, cache.state
        for offset, n in ((0, 16), (16, 11)):
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = prompt[offset:offset + n]
            got, kp, vp, state = G.prefill_chunk(
                params, jnp.asarray(chunk), kp, vp, tables, jnp.asarray([n]), cfg,
                q_offset=offset, attn_impl="xla", state=state, slot_ids=slot,
            )
    np.testing.assert_allclose(got, want, atol=F32_ATOL)
    np.testing.assert_allclose(state[0], state_one[0], atol=1e-4)
    np.testing.assert_allclose(state[1], state_one[1], atol=1e-6)
    assert float(jnp.abs(state[0][:, [0, 1, 3]]).max()) == 0.0  # only slot 2 was written


def test_a_slot_that_is_not_active_keeps_its_state(jax, G, model):
    """Between a prefill and its first decode step (the first token not
    harvested yet) other slots' decode steps run over every row: the waiting
    slot's state and tail stand still."""
    import jax.numpy as jnp

    cfg, params = model
    prompt = np.random.default_rng(4).integers(3, 512, size=13).tolist()
    _, state = _serve(jax, G, cfg, params, [prompt], 0)
    cache = _cache(jax, cfg)
    active = jnp.asarray([False, True, False, False])
    _, _, _, after = G.decode_step(
        params, jnp.asarray([5, 7, 0, 0]), jnp.asarray([13, 4, 0, 0]), cache.k_pages,
        cache.v_pages, jnp.asarray(_tables(4)), active, cfg, state=state,
    )
    np.testing.assert_array_equal(after[0][:, 0], state[0][:, 0])
    np.testing.assert_array_equal(after[1][:, 0], state[1][:, 0])
    assert float(jnp.abs(after[0][:, 1]).max()) > 0.0  # the active slot moved


def test_in_bf16_the_program_keeps_to_the_reference(jax, G, ref):
    """The served precision: bf16 weights and activations, float32 state and
    sums, against the float32 reference of the same bf16 weights."""
    cfg = G.GraniteHybridConfig.tiny()
    params = _params(jax, G, cfg, seed=5)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, 512, size=n).tolist() for n in (26, 14)]
    feed = [rng.integers(3, 512, size=5).tolist() for _ in prompts]
    got, state = _serve(jax, G, cfg, params, prompts, 5, feed=feed)
    assert state[0].dtype == np.float32 and str(state[1].dtype) == "bfloat16"
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        np.testing.assert_allclose(got[r], want, atol=BF16_ATOL)


@pytest.mark.parametrize("control", ["bf16-state", "8-bit-weights"])
def test_a_computation_in_lower_precision_breaks_a_tolerance(jax, G, ref, model, control, monkeypatch):
    """The comparison of ``test_prefill_then_decode_is_the_references_full_
    pass`` again, with the SSM state held in bf16, or the matmul weights
    rounded to 8 bits (the program's own ``quantization="int8"`` rounding):
    each breaks a tolerance of the float32 run by a wide margin."""
    from modal_examples_tpu.models.quantize import dequantize_weight, quantize_llama

    import jax.numpy as jnp

    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, 512, size=n).tolist() for n in (21, 9)]
    feed = [rng.integers(3, 512, size=6).tolist() for _ in prompts]
    served = params
    if control == "bf16-state":
        monkeypatch.setattr(G, "STATE_DTYPE", "bfloat16")  # a constant of the program, no option
    else:
        rounded = quantize_llama(params, cfg.quant_targets)
        assert type(rounded["mamba_layers"]["in_xbc"]).__name__ == "QuantizedWeight"
        assert type(rounded["mamba_layers"]["conv_w"]).__name__ != "QuantizedWeight"
        served = jax.tree.map(
            lambda w: dequantize_weight(w, jnp.float32) if hasattr(w, "scale") else w,
            rounded, is_leaf=lambda w: hasattr(w, "scale"),
        )
    got, state = _serve(jax, G, cfg, served, prompts, 6, feed=feed)
    assert str(state[0].dtype) == G.STATE_DTYPE
    monkeypatch.undo()
    _, sound = _serve(jax, G, cfg, params, prompts, 6, feed=feed)
    worst = 0.0
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        worst = max(worst, float(np.abs(got[r] - want).max()))
    state_error = float(np.abs(np.asarray(state[0], np.float32) - np.asarray(sound[0])).max())
    if control == "8-bit-weights":
        assert worst > 10 * F32_ATOL  # the logits leave their tolerance by a wide margin
    else:
        # the state leaves its own (1e-4, the chunk-call test's) by a wide margin. The
        # logits of six steps at this size do not see it (5e-5 observed, inside
        # F32_ATOL): what the chip's check reads of a bf16 state is PERF.md section 6
        assert state_error > 10 * 1e-4


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
def test_a_narrower_tail_chunk_leaves_the_state_at_the_last_real_token(tail_engine, case, monkeypatch):
    """The decode steps after a tail chunk of the bucket that holds what is
    left start from the state the chunk padded to the largest bucket left:
    the same greedy tokens (tests/chunk_tail.py)."""
    chunk_tail.check(tail_engine, case, monkeypatch)


PROMPTS = {
    "short": "a hybrid of scans",  # one bucketed call, padded
    "chunked": "a prompt long enough to need a second and a third chunk call over carried state",
}


@pytest.mark.parametrize("state_step", ["xla", "pallas"])
def test_the_engine_serves_the_references_first_choice_and_reuses_slots(
    jax, G, ref, model, state_step, monkeypatch
):
    """Through ``LLMEngine``: a bucketed prompt and a chunked one (three
    chunk calls, the state carried in the slot between them), five requests
    over three slots so that every slot is taken a second time, requests of
    different lengths in one decode batch. A slot's second tenant is served
    what a fresh engine serves it. Under either form of the state step (the
    kernel in the interpreter), and the engine's plan and its
    ``mtpu_decode_impl`` series name the form that served."""
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.utils.prometheus import default_registry

    _force_state_step(monkeypatch, G, state_step)
    cfg, params = model
    texts = [PROMPTS["short"], PROMPTS["chunked"], "third", PROMPTS["chunked"][::-1], "fifth one"]
    stepped0 = default_registry.value(C.STATE_ROWS_TOTAL, {"kind": "stepped"}) or 0.0
    live0 = default_registry.value(C.STATE_ROWS_TOTAL, {"kind": "live"}) or 0.0
    eng = _engine(cfg, params)
    try:
        assert len(eng.cache.state) == 2 and eng.cache.k_pages.shape[0] == cfg.n_cache_layers
        assert eng.cache.state[0].shape == (4, 3, 8, 16, 16)  # [mamba layers, slots, H, P, N]
        assert default_registry.value(C.STATE_BYTES) == eng.cache.state_bytes() > 0
        assert eng.impl_plan["state_step"] == state_step
        assert state_step in [labels["state_step"] for labels, _ in default_registry.series(C.DECODE_IMPL)]
        served = [_tokens(eng, r) for r in [_submit(eng, t) for t in texts]]
    finally:
        eng.stop()
    assert not eng.error_log
    assert len(served[1][0]) > 64  # chunk calls at offsets 0, 32 and 64
    for prompt_ids, out in served:
        assert len(out) == 10
        _assert_decided_tokens_are_the_references(jax, ref, params, cfg, prompt_ids, out)
    stepped = default_registry.value(C.STATE_ROWS_TOTAL, {"kind": "stepped"}) - stepped0
    live = default_registry.value(C.STATE_ROWS_TOTAL, {"kind": "live"}) - live0
    assert stepped > 0 and stepped % (3 * 4) == 0  # max_slots x the steps of a block
    assert 0 < live <= stepped
    fresh = _engine(cfg, params)
    try:
        assert _tokens(fresh, _submit(fresh, texts[4])) == served[4]
    finally:
        fresh.stop()


def test_a_request_requeued_for_want_of_pages_is_served_as_undisturbed(jax, G, ref, model):
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


# -- what is refused, and the checkpoint ----------------------------------------------------

REFUSED = {
    "prefix caching": dict(enable_prefix_cache=True),
    "int8 KV cache": dict(kv_dtype="int8"),
    "speculative decoding": dict(speculative=("ngram", 2)),
    "tensor parallelism": "mesh",
    "vision": dict(vision=(object(), None)),
    "disaggregated transfer": dict(tiered_prefix=True),
    "a Pallas paged_impl or scatter_impl": dict(paged_impl="pallas"),
}


@pytest.mark.parametrize(
    "backend,size,state_dtype,want",
    [
        ("cpu", "published", None, "xla"),  # the interpreter is no serving path
        ("tpu", "published", None, "pallas"),
        ("tpu", "published", "bfloat16", "xla"),  # the kernel is float32's
        ("tpu", "tiny", None, "xla"),  # a [16, 16] state is no whole vreg
    ],
    ids=["cpu", "tpu-published", "tpu-bf16-leaf", "tpu-tiny"],
)
def test_the_plan_names_the_state_steps_form_from_backend_dtype_and_shape(
    jax, G, monkeypatch, backend, size, state_dtype, want
):
    """``paged_impl_plan`` chooses the state step from what it can see, and
    no argument or environment variable says otherwise."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = G.GraniteHybridConfig() if size == "published" else G.GraniteHybridConfig.tiny()
    plan = G.paged_impl_plan(cfg, 16, state_dtype=state_dtype)
    assert plan["state_step"] == want
    assert plan["attention"] == "xla-gather" and plan["scatter"] == "xla"


@pytest.mark.parametrize("feature", list(REFUSED))
def test_each_feature_the_model_lacks_is_refused_by_name(jax, G, model, feature):
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    kw = REFUSED[feature]
    if kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tensor",)))
    kw = {"enable_prefix_cache": False, **kw}
    assert feature in cfg.unsupported
    with pytest.raises(NotImplementedError, match=feature):
        LLMEngine(cfg, params, max_slots=2, max_model_len=64, prefill_buckets=(32,), **kw)


def test_disaggregated_roles_and_lora_are_refused_too(jax, G, model):
    import jax.numpy as jnp

    from modal_examples_tpu.scheduling.router import EngineReplica
    from modal_examples_tpu.serving.engine import MODEL_PRESETS

    cfg, params = model
    assert MODEL_PRESETS["tiny-granite-hybrid"]().model is G
    eng = _engine(cfg, params)
    try:
        with pytest.raises(NotImplementedError, match="disaggregated transfer"):
            EngineReplica(eng, "p0", role="prefill")
        with pytest.raises(NotImplementedError, match="disaggregated transfer"):
            eng.prefill_sync(eng.make_request("x"))
        assert EngineReplica(eng, "u0").role == "unified"
    finally:
        eng.stop()
    with pytest.raises(NotImplementedError, match="LoRA"):
        G.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, lora={})
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        G.partition_specs(cfg)


def _write_published_checkpoint(jax, cfg, params, model_dir):
    """``params`` as ``model.safetensors`` under the published tensor names
    (a routed model's ``block_sparse_moe.*`` among them)."""
    from safetensors.numpy import save_file

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
    }
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(cfg.layer_types):
        layer = jax.tree.map(lambda a: np.asarray(a[seen[kind]]), params[f"{kind}_layers"])
        seen[kind] += 1
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = layer["mixer_norm"]
        tensors[p + "post_attention_layernorm.weight"] = layer["mlp_norm"]
        tensors[p + "shared_mlp.input_linear.weight"] = np.concatenate(
            [layer["gate"], layer["up"]], axis=1).T
        tensors[p + "shared_mlp.output_linear.weight"] = layer["down"].T
        if "moe_layers" in params:  # one tensor for all experts: [E, 2F, D], [E, D, F]; [E, D]
            moe = jax.tree.map(lambda a: np.asarray(a[i]), params["moe_layers"])
            tensors[p + "block_sparse_moe.router.layer.weight"] = moe["router"].T
            tensors[p + "block_sparse_moe.input_linear.weight"] = np.concatenate(
                [moe["moe_gate"], moe["moe_up"]], axis=2).transpose(0, 2, 1)
            tensors[p + "block_sparse_moe.output_linear.weight"] = moe["moe_down"].transpose(0, 2, 1)
        if kind == "mamba":
            tensors[p + "mamba.in_proj.weight"] = np.concatenate(
                [layer["in_z"], layer["in_xbc"], layer["in_dt"]], axis=1).T
            tensors[p + "mamba.conv1d.weight"] = layer["conv_w"].T[:, None, :]
            tensors[p + "mamba.conv1d.bias"] = layer["conv_b"]
            for ours, theirs in (("A_log", "A_log"), ("D", "D"), ("dt_bias", "dt_bias"),
                                 ("gate_norm", "norm.weight")):
                tensors[p + "mamba." + theirs] = layer[ours]
            tensors[p + "mamba.out_proj.weight"] = layer["out_proj"].T
        else:
            for n in "qkvo":
                tensors[p + f"self_attn.{n}_proj.weight"] = layer["w" + n].T
    save_file({k: np.ascontiguousarray(v) for k, v in tensors.items()},
              str(model_dir / "model.safetensors"))


def test_load_hf_weights_maps_the_published_names(jax, G, ref, model, tmp_path):
    """A made-up tiny checkpoint under the published tensor names (torch's
    ``[out, in]`` matrices, ``conv1d.weight`` ``[conv_dim, 1, d_conv]``,
    ``input_linear`` and ``in_proj`` whole) loads as the tree it was written
    from, and gives its logits."""
    import jax.numpy as jnp

    cfg, params = model
    _write_published_checkpoint(jax, cfg, params, tmp_path)
    loaded = G.load_hf_weights(tmp_path, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ids = np.random.default_rng(6).integers(3, 512, size=12)
    with jax.default_matmul_precision("highest"):
        got = G.forward(loaded, jnp.asarray(ids)[None], cfg, attn_impl="xla")[0]
    np.testing.assert_allclose(got, _ref_logits(jax, ref, params, ids, cfg), atol=F32_ATOL)
    quantized = G.load_hf_weights(tmp_path, cfg, quantization="int8")
    assert type(quantized["attention_layers"]["wq"]).__name__ == "QuantizedWeight"
    with pytest.raises(FileNotFoundError):
        G.load_hf_weights(tmp_path / "nothing", cfg)


# -- the models that declare no per-slot state are as they were -----------------------------


def test_a_model_without_per_slot_state_gets_none_and_no_new_program_arguments(jax):
    """Llama's cache has no per-slot leaves, and its three engine programs
    take exactly the arguments they took (an empty ``state`` and a ``None``
    for the slot ids are no arguments of a lowered program): the counts below
    are the parent commit's."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    cfg = llama.LlamaConfig.tiny()
    eng = LLMEngine(cfg, max_slots=4, page_size=8, max_model_len=64, prefill_buckets=(16,),
                    prefill_batch=2, decode_block=4)
    try:
        assert eng.cache.state == () and eng.cache.state_bytes() == 0
        assert eng._state_args([1], 2) == {} == eng._state_args()
        n_params = len(jax.tree.leaves(eng.params))
        B, pp = eng.max_slots, eng.pages_per_slot
        i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
        block = eng._block_jit.lower(
            eng.params, eng.cache.k_pages, eng.cache.v_pages, i32(B), i32(B),
            jnp.zeros((B,), bool), i32(B), i32(B, pp), jnp.zeros((B,), bool), eng._next_key(),
            f32(B), f32(B), i32(B), i32(B), **eng._state_args(),
        )
        assert len(jax.tree.leaves(block.in_avals)) == n_params + 2 + 11
        chunk = eng._chunk_jit(16).lower(
            eng.params, i32(1, 16), eng.cache.k_pages, eng.cache.v_pages, i32(1, pp), i32(1),
            eng._next_key(), f32(1), f32(1), i32(1), i32(1), i32(1),
            **eng._state_args([0], 1), cfg=cfg,
        )
        assert len(jax.tree.leaves(chunk.in_avals)) == n_params + 1 + 2 + 2 + 6
        assert "ssm" not in block.as_text() and "ssm" not in chunk.as_text()
    finally:
        eng.stop()
