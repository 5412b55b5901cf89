"""SmallThinker on the engine's normal path (models/smallthinker.py): the
program against the plain reference (models/smallthinker_reference.py) through
the two page groups, with a window (32) far shorter than the contexts so that
a sequence's ring of window pages turns several times; the controls; the cache
manager's second budget; the flash kernel's window; ReGLU through the routed
layer's two tile loops; the engine end to end.

Tolerances. ``F32_ATOL`` 2e-3: program and reference both run float32 at
``highest`` and differ by the order of their sums (online softmax over chunks
and tiles against one dense softmax; measured 4e-6 .. 3e-4 at these sizes);
2e-3 is some ten times that and a hundredth of what the weakest control moves
(0.3). ``CONTROL_MOVES`` 0.1: fifty times the tolerance; the controls move
the logits by 1 .. 4.
"""

import numpy as np
import pytest

F32_ATOL = 2e-3
CONTROL_MOVES = 0.1
PAGE, WINDOW = 8, 32
RING = WINDOW // PAGE + 1  # pages a sequence holds in the window group


@pytest.fixture(scope="module")
def jax():
    import jax

    return jax


@pytest.fixture(scope="module")
def L():
    from modal_examples_tpu.models import smallthinker

    return smallthinker


@pytest.fixture(scope="module")
def ref():
    from modal_examples_tpu.models import smallthinker_reference

    return smallthinker_reference


@pytest.fixture(scope="module")
def model(jax, L):
    """Two periods of (global, window, window, window) in float32."""
    cfg = L.SmallThinkerConfig.tiny(dtype="float32")
    return cfg, L.init_params(jax.random.PRNGKey(1), cfg)


def _ref_logits(jax, ref, params, tokens, cfg, **kw):
    import jax.numpy as jnp

    return np.asarray(ref.forward(params, jnp.asarray(tokens), cfg, **kw), np.float32)


# -- the configuration ----------------------------------------------------------------------


def test_the_configuration_declares_its_two_page_groups(L):
    cfg = L.SmallThinkerConfig()
    assert (cfg.n_layers, cfg.n_window_layers, cfg.n_cache_layers, cfg.period) == (52, 39, 13, 4)
    assert cfg.window_group == (39, 4096) and cfg.cache_leaf_shapes == ((4, 128), (4, 128))
    assert cfg.param_count == 21_506_562_560  # 21.5 B by the config's keys
    tiny = L.SmallThinkerConfig.tiny()
    assert tiny.window_group == (6, WINDOW) and tiny.n_cache_layers == 2 and tiny.period == 4
    assert L.SmallThinkerConfig.tiny(window_layout=(0,) * 4, rope_layout=(1,) * 4).window_group is None
    ragged = L.SmallThinkerConfig.tiny(window_layout=(0, 1, 1, 0, 1), rope_layout=(1, 1, 0, 0, 1))
    assert ragged.period == 5  # no shorter unit repeats: the scan's body is the whole stack
    with pytest.raises(ValueError):
        L.SmallThinkerConfig.tiny(rope_layout=(0, 1))


def test_the_benchmarks_file_is_the_first_sixteen_layers(L):
    cfg = L.SmallThinkerConfig.from_hf_config(
        "benchmarks/serving/configs/smallthinker-21b-a3b-int8-1chip.json"
    )
    assert cfg.window_layout == cfg.rope_layout == (0, 1, 1, 1) * 4
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2560, 28, 4, 128)
    assert (cfg.n_experts, cfg.top_k_experts, cfg.moe_ffn_dim) == (64, 6, 768)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.norm_eps) == (4096, 1.5e6, 1e-6)
    assert cfg.window_group == (12, 4096) and cfg.n_cache_layers == 4 and cfg.vocab_size == 32768


def test_the_preset_is_in_the_engines_table_and_refusals_are_by_name(model):
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine
    from modal_examples_tpu.serving.engine import MODEL_PRESETS

    assert MODEL_PRESETS["tiny-smallthinker"]().window_group == (6, WINDOW)
    cfg, params = model
    for kw, said in (
        ({"enable_prefix_cache": True}, "prefix caching"),
        ({"enable_prefix_cache": False, "kv_dtype": "int8"}, "int8 KV cache"),
        ({"enable_prefix_cache": False, "speculative": ("ngram", 2)}, "speculative decoding"),
    ):
        kw.setdefault("kv_dtype", jnp.float32)
        with pytest.raises(NotImplementedError, match=said):
            LLMEngine(cfg, params, max_slots=2, page_size=PAGE, max_model_len=64, **kw)


# -- (b) the program in float32 is the reference ------------------------------------------------


def test_forward_is_the_reference_and_each_control_moves_it(jax, L, ref, model):
    """No cache: 96 positions, three windows long. The controls leave the
    positions inside the first window alone (``no-window``) or move every one
    (``rope-everywhere``), and move the rest by far more than the tolerance."""
    import jax.numpy as jnp

    cfg, params = model
    tokens = np.random.default_rng(0).integers(3, 512, size=96)
    want = _ref_logits(jax, ref, params, tokens, cfg)
    got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_ATOL)
    no_window = _ref_logits(jax, ref, params, tokens, cfg, control="no-window")
    np.testing.assert_allclose(no_window[:WINDOW], want[:WINDOW], atol=1e-5)
    assert np.abs(no_window[WINDOW + 8:] - want[WINDOW + 8:]).max(axis=-1).min() > CONTROL_MOVES
    everywhere = _ref_logits(jax, ref, params, tokens, cfg, control="rope-everywhere")
    assert np.abs(everywhere[8:] - want[8:]).max(axis=-1).min() > CONTROL_MOVES
    with pytest.raises(ValueError):
        ref.forward(params, jnp.asarray(tokens), cfg, control="int4")


def test_the_int4_control_moves_the_logits(jax, ref, model):
    """(c), third control: the matmul weights requantised to int4."""
    from modal_examples_tpu.models.quantize import dequantize_weight, quantize_weight

    cfg, params = model
    tokens = np.random.default_rng(0).integers(3, 512, size=48)
    low = dict(params, layers={
        k: dequantize_weight(quantize_weight(w, 4), w.dtype) if k in cfg.quant_targets else w
        for k, w in params["layers"].items()
    })
    moved = np.abs(_ref_logits(jax, ref, low, tokens, cfg) - _ref_logits(jax, ref, params, tokens, cfg))
    assert moved.max(axis=-1).min() > CONTROL_MOVES


def test_the_route_is_the_top_k_of_the_logits_under_a_softmax_of_the_k(jax):
    """Mixtral's route (softmax over all, top-k, renormalised) is the
    published one (top-k of the logits, softmax over the k), tie to the lower
    index included: every choice equal, the weights to float32 rounding."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    logits = jax.random.normal(jax.random.PRNGKey(3), (257, 64), jnp.float32) * 3.0
    logits = logits.at[:, 7].set(logits[:, 3])  # a tie in every row
    weights, ids = moe.route_group_limited(jax.nn.softmax(logits, -1), 6, renormalize=True)
    top, want_ids = jax.lax.top_k(logits, 6)
    assert (np.asarray(ids) == np.asarray(want_ids)).all()
    np.testing.assert_allclose(weights, jax.nn.softmax(top, -1), rtol=2e-6, atol=1e-7)


# -- (a) prefill then decode through the two page groups ---------------------------------------


def _cache(jax, cfg, *, slots=4, pages_per_slot=24, n_window_pages=None):
    import jax.numpy as jnp

    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    return PagedKVCache.create(
        n_layers=cfg.n_cache_layers, leaf_shapes=cfg.cache_leaf_shapes,
        n_pages=1 + slots * pages_per_slot, page_size=PAGE, kv_dtype=jnp.float32,
        max_slots=slots, window_group=cfg.window_group, n_window_pages=n_window_pages,
        prefer_native=False,
    )


def _tables(slots, pages_per_slot=24):
    return 1 + np.arange(slots * pages_per_slot, dtype=np.int32).reshape(slots, pages_per_slot)


class _Served:
    """One cache and the jitted programs over it: prompts go in by a bucket
    call or by chunk calls at run-time offsets, tokens are fed to the decode
    step, and the logits come back row by row."""

    def __init__(self, jax, L, cfg, params, slots=4, impl=None, **cache_kw):
        import jax.numpy as jnp

        self.jax, self.jnp, self.L, self.cfg, self.params = jax, jnp, L, cfg, params
        self.slots = slots
        self.cache = _cache(jax, cfg, slots=slots, **cache_kw)
        self.kp, self.vp, self.state = self.cache.k_pages, self.cache.v_pages, self.cache.window_pages
        self.tables = _tables(slots)
        self.positions = np.zeros((slots,), np.int32)
        self.active = np.zeros((slots,), bool)
        self._decode = jax.jit(
            lambda p, tok, pos, kp, vp, tab, act, st, wt: L.decode_step(
                p, tok, pos, kp, vp, tab, act, cfg, impl=impl, state=st, window_tables=wt
            )
        )
        self._chunk = jax.jit(
            lambda p, tok, kp, vp, tab, lens, st, wt, off, prefix_len: L.prefill_chunk(
                p, tok, kp, vp, tab, lens, cfg, q_offset=off, prefix_len=prefix_len,
                state=st, window_tables=wt,
            ),
            static_argnames=("prefix_len",),
        )

    def admit(self, slot, longest):
        self.cache.window.install(slot, self.cache.window.claim(longest))

    def _window_rows(self, slots):
        return self.jnp.asarray(self.cache.window.tables[list(slots)])

    def bucket(self, prompts, bucket):
        """One bucket call, prompt r into slot r. Returns the last logits."""
        jnp = self.jnp
        rows = len(prompts)
        toks = np.zeros((rows, bucket), np.int32)
        lens = np.array([len(p) for p in prompts], np.int32)
        for r, p in enumerate(prompts):
            toks[r, : len(p)] = p
        logits, self.kp, self.vp, self.state = self.L.prefill(
            self.params, jnp.asarray(toks), self.kp, self.vp, jnp.asarray(self.tables[:rows]),
            jnp.asarray(lens), self.cfg, state=self.state, window_tables=self._window_rows(range(rows)),
        )
        self.positions[:rows], self.active[:rows] = lens, True
        return np.asarray(logits)

    def chunks(self, slot, prompt, width, prefix_of=lambda offset: offset):
        """The prompt in chunk calls of ``width`` rows, the offset an
        argument, the prefix gathered at ``prefix_of(offset)`` positions."""
        jnp = self.jnp
        for offset in range(0, len(prompt), width):
            part = prompt[offset:offset + width]
            toks = np.zeros((1, width), np.int32)
            toks[0, : len(part)] = part
            logits, self.kp, self.vp, self.state = self._chunk(
                self.params, jnp.asarray(toks), self.kp, self.vp,
                jnp.asarray(self.tables[slot:slot + 1]), jnp.asarray([len(part)], np.int32),
                self.state, self._window_rows([slot]), jnp.int32(offset),
                prefix_len=prefix_of(offset),
            )
        self.positions[slot], self.active[slot] = len(prompt), True
        return np.asarray(logits)

    def decode(self, feed):
        """``feed[slot]``: the tokens fed to a slot, one a step. Returns
        {slot: logits [steps, vocab]}."""
        jnp = self.jnp
        steps = len(next(iter(feed.values())))
        out = {slot: [] for slot in feed}
        for step in range(steps):
            tok = np.zeros((self.slots,), np.int32)
            for slot, tokens in feed.items():
                tok[slot] = tokens[step]
            logits, self.kp, self.vp, self.state = self._decode(
                self.params, jnp.asarray(tok), jnp.asarray(self.positions), self.kp, self.vp,
                jnp.asarray(self.tables), jnp.asarray(self.active), self.state,
                jnp.asarray(self.cache.window.tables),
            )
            logits = np.asarray(logits)
            for slot in feed:
                out[slot].append(logits[slot])
            self.positions[self.active] += 1
        return {slot: np.stack(rows) for slot, rows in out.items()}


def _case(seed, lengths, n):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, 512, size=k).tolist() for k in lengths]
    return prompts, [rng.integers(3, 512, size=n).tolist() for _ in prompts]


@pytest.mark.parametrize("impl", [None, "pallas"], ids=["the-loop", "the-kernel"])
def test_bucketed_prefill_then_three_windows_of_decode_is_the_references_full_pass(jax, L, ref, model, impl):
    """Two requests in one bucket call of 64 rows, one shorter than the
    window (21) and one longer than a ring holds (50 > 40: the call keeps
    only its last five pages in the window group), then 100 decode steps,
    three windows' worth: every sequence's ring turns at least twice (the
    pages a sequence holds never pass ``RING``), slots 2 and 3 idle. At
    every served position the logits are the reference's over prompt + fed
    tokens: through the chunked loop (what the CPU's plan picks), and
    through the ragged kernel's all-heads form in both page groups (the
    interpreter here), a ring read from its first live page with a wrap."""
    cfg, params = model
    prompts, feed = _case(1, (21, 50), 100)
    served = _Served(jax, L, cfg, params, impl=impl)
    for slot, p in enumerate(prompts):
        served.admit(slot, len(p) + 100)
    first = served.bucket(prompts, 64)
    later = served.decode(dict(enumerate(feed)))
    window = served.cache.window
    assert window.ring == RING and [window.held(s) for s in range(4)] == [RING, RING, 0, 0]
    assert window.occupancy()["pages_used"] == 2 * RING == window.occupancy()["pages_peak"]
    for r, p in enumerate(prompts):
        want = _ref_logits(jax, ref, params, p + feed[r], cfg)[len(p) - 1:]
        got = np.concatenate([first[r:r + 1], later[r]])[:-1]
        np.testing.assert_allclose(got, want[:-1], atol=F32_ATOL)
    assert window.recycled(21, 121) == 121 // PAGE + (121 % PAGE > 0) - RING  # pages begun past the first turn


@pytest.mark.parametrize("bucketed", [False, True], ids=["prefix-is-the-offset", "prefix-bucket-longer"])
def test_chunked_prefill_across_the_windows_edge_is_the_references(jax, L, ref, model, bucketed):
    """A prompt of 75 tokens in five chunk calls of 16 rows at run-time
    offsets 0 .. 64: from offset 48 on a window layer reads a window's worth
    of the ring and nothing before it. With the prefix gathered at the
    offset itself, and at one static bucket of 64 for every offset (the
    rows before the sequence's start masked by ``k_first``). Then 40 decode
    steps in slot 1, its neighbours idle."""
    cfg, params = model
    (prompt,), (feed,) = _case(2, (75,), 40)
    served = _Served(jax, L, cfg, params)
    served.admit(1, len(prompt) + 40)
    first = served.chunks(1, prompt, 16, (lambda o: 64 if o else 0) if bucketed else (lambda o: o))
    later = served.decode({1: feed})[1]
    want = _ref_logits(jax, ref, params, prompt + feed, cfg)[len(prompt) - 1:]
    np.testing.assert_allclose(np.concatenate([first, later])[:-1], want[:-1], atol=F32_ATOL)


def test_a_refilled_slot_sees_nothing_of_its_predecessors_window_pages(jax, L, ref, model):
    """(d) Slot 0 serves a long sequence (its ring full and turned), is
    released, and is given, with the very same physical window pages, a
    prompt of 11 tokens: served what the reference serves it alone."""
    cfg, params = model
    (old,), (old_feed,) = _case(3, (60,), 30)
    (new,), (new_feed,) = _case(4, (11,), 12)
    served = _Served(jax, L, cfg, params, n_window_pages=1 + RING)  # one sequence's worth
    served.admit(0, 90)
    before = list(served.cache.window.tables[0])
    served.bucket([old], 64)
    served.decode({0: old_feed})
    served.cache.window.release(0)
    assert served.cache.window.occupancy()["pages_used"] == 0 and not served.cache.window.tables.any()
    served.admit(0, len(new) + 12)
    assert set(served.cache.window.tables[0]) - {0} <= set(before)  # two pages of the same five
    first = served.bucket([new], 16)
    later = served.decode({0: new_feed})[0]
    want = _ref_logits(jax, ref, params, new + new_feed, cfg)[len(new) - 1:]
    np.testing.assert_allclose(np.concatenate([first, later])[:-1], want[:-1], atol=F32_ATOL)


# -- (e) the cache manager: two budgets ----------------------------------------------------------


def test_the_window_group_claims_a_ring_at_most_and_refuses_past_its_budget(jax, model):
    from modal_examples_tpu.serving.kv_cache import OutOfPages

    cfg, _ = model
    cache = _cache(jax, cfg, slots=3, n_window_pages=1 + 2 * RING)
    window = cache.window
    assert cache.window_pages[0].shape == (6, 1 + 2 * RING, PAGE, 2, 16)
    assert cache.k_pages.shape == (2, 1 + 3 * 24, PAGE, 2, 16)
    assert [window.pages_for(n) for n in (1, 8, 9, 32, 33, 40, 41, 4000)] == [1, 1, 2, 4, 5, 5, 5, 5]
    a, b = window.claim(4000), window.claim(17)
    assert len(a) == RING and len(b) == 3 and 0 not in a + b
    with pytest.raises(OutOfPages):
        window.claim(24)  # 3 pages, 2 left
    window.install(0, a)
    window.install(2, b)
    assert window.held(0) == RING and list(window.tables[2]) == b + [0, 0]
    occupancy = cache.occupancy()
    assert occupancy["window"] == {
        "pages_used": 8, "pages_free": 2, "pages_total": 10, "pages_peak": 8, "ring": RING,
    }
    assert occupancy["pages_used"] == 0  # the whole-context group counts its own
    assert cache.bytes() == (2 * 73 + 6 * 11) * 2 * PAGE * 2 * 16 * 4
    window.release(0)
    assert window.held(0) == 0 and len(window.claim(4000)) == RING
    assert cache.beside == cache.window_pages and len(jax.tree.leaves(cache)) == 4


def test_admission_checks_both_budgets_and_release_returns_both(model):
    """Through the engine's own claim: short of either budget nothing is held
    and the request waits; a finished request returns the pages of both."""
    import jax.numpy as jnp

    from modal_examples_tpu.serving import LLMEngine, SamplingParams

    cfg, params = model
    eng = LLMEngine(
        cfg, params, max_slots=3, page_size=PAGE, max_model_len=128, n_pages=1 + 20,
        n_window_pages=1 + RING + 2, prefill_buckets=(16, 32), prefill_batch=2,
        kv_dtype=jnp.float32, enable_prefix_cache=False, decode_block=4, seed=0,
    )
    try:
        window, whole = eng.cache.window, eng.cache.allocator
        long = eng.make_request("a request whose context passes the window by far", SamplingParams(max_tokens=60))
        small = eng.make_request("short", SamplingParams(max_tokens=4))
        first = eng._claim_pages(long)
        assert len(first["window_pages"]) == RING and len(first["pages"]) == eng.cache.pages_for(
            len(long.prompt_tokens) + 60
        )
        free = whole.available
        assert eng._claim_pages(long) is None  # the window group is short: 2 of 5 left
        assert whole.available == free and window.allocator.available == 2
        second = eng._claim_pages(small)  # a short one still fits: 2 pages of each group
        assert len(second["window_pages"]) == 2 == len(second["pages"])
        window.free(second["window_pages"])
        whole.alloc(whole.available)  # now the whole-context group is short
        assert eng._claim_pages(small) is None and window.allocator.available == 2
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="declares no window group"):
        from modal_examples_tpu.models import llama

        LLMEngine(llama.LlamaConfig.tiny(), max_slots=2, max_model_len=32, n_window_pages=9)


# -- (f) the flash kernel's window -----------------------------------------------------------------


def _dense(q, k, v, q_offset, window, k_first):
    import jax
    import jax.numpy as jnp

    G = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, G, 1), jnp.repeat(v, G, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * q.shape[-1] ** -0.5
    t = q_offset + jnp.arange(q.shape[2])[:, None]
    pos = jnp.arange(k.shape[2])[None, :]
    seen = (pos <= t) & (pos >= k_first)
    if window:
        seen &= t - pos < window
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), vv)


@pytest.mark.parametrize("S,Skv,q_offset,window,k_first,block_q,block_k", [
    (64, 64, 0, 24, 0, 16, 16),  # a bucket call: the grid's k axis is 3 blocks, not 4
    (64, 192, 128, 40, 0, 16, 16),  # a chunk over a prefix: starts late, stops at the diagonal
    (64, 192, 128, 40, 70, 32, 16),  # ... whose first 70 rows are no one's
    (64, 192, 128, None, 70, 16, 32),  # k_first alone (a global layer over a long bucket)
    (128, 384, 256, 100, 3, 32, 64),
    (64, 128, 64, 1, 0, 16, 16),  # a window of one: a query sees itself
])
def test_flash_with_a_window_is_the_masked_dense_softmax(jax, S, Skv, q_offset, window, k_first,
                                                         block_q, block_k):
    """Interpret mode, a group of 3 query heads a K/V head, float32."""
    import jax.numpy as jnp

    from modal_examples_tpu.ops.flash_attention import _flash_forward

    keys = jax.random.split(jax.random.PRNGKey(S + Skv), 3)
    q = jax.random.normal(keys[0], (2, 6, S, 32))
    k, v = (jax.random.normal(key, (2, 2, Skv, 32)) for key in keys[1:])
    got, _ = _flash_forward(
        q, k, v, causal=True, sm_scale=32**-0.5, interpret=True, block_q=block_q,
        block_k=block_k, q_offset=q_offset, window=window,
        k_first=jnp.int32(k_first) if k_first else None,
    )
    np.testing.assert_allclose(got, _dense(q, k, v, q_offset, window, k_first), atol=2e-6)


def test_a_windows_k_grid_starts_at_the_window_and_a_call_without_one_is_the_parents(jax):
    """The grid of a 2048-row chunk at key offset 4096 under a window of 4096
    (the benchmark's third chunk call) has 5 key blocks of 1024 a query tile,
    not 6; and the lowered text of a call with neither ``window`` nor
    ``k_first`` is, by hash, what the parent commit lowers
    (``tests/lowered_text.py`` holds the families' whole programs to the
    same)."""
    import hashlib

    import jax.numpy as jnp

    from modal_examples_tpu.ops.flash_attention import flash_attention_chunked

    def grid(**kw):
        shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
        text = jax.jit(
            lambda q, k, v: flash_attention_chunked(q, k, v, q_offset=4096, **kw)
        ).lower(shape(1, 4, 2048, 128), shape(1, 4, 6144, 128), shape(1, 4, 6144, 128)).as_text()
        return text

    plain, windowed = grid(), grid(window=4096)
    assert plain != windowed
    small = jax.jit(lambda q, k, v: flash_attention_chunked(q, k, v, q_offset=64)).lower(
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in ((1, 4, 64, 32), (1, 2, 128, 32), (1, 2, 128, 32)))
    ).as_text()
    assert hashlib.sha256(small.encode()).hexdigest()[:16] == PARENT_FLASH_CALL


#: sha256 (16 hex) of the lowered text of the call above at the parent commit (4f653c1, PR 40)
PARENT_FLASH_CALL = "4a2ef9e55bf60598"


# -- the decode step's loop over a ring -------------------------------------------------------


def test_the_loop_over_a_ring_is_a_masked_softmax_over_the_window(jax):
    """``paged_window_decode_attention_chunked`` (a group of 7 query heads a
    K/V head, the published one) against a dense softmax over the positions
    the window holds, read straight out of the ring: slots inside their
    first turn, past it, at a page's edge, and a dead one. Its gathers run
    under ``mtpu.window_attention`` with its scores, none under
    ``mtpu.page_gather`` (the window layers' roofline share holds the ring's
    bytes against the scope's whole time)."""
    import jax.numpy as jnp

    from modal_examples_tpu import ops

    B, Hq, Hkv, D, ps, window = 5, 14, 2, 128, 16, 64
    ring = ops.window_ring_pages(window, ps)
    assert ring == 5
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    pages = [jax.random.normal(k, (2, 1 + B * ring, ps, Hkv, D), jnp.float32) for k in keys[:2]]
    tables = 1 + jnp.arange(B * ring, dtype=jnp.int32).reshape(B, ring)
    positions = jnp.asarray([7, 64, 95, 203, 0], jnp.int32)  # the last slot is dead
    q = jax.random.normal(keys[2], (B, Hq, D), jnp.float32)
    k_new, v_new = (jax.random.normal(k, (B, Hkv, D), jnp.float32) for k in keys[3:])
    args = (q, *pages, jnp.int32(1), tables, positions, k_new, v_new)
    got = ops.paged_window_decode_attention_chunked(*args, window=window)
    for b, t in enumerate(np.asarray(positions)):
        seen = np.arange(max(t - window + 1, 0), t)  # cached positions the token at t sees
        at = (np.asarray(tables)[b, (seen // ps) % ring], seen % ps)
        k = jnp.concatenate([pages[0][1][at], k_new[b][None]])  # [n + 1, Hkv, D]
        v = jnp.concatenate([pages[1][1][at], v_new[b][None]])
        s = jnp.einsum("hgd,nhd->hgn", q[b].reshape(Hkv, Hq // Hkv, D), k) * D**-0.5
        want = jnp.einsum("hgn,nhd->hgd", jax.nn.softmax(s, axis=-1), v).reshape(Hq, D)
        np.testing.assert_allclose(got[b], want, atol=2e-5)  # float32 sums in another order
    # the view the loop walks: column 0 is the oldest page the window reaches
    view, lens, starts = ops.window_decode_view(np.asarray(tables), np.asarray(positions), window, ps)
    assert list(lens) == [7, 64, 79, 75, 0] and list(starts) == [0, 1, 16, 12, 0]
    assert list(view[3]) == [tables[3, (8 + j) % ring] for j in range(ring)]  # 203 // 16 - 4 = 8
    text = jax.jit(
        lambda *a: ops.paged_window_decode_attention_chunked(*a, window=window)
    ).lower(*args).as_text(debug_info=True)
    assert "mtpu.window_attention" in text and "mtpu.page_gather" not in text


def test_the_plan_chooses_from_backend_and_shapes_and_refuses_another_scatter(jax, L, model):
    """Unset, the plan picks: on a TPU at the published 4 K/V heads of 128,
    pages of 16 and bf16 pages, the ragged kernel's ``flat`` form in both
    page groups; the chunked loop on the CPU, at the preset's 16-wide heads,
    at pages of 8 and for float32 pages. ``"xla"`` forces the loop,
    ``"pallas"`` the kernel wherever it can run (the interpreter off the
    chip) and is named in ``downgraded`` where the chip's shapes refuse it.
    ``grouped`` is never the variant. A Pallas scatter is refused by name,
    by the plan and where the engine is built."""
    from modal_examples_tpu.serving import LLMEngine

    cfg, params = model
    published = L.SmallThinkerConfig()
    loop, kernel = ("xla-gather", "xla-gather-ring", None), ("ragged", "ragged-ring", "flat")

    def forms(c, page, impl=None, **kw):
        plan = L.paged_impl_plan(c, page, impl, warn=False, **kw)
        return (plan["attention"], plan["window_attention"], plan["ragged_variant"]), plan["downgraded"]

    backend = jax.default_backend
    try:
        jax.default_backend = lambda: "cpu"
        for c, page in ((cfg, PAGE), (published, 16)):
            assert forms(c, page) == forms(c, page, "xla") == (loop, [])
            assert forms(c, page, "pallas") == (kernel, [])  # the interpreter takes any shape
        jax.default_backend = lambda: "tpu"
        assert forms(published, 16) == forms(published, 16, "pallas") == (kernel, [])
        assert forms(published, 16, "xla") == (loop, [])
        eight_heads = L.SmallThinkerConfig(n_heads=32, n_kv_heads=8)
        assert forms(eight_heads, 16) == (kernel, [])
        for c, page, kw in (
            (cfg, 16, {}),  # heads of 16
            (published, 8, {}),  # half a tile of positions a page
            (published, 16, {"kv_dtype": "float32"}),
            (L.SmallThinkerConfig(n_heads=24, n_kv_heads=12), 16, {}),  # the view would be a copy
            (L.SmallThinkerConfig(n_heads=28, n_kv_heads=2), 16, {}),  # not measured
        ):
            assert forms(c, page, **kw) == forms(c, page, "xla", **kw) == (loop, [])
            got, downgraded = forms(c, page, "pallas", **kw)
            assert got == loop and len(downgraded) == 1 and "xla-gather" in downgraded[0]
    finally:
        jax.default_backend = backend
    no_window = L.SmallThinkerConfig.tiny(window_layout=(0, 0), rope_layout=(0, 1))
    assert L.paged_impl_plan(no_window, PAGE)["window_attention"] is None
    assert L.paged_impl_plan(no_window, PAGE, "pallas")["window_attention"] is None
    with pytest.raises(NotImplementedError, match="Pallas scatter_impl"):
        L.paged_impl_plan(cfg, PAGE, scatter_impl="pallas")
    with pytest.raises(NotImplementedError, match="Pallas scatter_impl"):
        LLMEngine(
            cfg, params, max_slots=2, page_size=PAGE, max_model_len=64, prefill_buckets=(32,),
            enable_prefix_cache=False, scatter_impl="pallas",
        )


# -- (g) ReGLU through the routed layer's two tile loops -------------------------------------------


@pytest.mark.parametrize("scan", ["xla", "pallas"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_relu_through_the_sparse_layer_is_the_plain_form(jax, scan, quantized):
    """``moe_swiglu_sparse(activation="relu")`` through XLA's loop and through
    the grouped-matmul kernel (the interpreter here), against ``sum_e w_e
    W_down,e (relu(W_gate,e z) * W_up,e z)`` written out; and SiLU, the
    default, is not it."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe
    from modal_examples_tpu.models.quantize import dequantize_weight, quantize_weight

    T, D, F, E, k = 24, 128, 128, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    gate, up = (jax.random.normal(key, (2, E, D, F)) * D**-0.5 for key in keys[:2])
    down = jax.random.normal(keys[2], (2, E, F, D)) * F**-0.5
    if quantized:
        gate, up, down = (quantize_weight(w, 8) for w in (gate, up, down))
    z = jax.random.normal(keys[3], (T, D))
    weights, ids = moe.route_group_limited(
        jax.nn.softmax(jax.random.normal(keys[4], (T, E)), -1), k, renormalize=True
    )
    with jax.default_matmul_precision("highest"):
        got, _ = moe.moe_swiglu_sparse(
            gate, up, down, z, ids, weights, layer=jnp.int32(1), scan=scan, activation="relu"
        )
        silu, _ = moe.moe_swiglu_sparse(gate, up, down, z, ids, weights, layer=jnp.int32(1), scan=scan)
        g, u, d = ((dequantize_weight(w, jnp.float32) if quantized else w)[1] for w in (gate, up, down))
        want = sum(
            weights[:, j:j + 1] * jnp.einsum(
                "tf,tfd->td", jax.nn.relu(jnp.einsum("td,tdf->tf", z, g[ids[:, j]]))
                * jnp.einsum("td,tdf->tf", z, u[ids[:, j]]), d[ids[:, j]],
            )
            for j in range(k)
        )
    np.testing.assert_allclose(got, want, atol=2e-5)  # float32 sums in another order
    assert float(jnp.abs(silu - want).max()) > 0.05


# -- the engine, end to end ---------------------------------------------------------------------------


def _metric(name, **labels):
    from modal_examples_tpu.utils.prometheus import default_registry

    return default_registry.value(name, labels) or 0.0


def test_the_engine_serves_the_references_first_choice_past_the_window_and_reuses_slots(jax, ref, model):
    """Through ``LLMEngine``: a bucketed prompt and a chunked one (five chunk
    calls across the window's edge, the prefix buckets longer than some
    offsets), five requests over two slots so that both are taken again,
    answers of 70 tokens (two windows) in one decode batch with requests at
    other positions. Greedy tokens are the reference's first choice wherever
    it is decided. The plan and ``mtpu_decode_impl`` name the forms that
    served; the window group's gauges, its recycled pages and the decode
    steps' reads by layer kind reach the registry; when all is done both
    budgets are whole again."""
    import jax.numpy as jnp

    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving import LLMEngine, SamplingParams

    cfg, params = model
    texts = [
        "hello window",
        "a prompt long enough to need five chunk calls across the window's edge, with words to spare for it",
        "second tenant", "a third, somewhat longer, tenant of a slot that was used", "the last one",
    ]
    recycled0 = _metric(C.KV_WINDOW_PAGES_RECYCLED_TOTAL)
    read0 = {
        (kind, layers): _metric(C.DECODE_KV_POSITIONS_TOTAL, kind=kind, layers=layers)
        for kind in ("read", "live", "table") for layers in ("global", "window")
    }
    unlabelled0 = _metric(C.DECODE_KV_POSITIONS_TOTAL, kind="read")  # the other models' series
    eng = LLMEngine(
        cfg, params, max_slots=2, page_size=PAGE, max_model_len=192, prefill_buckets=(16, 32),
        prefill_batch=2, kv_dtype=jnp.float32, enable_prefix_cache=False, decode_block=4, seed=0,
    )
    assert eng.impl_plan["attention"] == "xla-gather"
    assert eng.impl_plan["window_attention"] == "xla-gather-ring"
    assert eng.cache.window.ring == RING and eng.cache.window.allocator.n_pages == 1 + 2 * RING
    eng.start()
    try:
        reqs = [eng.submit(t, SamplingParams(max_tokens=70, temperature=0.0)) for t in texts]
        for req in reqs:
            "".join(eng.stream(req))
        peak = eng.cache.occupancy()["window"]
    finally:
        eng.stop()
    assert peak == {"pages_used": 0, "pages_free": 2 * RING, "pages_total": 2 * RING,
                    "pages_peak": 2 * RING, "ring": RING}
    assert eng.cache.occupancy()["pages_used"] == 0
    assert len(reqs[1].prompt_tokens) > 2 * WINDOW
    for req in reqs:
        prompt, served = list(req.prompt_tokens), list(req.generated_tokens)
        assert len(served) == 70
        logits = _ref_logits(jax, ref, params, prompt + served[:-1], cfg)[len(prompt) - 1:]
        top2 = np.sort(logits, axis=-1)[:, -2:]
        decided = top2[:, 1] - top2[:, 0] > 10 * F32_ATOL
        assert decided.sum() >= 60
        assert [int(t) for t in logits.argmax(-1)[decided]] == [t for t, d in zip(served, decided) if d]
    assert _metric(C.KV_WINDOW_PAGES_PEAK) == 2 * RING and _metric(C.KV_WINDOW_PAGES_TOTAL) == 2 * RING
    assert _metric(C.KV_WINDOW_PAGES_USED) == 0
    assert _metric(C.KV_WINDOW_PAGES_RECYCLED_TOTAL) - recycled0 >= 5 * (70 // PAGE - RING)
    moved = {key: _metric(C.DECODE_KV_POSITIONS_TOTAL, kind=key[0], layers=key[1]) - was
             for key, was in read0.items()}
    assert all(v > 0 for v in moved.values())
    # a window layer's sequence holds at most a window; the loop reads whole trips of the ring
    assert moved["live", "window"] < moved["live", "global"]
    assert moved["table", "window"] == moved["table", "global"] * RING / (192 // PAGE)
    assert _metric(C.DECODE_KV_POSITIONS_TOTAL, kind="read") == unlabelled0  # none without the label


def test_the_engine_serves_the_same_tokens_through_the_kernel_and_counts_what_it_reads(jax, model):
    """``paged_impl="pallas"`` (what the plan picks unasked on a TPU at the
    published shapes; the interpreter here): the plan and the engine name
    the kernel in both page groups, greedy answers of 44 tokens past the
    window (three requests over two slots, decode blocks of 4) are the
    loop's token for token, and ``mtpu_decode_kv_positions_total{kind=
    "read"}`` counts, for both ``layers``, each live slot's own live pages
    step by step (``ragged_pages_read``): a global layer's from the
    context's first page, a window layer's from the first page its window
    reaches; nothing for a dead slot, nothing rounded up to a neighbour."""
    import jax.numpy as jnp

    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.ops import ragged_pages_read, window_decode_span
    from modal_examples_tpu.serving import LLMEngine, SamplingParams

    cfg, params = model
    texts = ["hello window", "a second, somewhat longer, tenant of a slot: past the window soon", "third"]
    served = {}
    for impl in (None, "pallas"):
        eng = LLMEngine(
            cfg, params, max_slots=2, page_size=PAGE, max_model_len=192, prefill_buckets=(16, 32),
            prefill_batch=2, kv_dtype=jnp.float32, enable_prefix_cache=False, decode_block=4, seed=0,
            paged_impl=impl,
        )
        want = ("ragged", "ragged-ring", "flat") if impl else ("xla-gather", "xla-gather-ring", None)
        assert tuple(eng.impl_plan[k] for k in ("attention", "window_attention", "ragged_variant")) == want
        eng.start()
        try:
            reqs = [eng.submit(t, SamplingParams(max_tokens=44, temperature=0.0)) for t in texts]
            for req in reqs:
                "".join(eng.stream(req))
        finally:
            eng.stop()
        served[impl] = [list(r.generated_tokens) for r in reqs]
    assert all(len(t) == 44 for t in served[None]) and served["pallas"] == served[None]
    # the count, on the engine that served through the kernel: a block of 4
    # steps over a slot in its ring's first turn, one several turns on, a dead one
    eng.max_slots = 3  # only the loop's count reads it
    positions, active, steps = np.array([35, 150, 77], np.int64), np.array([True, True, False]), 4
    before = {
        layers: _metric(C.DECODE_KV_POSITIONS_TOTAL, kind="read", layers=layers)
        for layers in ("global", "window")
    }
    eng._count_decode_kv(positions, active, steps)
    read = {layers: _metric(C.DECODE_KV_POSITIONS_TOTAL, kind="read", layers=layers) - was
            for layers, was in before.items()}
    want = {"global": 0, "window": 0}
    for t in (p + j for p in positions[active] for j in range(steps)):
        oldest = max(t // PAGE - (RING - 1), 0)  # the first page the window can reach
        want["global"] += -(-t // PAGE) * PAGE
        want["window"] += -(-(t - oldest * PAGE) // PAGE) * PAGE
    assert read == want
    at = (positions[active][:, None] + np.arange(steps)).reshape(-1)
    _, lens, _ = window_decode_span(at, WINDOW, PAGE, RING)
    assert want["window"] == int(ragged_pages_read(lens, PAGE).sum()) * PAGE
    assert want["global"] == int(ragged_pages_read(at, PAGE).sum()) * PAGE


# -- the models there were get the programs they had ------------------------------------------------

#: sha256 (16 hex) of the lowered text at the parent commit (4f653c1, PR 40):
#: ``python tests/lowered_text.py`` in a checkout of it, with this PR's
#: ``lowered_text.py`` (which knows Mixtral's tiny routed preset and LFM2).
#: ``tests/test_lfm2.py`` holds the other four families to the same hashes;
#: both ``chunk`` entries are PR 43's, whose chunk program samples
PARENT_PROGRAMS = {
    "llama_moe": {"decode_step": "e4d8c6df425d309c", "block": "f23a182c9d19eb6f",
                  "bucket": "cb1fa07f61616d4f", "chunk": "7f2b0e1c119bed20"},
    "lfm2": {"decode_step": "d82d9545e710e4bc", "block": "bd655d89200f5972",
             "bucket": "c3bef3039f79951c", "chunk": "003c695d4271080c"},
}


@pytest.mark.parametrize("family", ["llama_moe", "lfm2"])
def test_the_lowered_text_of_the_routed_families_programs_is_the_parents(jax, family):
    """The activation argument, the logits from outside, the flash kernel's
    window, the decode loop's ``starts`` and the cache's second group change
    no program of a model that uses none of them: ``decode_step``, the decode
    block, the bucketed prefill call and the chunk call, by hash."""
    import lowered_text

    assert lowered_text.hashes(family) == PARENT_PROGRAMS[family]
