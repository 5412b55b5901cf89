"""The decode step's chunk loop (ops.paged_decode_attention_chunked) as the
engine runs it: the trip count follows the positions *inside* a decode block,
what the host counts is what the device loops, and a sequence whose context
crosses a chunk edge decodes as over the whole table."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


PS, MAX_LEN, BLOCK = 16, 2048, 8


@pytest.fixture(scope="module")
def span():
    from modal_examples_tpu.ops import decode_chunk_pages

    pp = MAX_LEN // PS
    span = decode_chunk_pages(PS, pp) * PS
    assert MAX_LEN >= 3 * span, "the table must hold several chunks"
    return span


def _cfg():
    import dataclasses

    from modal_examples_tpu.models import llama

    return dataclasses.replace(llama.LlamaConfig.tiny(), max_seq_len=MAX_LEN)


def _engine(jax, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    return LLMEngine(
        _cfg(), seed=0, max_slots=3, max_model_len=MAX_LEN,
        page_size=PS, prefill_buckets=(64, 256), decode_block=BLOCK, **kw,
    )


def _counter(kind):
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.utils.prometheus import default_registry

    return default_registry.value(C.DECODE_KV_POSITIONS_TOTAL, {"kind": kind})


class TestBlockCrossesAChunkEdge:
    """Cross-path: the block's steps with the loop, against the same steps
    with one chunk as wide as the table (one trip over everything, the
    mathematics of before the loop). The two differ by the rounding of the
    running softmax, so logits within the cross-path tolerance and the same
    greedy token wherever the margin exceeds it, not bitwise identity."""

    def _logits(self, jax, span, chunk_positions, monkeypatch):
        """K steps of greedy decode in one program (the engine's block body:
        decode_step in a scan, pos + 1 a step), from a context that ends 3
        tokens short of a chunk edge, and a short neighbour."""
        import functools

        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.ops import paged_attention

        if chunk_positions is not None:
            with monkeypatch.context() as m:
                m.setattr(paged_attention, "_CHUNK_POSITIONS", chunk_positions)
                return self._logits(jax, span, None, monkeypatch)
        cfg = _cfg()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        B, pp = 2, MAX_LEN // PS
        lens = np.array([span - 3, 9], np.int32)
        toks = np.zeros((B, span), np.int32)
        rng = np.random.default_rng(1)
        for b, n in enumerate(lens):
            toks[b, :n] = rng.integers(3, cfg.vocab_size, n)
        shape = (cfg.n_layers, 1 + B * pp, PS, cfg.n_kv_heads, cfg.head_dim)
        kp = jnp.zeros(shape, cfg.jnp_dtype)
        vp = jnp.zeros(shape, cfg.jnp_dtype)
        tables = jnp.asarray(1 + np.arange(B * pp).reshape(B, pp), jnp.int32)
        lo, kp, vp = jax.jit(functools.partial(llama.prefill, cfg=cfg))(
            params, jnp.asarray(toks), kp, vp, tables, jnp.asarray(lens)
        )
        active = jnp.ones((B,), bool)

        def body(carry, _):
            tok, pos, kp, vp = carry
            logits, kp, vp = llama.decode_step(
                params, tok, pos, kp, vp, tables, active, cfg
            )
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, pos + 1, kp, vp), logits

        first = jnp.argmax(lo, -1).astype(jnp.int32)
        # a new function per call: jit's cache is keyed on it, and the
        # chunk size is read at trace time
        _, logits = jax.jit(
            lambda kp, vp: jax.lax.scan(
                body, (first, jnp.asarray(lens), kp, vp), None, length=BLOCK
            )
        )(kp, vp)
        return np.asarray(logits, np.float32)  # [K, B, vocab]

    def test_logits_and_greedy_tokens_of_a_full_width_run(
        self, jax, span, monkeypatch
    ):
        chunked = self._logits(jax, span, None, monkeypatch)
        full = self._logits(jax, span, 1 << 30, monkeypatch)
        # the long context crosses the edge at the block's 4th step
        assert np.max(np.abs(chunked - full)) < 2e-2, np.max(np.abs(chunked - full))
        top2 = np.sort(full, axis=-1)[..., -2:]
        decided = (top2[..., 1] - top2[..., 0]) > 4e-2
        assert decided.mean() > 0.5
        same = chunked.argmax(-1) == full.argmax(-1)
        assert same[decided].all()

    def test_a_trip_count_taken_once_a_block_would_show(
        self, jax, span, monkeypatch
    ):
        """The teeth of the test above: freeze the trips at the block's
        first step and the steps behind the edge lose their newest tokens."""
        import jax.numpy as jnp

        from modal_examples_tpu.ops import paged_attention

        full = self._logits(jax, span, 1 << 30, monkeypatch)
        real = paged_attention.decode_chunk_trips
        monkeypatch.setattr(
            paged_attention, "decode_chunk_trips",
            lambda longest, ps, pp: jnp.minimum(real(longest, ps, pp), 1),
        )
        frozen = self._logits(jax, span, None, monkeypatch)
        behind = np.abs(frozen[4:, 0] - full[4:, 0]).max()
        before = np.abs(frozen[:3, 0] - full[:3, 0]).max()
        assert before < 2e-2 < behind, (before, behind)


class TestEngineCountsWhatTheDeviceLoops:
    def test_read_positions_equal_the_devices_trips(self, jax, span):
        """A request whose context crosses a chunk edge in the middle of a
        block, beside a short one: the host's
        ``mtpu_decode_kv_positions_total{kind="read"}`` is the trips a
        test-only jit counts from the positions each dispatch was handed,
        and ``table`` / ``live`` are what the shapes and positions say."""
        import jax.numpy as jnp

        from modal_examples_tpu.ops import decode_chunk_pages, decode_chunk_trips
        from modal_examples_tpu.serving import SamplingParams

        eng = _engine(jax)
        pp = eng.pages_per_slot
        seen = []
        block = eng._block_jit

        def spy(*args):
            seen.append((np.asarray(args[6]), np.asarray(args[8])))
            return block(*args)

        eng._block_jit = spy

        @jax.jit
        def device_trips(positions, active):
            def step(pos, _):
                longest = jnp.max(jnp.where(active, pos, 0))
                return pos + 1, (
                    decode_chunk_trips(longest, PS, pp),
                    jnp.sum(jnp.where(active, pos, 0)),
                )
            return jax.lax.scan(step, positions, None, length=BLOCK)[1]

        before = {k: _counter(k) for k in ("read", "live", "table")}
        try:
            params = SamplingParams(max_tokens=2 * BLOCK, temperature=0.0)
            long = eng.submit("a" * (span - BLOCK // 2 - 2), params)
            short = eng.submit("hello", params)
            for r in (long, short):
                "".join(eng.stream(r))
        finally:
            eng.stop()
        assert len(long.generated_tokens) == 2 * BLOCK and seen
        trips = live = 0
        crossed_inside_a_block = False
        for positions, active in seen:
            t, l = device_trips(jnp.asarray(positions), jnp.asarray(active))
            trips += int(t.sum())
            live += int(l.sum())
            crossed_inside_a_block |= {1, 2} <= set(np.asarray(t).tolist())
        assert crossed_inside_a_block
        chunk = decode_chunk_pages(PS, pp) * PS
        delta = {k: _counter(k) - before[k] for k in before}
        assert delta["read"] == trips * chunk * eng.max_slots
        assert delta["live"] == live
        assert delta["table"] == len(seen) * BLOCK * eng.max_slots * pp * PS
        assert 0 < delta["live"] < delta["read"] < delta["table"]

    def test_under_the_ragged_plan_read_positions_are_the_kernels_pages(self, jax, monkeypatch):
        """The same two requests with the ragged kernel serving
        (``paged_impl="pallas"``: on the CPU the plan picks it only when
        asked): ``read`` is, step by step, the pages the kernel's own loop
        bounds name for each live slot (``ragged_pages_read``: what it DMAs),
        nothing for a dead slot, across an edge of the kernel's chunk inside
        a block; it stays under the loop's count for the same steps."""
        import jax.numpy as jnp

        from modal_examples_tpu.ops import (
            decode_chunk_pages, decode_chunk_trips, paged_attention, ragged_kernel_sizes,
            ragged_pages_read,
        )
        from modal_examples_tpu.serving import SamplingParams

        eng = _engine(jax, paged_impl="pallas")
        assert eng.impl_plan["attention"] == "ragged"
        pp, cfg = eng.pages_per_slot, eng.cfg
        # a ring of 8-page halves and 4-page updates, so that the table
        # holds several chunks (the sizes picked for this toy cover it whole)
        itemsize = eng.cache.k_pages.dtype.itemsize
        monkeypatch.setattr(paged_attention, "_FLAT_UPDATE_COLUMNS", 4 * PS * cfg.n_kv_heads)
        monkeypatch.setattr(paged_attention, "_GROUPED_UPDATE_POSITIONS", 4 * PS)
        monkeypatch.setattr(
            paged_attention, "_RING_BYTES", 4 * 8 * PS * cfg.n_kv_heads * cfg.head_dim * itemsize
        )
        chunk, update = ragged_kernel_sizes(
            eng.impl_plan["ragged_variant"], PS, cfg.n_kv_heads, cfg.head_dim, itemsize, pp
        )
        assert (chunk, update) == (8, 4) and 3 * chunk <= pp
        seen = []
        block = eng._block_jit

        def spy(*args):
            seen.append((np.asarray(args[6]), np.asarray(args[8])))
            return block(*args)

        eng._block_jit = spy

        @jax.jit
        def device_pages(positions, active):
            def step(pos, _):  # the kernel's bounds, from the step's prefix_lens
                prefix = jnp.where(active, pos, 0)
                pages = ragged_pages_read(prefix, PS)
                return pos + 1, (pages.sum(), jnp.max(-(-pages // chunk)), jnp.max(prefix))
            return jax.lax.scan(step, positions, None, length=BLOCK)[1]

        before = {k: _counter(k) for k in ("read", "live", "table")}
        try:
            params = SamplingParams(max_tokens=2 * BLOCK, temperature=0.0)
            long = eng.submit("a" * (chunk * PS - BLOCK // 2 - 2), params)
            short = eng.submit("hello", params)
            for r in (long, short):
                "".join(eng.stream(r))
        finally:
            eng.stop()
        assert len(long.generated_tokens) == 2 * BLOCK and seen
        pages = loop = 0
        crossed_inside_a_block = False
        for positions, active in seen:
            n, chunks, longest = device_pages(jnp.asarray(positions), jnp.asarray(active))
            pages += int(n.sum())
            loop += int(decode_chunk_trips(np.asarray(longest), PS, pp).sum())
            crossed_inside_a_block |= {1, 2} <= set(np.asarray(chunks).tolist())
        assert crossed_inside_a_block
        delta = {k: _counter(k) - before[k] for k in before}
        assert delta["read"] == pages * PS
        assert delta["table"] == len(seen) * BLOCK * eng.max_slots * pp * PS
        assert 0 < delta["live"] <= delta["read"] < delta["live"] + 2 * PS * len(seen) * BLOCK
        assert delta["read"] < loop * decode_chunk_pages(PS, pp) * PS * eng.max_slots

    def test_greedy_tokens_across_the_edge_match_a_whole_forward(self, jax, span):
        """End to end through the engine: the tokens a request decodes on
        both sides of a chunk edge are the argmax of a teacher-forced full
        forward over prompt + answer, wherever that argmax is decided by
        more than the cross-path tolerance."""
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import SamplingParams

        eng = _engine(jax)
        try:
            # a prompt whose greedy continuation is decided (every margin
            # over 0.25 on this seeded tiny model). One repeated letter ran
            # into near-ties (margins 0.001-0.007 by the fourth token), and
            # which side of a tie the bf16 prefill falls decided whether
            # half the answer cleared the tolerance at all
            req = eng.submit(
                ("ab" * span)[: span - BLOCK - 3],
                SamplingParams(max_tokens=2 * BLOCK, temperature=0.0),
            )
            "".join(eng.stream(req))
            prompt, answer = list(req.prompt_tokens), list(req.generated_tokens)
            cfg, params = eng.cfg, eng.params
        finally:
            eng.stop()
        assert len(prompt) < span < len(prompt) + len(answer)
        seq = jnp.asarray([prompt + answer], jnp.int32)
        logits = np.asarray(
            llama.forward(params, seq, cfg, attn_impl="xla")[0], np.float32
        )
        rows = logits[len(prompt) - 1 : len(prompt) - 1 + len(answer)]
        top2 = np.sort(rows, axis=-1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > 4e-2
        assert decided.sum() >= len(answer) // 2
        assert (rows.argmax(-1) == np.asarray(answer))[decided].all()
