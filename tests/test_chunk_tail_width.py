"""The llama path's chunked prefill: the tail call takes its width from the
ordinary buckets (tests/chunk_tail.py holds the cases every model's file
runs): under a budget of one chunk a tick, with a draft model whose chunk
takes the same width, and under a two-way tensor-parallel mesh."""

import chunk_tail
import pytest


def _engine(jax, kind):
    import jax.numpy as jnp

    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    kw = dict(
        max_slots=2, max_model_len=chunk_tail.MAX_MODEL_LEN, page_size=16,
        prefill_buckets=chunk_tail.BUCKETS, seed=0,
    )
    if kind == "budgeted":  # one chunk a tick, decode blocks between
        return LLMEngine(llama.LlamaConfig.tiny(), max_prefill_tokens_per_tick=1, **kw)
    if kind == "draft":  # the draft model's chunk beside the target's
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return LLMEngine(cfg, params, speculative=(cfg, 4), draft_params=params, **kw)
    from modal_examples_tpu.parallel import make_mesh

    cfg = llama.LlamaConfig(  # heads that two shards divide
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
        max_seq_len=512, dtype="float32",
    )
    mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
    return LLMEngine(cfg, mesh=mesh, kv_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module", params=["budgeted", "draft", "tp2"])
def engine(request, jax_cpu):
    eng = _engine(jax_cpu, request.param)
    yield chunk_tail.warmed(eng)
    eng.stop()


@pytest.mark.parametrize("case", list(chunk_tail.CASES))
def test_the_tail_chunk_is_as_wide_as_what_is_left(engine, case, monkeypatch):
    chunk_tail.check(engine, case, monkeypatch)
    if engine.spec_mode == "draft":  # its programs built with the target's, and taken
        assert sum(draft for _off, _w, draft in engine._chunk_programs) == len(
            engine._chunk_programs
        ) // 2


def test_the_widths_an_offset_can_take(engine):
    """Offset 0 only ever sees a whole chunk; a later offset every bucket a
    prompt of at most ``max_model_len - 1`` tokens can end in."""
    C = chunk_tail.C
    assert engine._chunk_widths(0) == [C]
    assert engine._chunk_widths(C) == [16, 32, C]
    assert engine._chunk_widths(3 * C) == [16, 32, C]  # 63 tokens can be left
    engine.max_model_len = 3 * C + 17
    try:
        assert engine._chunk_widths(3 * C) == [16]
        assert engine._chunk_widths(2 * C) == [16, 32, C]
    finally:
        engine.max_model_len = chunk_tail.MAX_MODEL_LEN


def test_until_a_tail_program_is_built_the_next_wider_one_serves(jax_cpu, monkeypatch):
    """Only the width a chunk is about to take is built on the scheduler's
    thread; the offset's other widths wait for an idle tick and are built by
    helper threads. Until then a narrower tail takes the narrowest program
    that is built, and nobody waits."""
    from modal_examples_tpu.observability import catalog

    def builds(how):
        return chunk_tail._value(catalog.COMPILES_TOTAL, program="prefill_chunk", cache=how)

    C = chunk_tail.C
    eng = _engine(jax_cpu, "budgeted")
    try:
        monkeypatch.setattr(eng, "_start_chunk_builds", lambda: None)  # never idle
        missed, ahead = builds("miss"), builds("ahead")
        with chunk_tail.dispatched(eng) as seen:
            chunk_tail.serve(eng, 2 * C - 1, seed=1)  # the first chunked request: tail C
        assert chunk_tail._chunk_keys(seen) == ["off0w64", "off64w64"]
        assert set(eng._chunk_queued) == {(C, 16, False), (C, 32, False)}
        with chunk_tail.dispatched(eng) as seen:
            chunk_tail.serve(eng, C + 1, seed=2)  # wants 16 rows, takes the 64 there are
        assert chunk_tail._chunk_keys(seen) == ["off0w64", "off64w64"]
        # a dispatch waited for each of the two: misses; nothing built ahead yet
        assert (builds("miss") - missed, builds("ahead") - ahead) == (2, 0)
        monkeypatch.undo()
        chunk_tail.settle(eng)
        assert (builds("miss") - missed, builds("ahead") - ahead) == (2, 2)
        with chunk_tail.dispatched(eng) as seen:
            chunk_tail.serve(eng, C + 1, seed=2)
        assert chunk_tail._chunk_keys(seen) == ["off0w64", "off64w16"]
    finally:
        eng.stop()


def test_a_helpers_failed_build_leaves_the_wider_program_serving(jax_cpu, monkeypatch):
    """A build that raises on a helper thread poisons nothing: the width is
    unbuilt again, its chunks keep the next wider program, and the engine's
    other widths are built as ever."""
    C = chunk_tail.C
    eng = _engine(jax_cpu, "budgeted")
    try:
        monkeypatch.setattr(eng, "_start_chunk_builds", lambda: None)
        chunk_tail.serve(eng, 2 * C - 1, seed=1)
        monkeypatch.undo()

        def fails():
            raise RuntimeError("no room to compile")

        eng._chunk_queued[C, 16, False] = fails
        eng._start_chunk_builds()
        eng._chunk_programs[C, 32, False].result(timeout=300.0)  # the one that builds
        with pytest.raises(RuntimeError):
            eng._chunk_programs[C, 16, False].result()
        with chunk_tail.dispatched(eng) as seen:
            chunk_tail.serve(eng, C + 1, seed=2)  # wants 16 rows, takes 32
        assert chunk_tail._chunk_keys(seen) == ["off0w64", "off64w32"]
        assert (C, 16, False) not in eng._chunk_programs
        assert not eng.error_log
    finally:
        eng.stop()


def test_a_tick_never_runs_beside_a_helpers_lowering(jax_cpu):
    """One thread traces at a time (``LLMEngine._chunk_lowering``): while a
    helper lowers a chunk program a tick waits for it, a helper waits for a
    tick, and the tick's own build takes the lock again without blocking."""
    import threading

    C = chunk_tail.C
    eng = _engine(jax_cpu, "budgeted")
    try:
        held, release, ticked = threading.Event(), threading.Event(), threading.Event()

        def helper():
            with eng._chunk_lowering:
                held.set()
                release.wait(30.0)

        def tick():
            eng.step()
            ticked.set()

        threads = [threading.Thread(target=helper), threading.Thread(target=tick)]
        threads[0].start()
        assert held.wait(30.0)
        threads[1].start()
        assert not ticked.wait(0.3)  # the tick waits for the helper's lowering
        release.set()
        assert ticked.wait(30.0)
        for thread in threads:
            thread.join(30.0)
        # a chunked request builds its programs inside ticks, under the lock they hold
        with chunk_tail.dispatched(eng) as seen:
            chunk_tail.serve(eng, C + 1, seed=3)
        assert chunk_tail._chunk_keys(seen)[0] == "off0w64" and not eng.error_log
    finally:
        eng.stop()
