"""ISSUE 19 acceptance: macro-step decode runtime (docs/multistep.md).

The exactness contract, pinned as a matrix: an engine running N decode
steps per dispatch (``decode_steps`` / ``MTPU_DECODE_STEPS``) is
**token-identical** to the classic one-block-per-dispatch path on the
same replica — greedy AND seeded, bf16 AND int8 KV, N in {1, 4, 8},
including runtime knob flips on a live engine. The harvest boundary is
a first-class failover point: a checkpoint whose resume position lands
*inside* a macro-step (k not a multiple of N) resumes token-identically
on a peer running a *different* N; live migration mid-macro-step ships
only harvested tokens (the detok worker is flushed on the victim's
scheduler thread first) and continues byte-identically. Abort and
deadline landing between harvest boundaries terminate honestly with
nothing leaked, and stop-string truncation through the off-thread
detokenization worker matches the classic in-line path byte for byte.
"""

import threading
import time

import pytest


PROMPT = "the quick brown fox jumps over the lazy dog and naps in the sun"


def _mk_engine(kv_dtype="bfloat16", params=None, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (16, 32))
    return LLMEngine(
        llama.LlamaConfig.tiny(), seed=0, params=params,
        kv_dtype=kv_dtype, **kw,
    )


def _drained(eng) -> list:
    from modal_examples_tpu.faults.chaos import check_drained

    return check_drained({"eng": eng})


def _wait_tokens(req, n, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(req.generated_tokens) >= n:
            return True
        time.sleep(0.005)
    return False


def _wait_drained(eng, timeout=30.0) -> list:
    """Abort/deadline reaping is asynchronous (the finish marker is
    delivered immediately; the slot is reaped at the next decode tick) —
    poll until the engine drains instead of asserting instantaneously."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _drained(eng) == []:
            return []
        time.sleep(0.02)
    return _drained(eng)


class TestTokenIdentityMatrix:
    """classic (N=1) vs macro-step (N in {4, 8}) on the same replica:
    greedy + seeded, bf16 + int8 KV — byte-identical text, identical
    token ids, identical finish reason. N mutates on a LIVE engine
    between runs (the knob is read once per dispatch, like
    prefill_budget), so this also pins the byte-identical fall-through
    back to the classic path at N=1."""

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_classic_vs_multistep_matrix(self, jax_cpu, kv_dtype):
        from modal_examples_tpu.serving import SamplingParams

        sps = {
            "greedy": SamplingParams(max_tokens=16, temperature=0.0),
            "seeded": SamplingParams(max_tokens=16, temperature=0.9, seed=7),
        }
        ref_eng = _mk_engine(kv_dtype)  # classic: decode_steps unset -> 1
        ms_eng = _mk_engine(kv_dtype, params=ref_eng.params, decode_steps=8)
        try:
            refs = {}
            for name, sp in sps.items():
                r = ref_eng.submit(PROMPT, sp)
                refs[name] = (
                    "".join(ref_eng.stream(r)),
                    list(r.generated_tokens),
                    r.finish_reason,
                )
            for n in (8, 4, 1):
                ms_eng.decode_steps = n
                for name, sp in sps.items():
                    req = ms_eng.submit(PROMPT, sp)
                    out = "".join(ms_eng.stream(req))
                    ref_text, ref_tokens, ref_fin = refs[name]
                    assert req.generated_tokens == ref_tokens, (
                        kv_dtype, name, n,
                    )
                    assert out == ref_text, (kv_dtype, name, n)
                    assert req.finish_reason == ref_fin, (kv_dtype, name, n)
            assert _drained(ref_eng) == [] and _drained(ms_eng) == []
        finally:
            ref_eng.stop()
            ms_eng.stop()


class TestCheckpointMidMacroStep:
    """checkpoint -> resume on a PEER running a different N: resume
    positions deliberately chosen NOT to align with either engine's
    harvest boundary (k not a multiple of 4 or 8) — the continuation is
    still byte-identical, because checkpoints only ever contain
    harvested tokens and sampling is (seed, position)-keyed."""

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    @pytest.mark.parametrize("sampling", ["greedy", "seeded"])
    def test_resume_matrix(self, jax_cpu, kv_dtype, sampling):
        from modal_examples_tpu.serving import SamplingParams

        sp = (
            SamplingParams(max_tokens=12, temperature=0.0)
            if sampling == "greedy"
            else SamplingParams(max_tokens=12, temperature=0.9, seed=7)
        )
        eng_a = _mk_engine(kv_dtype, decode_steps=4)  # victim
        eng_b = _mk_engine(  # peer on a DIFFERENT macro-step width
            kv_dtype, params=eng_a.params, decode_steps=8,
        )
        try:
            ref = eng_a.submit(PROMPT, sp)
            ref_text = "".join(eng_a.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            assert ref.n_generated == 12
            # k=1/3/6/11: inside a 4-step macro on the victim, inside an
            # 8-step macro on the peer, and the last-token edge
            for k in (1, 3, 6, 11):
                req = eng_b.make_request(PROMPT, sp)
                req.auto_seed = ref.auto_seed  # rides the checkpoint
                eng_b.submit_resumed(
                    req,
                    prompt_tokens=ref.prompt_tokens,
                    generated=ref_tokens[:k],
                    emitted_len=0,
                )
                out = "".join(eng_b.stream(req))
                assert req.generated_tokens == ref_tokens, (
                    sampling, kv_dtype, k,
                )
                assert out == ref_text, (sampling, kv_dtype, k)
                assert req.finish_reason == ref.finish_reason
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()


class TestLiveMigrationMidMacroStep:
    """Live KV migration extracted between macro-steps: the victim's
    scheduler flushes the detok worker before checkpointing, so the
    shipped state holds only harvested tokens — the stream continues on
    the target (running a different N) byte-identically."""

    def _fleet(self, **eng_kw):
        from modal_examples_tpu.scheduling import EngineReplica

        steps_a = eng_kw.pop("steps_a", 4)
        steps_b = eng_kw.pop("steps_b", 8)
        eng_a = _mk_engine(decode_steps=steps_a, **eng_kw)
        eng_b = _mk_engine(
            params=eng_a.params, decode_steps=steps_b, **eng_kw,
        )
        rep_a = EngineReplica(eng_a, "ms-mig-a", role="unified")
        rep_b = EngineReplica(eng_b, "ms-mig-b", role="unified")
        return eng_a, eng_b, rep_a, rep_b

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_migrate_mid_macro_step_token_identical(self, jax_cpu, kv_dtype):
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        sp = SamplingParams(max_tokens=48, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet(kv_dtype=kv_dtype)
        try:
            ref = eng_b.submit(PROMPT, sp)  # fault-free reference on B
            ref_text = "".join(eng_b.stream(ref))
            ref_tokens = list(ref.generated_tokens)

            req = rep_a.submit(PROMPT, sp)
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng_a.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 5)
            result = fo.migrate_request(
                rep_a, rep_b, req, chunk_bytes=512
            )
            assert result == "ok"
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.finish_reason == ref.finish_reason
            assert req.generated_tokens == ref_tokens, kv_dtype
            assert "".join(pieces) == ref_text, kv_dtype
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()


class TestAbortDeadlineBetweenHarvests:
    """Failure hygiene at the harvest boundary: an abort or deadline
    that lands while the engine is inside a macro-step discards the
    un-harvested tail at the next harvest — honest finish reason, pages
    freed, nothing stuck in the detok worker."""

    def test_abort_between_harvest_boundaries(self, jax_cpu):
        from modal_examples_tpu.serving import SamplingParams

        eng = _mk_engine(decode_steps=8)
        try:
            req = eng.submit(PROMPT, SamplingParams(
                max_tokens=96, temperature=0.0,
            ))
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng.stream(req))
            )
            t.start()
            # at least one harvest landed; the next macro-step is in
            # flight (or about to be) when the abort arrives
            assert _wait_tokens(req, 4)
            eng.abort(req)
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.finish_reason == "stop"
            assert len(req.generated_tokens) < 96
            assert _wait_drained(eng) == []
        finally:
            eng.stop()

    def test_deadline_between_harvest_boundaries(self, jax_cpu):
        from modal_examples_tpu.serving import SamplingParams

        eng = _mk_engine(decode_steps=8)
        try:
            req = eng.submit(PROMPT, SamplingParams(
                max_tokens=96, temperature=0.0,
            ))
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 4)
            # the deadline lapses mid-macro-step; the sweep reaps it at
            # the next harvest boundary
            req.deadline = eng._clock() - 1.0
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.finish_reason == "deadline"
            assert len(req.generated_tokens) < 96
            assert _wait_drained(eng) == []
        finally:
            eng.stop()


class TestDetokWorkerStopStrings:
    """Stop-string truncation runs on the detokenization worker when
    decode_steps > 1 (classic path matches stop strings in-line on the
    scheduler thread): both paths emit byte-identical truncated text."""

    def test_stop_string_truncates_identically(self, jax_cpu):
        from modal_examples_tpu.serving import SamplingParams

        eng1 = _mk_engine()  # classic in-line stop matching
        eng8 = _mk_engine(params=eng1.params, decode_steps=8)
        try:
            free = SamplingParams(max_tokens=24, temperature=0.0)
            # a random-weight model's ids decode to whatever they happen to
            # (ids past the byte range: to nothing): take the first prompt
            # whose free run has the text a mid-stream stop string needs
            for prompt in (PROMPT, "hello world", "The answer is",
                           "once upon a time", "stop strings need text"):
                ref = eng1.submit(prompt, free)
                ref_text = "".join(eng1.stream(ref))
                if len(ref_text) > 8:
                    break
            assert len(ref_text) > 8
            # a substring from the middle of the free-running output:
            # guaranteed to match mid-stream on both engines
            stop = ref_text[len(ref_text) // 2:len(ref_text) // 2 + 3]
            sp = SamplingParams(max_tokens=24, temperature=0.0, stop=(stop,))

            c = eng1.submit(prompt, sp)
            classic_out = "".join(eng1.stream(c))
            m = eng8.submit(prompt, sp)
            ms_out = "".join(eng8.stream(m))

            assert ms_out == classic_out
            assert m.finish_reason == c.finish_reason == "stop"
            # truncation actually happened: shorter than the free run
            assert len(classic_out) < len(ref_text)
            assert stop not in classic_out
            assert _drained(eng1) == [] and _drained(eng8) == []
        finally:
            eng1.stop()
            eng8.stop()
