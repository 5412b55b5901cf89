"""The detokenization worker of a speculating engine
(serving/spec_runtime/detok.py, docs/speculative.md "The harvest boundary").

A speculative round harvests several tokens a slot, so a speculating engine
decodes text, scans stop strings and emits on the worker's thread; the
plain engine does all three in line on the scheduler's. Both must emit the
same bytes and finish for the same reason.
"""

import time

import pytest


PROMPT = "the quick brown fox jumps over the lazy dog and naps in the sun"


def _mk_engine(params=None, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    return LLMEngine(
        llama.LlamaConfig.tiny(), seed=0, params=params, max_slots=2,
        max_model_len=128, page_size=8, prefill_buckets=(16, 32), **kw,
    )


def _drained(eng) -> list:
    from modal_examples_tpu.faults.chaos import check_drained

    return check_drained({"eng": eng})


def _wait_drained(eng, timeout=30.0) -> list:
    """A stop string is seen on the worker's thread, which can only ask for
    teardown (``req.aborted``): the finish marker is delivered at once, the
    slot is reaped at the next decode tick — poll until the engine drains
    instead of asserting instantaneously."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _drained(eng) == []:
            return []
        time.sleep(0.02)
    return _drained(eng)


def _speculative(mode):
    from modal_examples_tpu.models import llama

    return ("ngram", 4) if mode == "ngram" else (llama.LlamaConfig.tiny(), 4)


@pytest.mark.parametrize("mode", ["ngram", "draft"])
def test_stop_string_truncates_identically(jax_cpu, mode):
    from modal_examples_tpu.serving import SamplingParams

    plain = _mk_engine()  # in-line stop matching
    spec = _mk_engine(params=plain.params, speculative=_speculative(mode))
    try:
        free = SamplingParams(max_tokens=24, temperature=0.0)
        # a random-weight model's ids decode to whatever they happen to
        # (ids past the byte range: to nothing): take the first prompt
        # whose free run has the text a mid-stream stop string needs
        for prompt in (PROMPT, "hello world", "The answer is",
                       "once upon a time", "stop strings need text"):
            ref = plain.submit(prompt, free)
            ref_text = "".join(plain.stream(ref))
            if len(ref_text) > 8:
                break
        assert len(ref_text) > 8
        # a substring from the middle of the free-running output:
        # guaranteed to match mid-stream on both engines
        stop = ref_text[len(ref_text) // 2:len(ref_text) // 2 + 3]
        sp = SamplingParams(max_tokens=24, temperature=0.0, stop=(stop,))

        p = plain.submit(prompt, sp)
        plain_out = "".join(plain.stream(p))
        s = spec.submit(prompt, sp)
        spec_out = "".join(spec.stream(s))

        assert spec._detok is not None and spec._detok.alive
        assert plain._detok is None
        assert spec_out == plain_out
        assert s.finish_reason == p.finish_reason == "stop"
        # truncation actually happened: shorter than the free run
        assert len(plain_out) < len(ref_text)
        assert stop not in plain_out
        assert _wait_drained(plain) == [] and _wait_drained(spec) == []
    finally:
        plain.stop()
        spec.stop()
