"""What every model's chunked prefill is held to: a chunked prompt's last
chunk is as wide as the smallest bucket that holds what is left of it, not
another largest bucket (``LLMEngine._dispatch_prefill_chunk``).

One engine a test file (its model's own), the cases below through each:
``warmed()`` serves the first chunked request, ``check()`` is a case.
"""

from __future__ import annotations

import contextlib

BUCKETS = (16, 32, 64)
C, B0 = BUCKETS[-1], BUCKETS[0]
MAX_MODEL_LEN = 256

#: prompt tokens -> the widths of its chunk calls: the tail in each bucket,
#: at both of a bucket's edges, and a third chunk
CASES = {
    "C+1": (C + 1, [C, 16]),
    "C+b0": (C + B0, [C, 16]),
    "C+b0+1": (C + B0 + 1, [C, 32]),
    "2C-1": (2 * C - 1, [C, C]),
    "2C": (2 * C, [C, C]),
    "2C+1": (2 * C + 1, [C, C, 16]),
}

_CHUNK_PROGRAMS = ("prefill_chunk", "draft_prefill")


def _value(name, **labels):
    from modal_examples_tpu.utils.prometheus import default_registry

    return default_registry.value(name, labels or None) or 0.0


def _chunk_builds() -> float:
    from modal_examples_tpu.observability import catalog

    return sum(
        _value(catalog.COMPILES_TOTAL, program=p, cache=how)
        for p in _CHUNK_PROGRAMS for how in ("miss", "ahead")
    )


def _computed() -> float:
    from modal_examples_tpu.observability import catalog

    return _value(catalog.PREFILL_POSITIONS_TOTAL, kind="computed")


@contextlib.contextmanager
def dispatched(eng):
    """The ``(program, shape_key)`` of every dispatch while the block runs."""
    seen, inner = [], eng._profiled

    def recording(program, shape_key, fn):
        seen.append((program, shape_key))
        return inner(program, shape_key, fn)

    eng._profiled = recording
    try:
        yield seen
    finally:
        del eng._profiled


def _chunk_keys(seen) -> list[str]:
    return [key for program, key in seen if program == "prefill_chunk"]


def serve(eng, n_prompt: int, seed: int, n_out: int = 6):
    """Greedy tokens for a prompt of exactly ``n_prompt`` tokens (the byte
    tokenizer: BOS and a letter a token), the letters drawn from ``seed``.
    Driven by ``step()`` on the caller's thread: the scheduler thread never
    starts, so ``check()`` may call the atomic loop beside it."""
    import queue
    import time

    import numpy as np

    from modal_examples_tpu.serving import SamplingParams
    from modal_examples_tpu.serving.engine import _Finish

    letters = np.random.default_rng(seed).integers(97, 123, size=n_prompt - 1)
    req = eng.submit(
        bytes(letters.tolist()).decode(), SamplingParams(max_tokens=n_out, temperature=0.0)
    )
    assert len(req.prompt_tokens) == n_prompt
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        if not eng.step():
            time.sleep(0.002)  # the detokenizer's thread owes the end marker
        with contextlib.suppress(queue.Empty):
            while not isinstance(req.out_queue.get_nowait(), _Finish):
                pass
            return list(req.prompt_tokens), list(req.generated_tokens)
    raise AssertionError("the request did not finish in 120 s")


def warmed(eng):
    """``eng`` after its first chunked request: one that reaches offsets 0, C
    and 2C, so every program a case can need is queued; then idle, and the
    helper threads have built them all."""
    serve(eng, 2 * C + 1, seed=0)
    settle(eng)
    return eng


def settle(eng) -> None:
    """Idle ticks until the engine hands the queued programs to the helper
    threads (it waits a tenth of a second for the last response to leave),
    then wait for every helper."""
    import time

    deadline = time.monotonic() + 10.0
    while eng._chunk_queued and time.monotonic() < deadline:
        assert not eng.step()
        time.sleep(0.01)
    assert not eng._chunk_queued
    for key, program in list(eng._chunk_programs.items()):
        if hasattr(program, "result"):  # a helper is at it: a stuck one fails, not hangs
            program.result(timeout=300.0)
        eng._chunk_program(*key)


def check(eng, case: str, monkeypatch) -> None:
    n_prompt, widths = CASES[case]
    keys = [f"off{i * C}w{w}" for i, w in enumerate(widths)]
    builds, programs = _chunk_builds(), set(eng._chunk_programs)
    jit_sizes = {off: fn._cache_size() for off, fn in eng._chunk_jits.items()}
    computed = _computed()

    # the budgeted state machine: the widths, and what the counter says of them
    with dispatched(eng) as seen:
        prompt, served = serve(eng, n_prompt, seed=n_prompt)
    assert _chunk_keys(seen) == keys
    assert _computed() - computed == sum(widths)
    assert served

    # the parent's rule, spelt out: every chunk the largest bucket wide
    with monkeypatch.context() as m:
        m.setattr(eng, "_bucket_for", lambda n: C)
        with dispatched(eng) as seen:
            assert serve(eng, n_prompt, seed=n_prompt) == (prompt, served)
        assert _chunk_keys(seen) == [f"off{i * C}w{C}" for i in range(len(widths))]

    # the atomic loop of the slot-free path goes through the same unit
    import numpy as np

    from modal_examples_tpu.serving import SamplingParams
    from modal_examples_tpu.serving.engine import Request

    with dispatched(eng) as seen:
        eng._run_prefill_chunks(
            Request("", SamplingParams(temperature=0.0), prompt_tokens=prompt),
            np.zeros((eng.pages_per_slot,), np.int32),
        )
    assert _chunk_keys(seen) == keys

    # nothing was built after the first chunked request
    assert _chunk_builds() == builds
    assert set(eng._chunk_programs) == programs
    assert {off: fn._cache_size() for off, fn in eng._chunk_jits.items()} == jit_sizes
