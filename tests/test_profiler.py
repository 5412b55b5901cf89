"""Hot-path profiler (observability/profiler.py, docs/observability.md):
fake-clock phase-attribution matrix over scoped spans, the trace-annotation
order, device starvation under scripted dispatch/harvest sequences, the
on-by-default switch and the zero-cost disabled gate (behavioral AND
AST-pinned, like the faults gate), compile-ledger schema + miss/hit
accounting, and the CLI/gateway surfaces."""

import ast
import json
import os
import shutil
from pathlib import Path

import pytest

from modal_examples_tpu.observability import catalog as C
from modal_examples_tpu.observability import profiler as P
from modal_examples_tpu.utils.prometheus import Registry

PKG_ROOT = Path(__file__).resolve().parents[1] / "modal_examples_tpu"


class ManualClock:
    """Monotonic fake clock advanced explicitly between marks."""

    def __init__(self):
        self.t = 100.0

    def advance(self, dt: float) -> None:
        self.t += dt

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# tick anatomy: fake-clock attribution matrix
# ---------------------------------------------------------------------------


class TestTickAttribution:
    def test_each_phase_lands_in_its_own_series(self, tmp_path):
        """The attribution matrix: a tick marking every phase with a known
        delta puts EXACTLY that delta in that phase's ring slot and
        histogram series — no bleed, no double count — and the deltas sum
        to the tick total."""
        clk = ManualClock()
        reg = Registry()
        prof = P.HotPathProfiler(
            clock=clk, name="t-rep", registry=reg,
            ledger_path=tmp_path / "compiles.jsonl",
        )
        deltas = {
            phase: 0.001 * (i + 1) for i, phase in enumerate(C.TICK_PHASES)
        }
        tick = prof.begin_tick()
        for phase, dt in deltas.items():
            tick.enter(phase, device=(phase == "harvest"))
            clk.advance(dt)
        prof.end_tick(tick, worked=True)

        [entry] = prof.perfetto_snapshot()["ticks"]
        for phase, dt in deltas.items():
            assert entry["phases"][phase] == pytest.approx(dt), phase
            q = reg.histogram_quantiles(
                C.TICK_PHASE_SECONDS, labels={"phase": phase}
            )
            assert q is not None and q["count"] == 1, phase
            assert q["sum"] == pytest.approx(dt), phase
        assert entry["total"] == pytest.approx(sum(deltas.values()))
        assert entry["device"] == pytest.approx(deltas["harvest"])
        total_q = reg.histogram_quantiles(
            C.TICK_PHASE_SECONDS, labels={"phase": C.TICK_TOTAL_PHASE}
        )
        assert total_q["sum"] == pytest.approx(sum(deltas.values()))

        summary = prof.overhead_summary()
        assert summary["ticks"] == 1
        # summary fields are rounded to 6 decimals: compare with abs tol
        assert summary["attribution_cover"] == pytest.approx(1.0, abs=1e-5)
        assert summary["host_fraction"] == pytest.approx(
            1.0 - deltas["harvest"] / sum(deltas.values()), abs=1e-5
        )
        assert summary["detok_share"] == pytest.approx(
            deltas["detokenize"] / sum(deltas.values()), abs=1e-5
        )
        assert summary["tick_p95"] == pytest.approx(
            sum(deltas.values()), abs=1e-5
        )

    def test_idle_ticks_record_nothing(self, tmp_path):
        clk = ManualClock()
        reg = Registry()
        prof = P.HotPathProfiler(
            clock=clk, name="t-idle", registry=reg,
            ledger_path=tmp_path / "compiles.jsonl",
        )
        # worked=False: even a tick that entered phases is discarded
        tick = prof.begin_tick()
        tick.enter("ctrl")
        clk.advance(0.5)
        prof.end_tick(tick, worked=False)
        # worked=True but no phase entered: also discarded
        prof.end_tick(prof.begin_tick(), worked=True)
        # no request queued or running, nothing on the device: the tick
        # is not profiled at all (no timestamp is taken)
        assert prof.begin_tick(demand=False) is None
        assert prof.perfetto_snapshot()["ticks"] == []
        assert prof.overhead_summary()["ticks"] == 0
        assert reg.histogram_quantiles(
            C.TICK_PHASE_SECONDS, labels={"phase": "ctrl"}
        ) is None

    def test_spans_of_one_phase_accumulate(self):
        """Two spans of one phase in a tick accumulate (the _admit path
        enters prefill_resume twice), and enter() returns the seconds of
        the span it closed (the harvest span's, for the roofline meter)."""
        clk = ManualClock()
        prof = P.HotPathProfiler(clock=clk, registry=Registry())
        tick = prof.begin_tick()
        tick.enter("prefill_resume")
        clk.advance(0.002)
        assert tick.enter("admit") == pytest.approx(0.002)
        clk.advance(0.001)
        tick.enter("prefill_resume")
        clk.advance(0.003)
        prof.end_tick(tick, worked=True)
        [entry] = prof.perfetto_snapshot()["ticks"]
        assert entry["phases"]["prefill_resume"] == pytest.approx(0.005)
        assert entry["phases"]["admit"] == pytest.approx(0.001)

    def test_scoped_phases_partition_a_tick(self):
        """Entered one after the other, the phases cover the tick: what is
        not attributed is only what passed before the first was entered."""
        clk = ManualClock()
        prof = P.HotPathProfiler(clock=clk, registry=Registry())
        tick = prof.begin_tick()
        clk.advance(0.0001)  # before the first phase: unattributed
        for phase in C.TICK_PHASES:
            tick.enter(phase)
            clk.advance(0.004)
            tick.enter("detokenize", annotate=False)  # a per-token split
            clk.advance(0.001)
            tick.enter(phase, annotate=False)
            clk.advance(0.001)
        prof.end_tick(tick, worked=True)
        summary = prof.overhead_summary()
        assert 0.95 <= summary["attribution_cover"] <= 1.0
        [entry] = prof.perfetto_snapshot()["ticks"]
        assert sum(entry["phases"].values()) == pytest.approx(
            entry["total"] - 0.0001
        )


class FakeAnnotations:
    """The engine hands the profiler ``jax.profiler.TraceAnnotation``; the
    tests hand it this: every enter and exit, in order."""

    def __init__(self):
        self.log: list[tuple] = []

    def __call__(self, name, **attrs):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.log.append(("enter", name, attrs))

            def __exit__(self, *exc):
                outer.log.append(("exit", name))

        return _Ann()


class TestAnnotations:
    def test_every_phase_and_dispatch_opens_and_closes_in_order(self, tmp_path):
        clk = ManualClock()
        ann = FakeAnnotations()
        prof = P.HotPathProfiler(
            clock=clk, registry=Registry(), annotate=ann,
            ledger_path=tmp_path / "compiles.jsonl",
        )
        tick = prof.begin_tick()
        tick.enter("admit")
        tick.enter("prefill_dispatch")
        assert prof.dispatch("prefill", "b32x4", lambda x: x + 1, (1,), {}) == 2
        tick.enter("accept")
        tick.enter("detokenize", annotate=False)  # accounting only
        tick.enter("accept", annotate=False)
        prof.end_tick(tick, worked=True)
        assert ann.log == [
            ("enter", "mtpu.tick/admit", {}),
            ("exit", "mtpu.tick/admit"),
            ("enter", "mtpu.tick/prefill_dispatch", {}),
            ("enter", "mtpu.dispatch/prefill", {"shape": "b32x4"}),
            ("exit", "mtpu.dispatch/prefill"),
            ("exit", "mtpu.tick/prefill_dispatch"),
            ("enter", "mtpu.tick/accept", {}),
            ("exit", "mtpu.tick/accept"),
        ]

    def test_a_dispatch_that_raises_still_closes_its_annotation(self, tmp_path):
        ann = FakeAnnotations()
        prof = P.HotPathProfiler(
            clock=ManualClock(), registry=Registry(), annotate=ann,
            ledger_path=tmp_path / "compiles.jsonl",
        )

        def boom():
            raise ValueError("no")

        with pytest.raises(ValueError):
            prof.dispatch("block", "s4k8", boom, (), {})
        assert [e[:2] for e in ann.log] == [
            ("enter", "mtpu.dispatch/block"), ("exit", "mtpu.dispatch/block"),
        ]
        assert prof.dispatched == 0  # nothing reached the device


class TestStarvation:
    """mtpu_device_starved_seconds_total: scheduler-thread time with
    nothing dispatched and unharvested while a request is queued or
    running, under the phase the thread was in."""

    def _prof(self, tmp_path):
        clk = ManualClock()
        reg = Registry()
        prof = P.HotPathProfiler(
            clock=clk, registry=reg, ledger_path=tmp_path / "compiles.jsonl"
        )
        return clk, reg, prof

    @staticmethod
    def _starved(reg):
        return {
            phase: reg.value(
                C.DEVICE_STARVED_SECONDS_TOTAL, labels={"phase": phase}
            )
            for phase in C.TICK_PHASES
            if reg.value(
                C.DEVICE_STARVED_SECONDS_TOTAL, labels={"phase": phase}
            )
        }

    def test_admission_wait_with_nothing_in_flight_is_all_starved(self, tmp_path):
        """A request reaches an idle engine: until the prefill is
        dispatched the device has nothing, and all of it is `admit`'s and
        `prefill_dispatch`'s."""
        clk, reg, prof = self._prof(tmp_path)
        tick = prof.begin_tick(demand=True)
        tick.enter("admit")
        clk.advance(0.030)
        tick.enter("prefill_dispatch")
        clk.advance(0.004)  # host work before the program is queued
        prof.dispatch("prefill", "b32x4", lambda: None, (), {})
        clk.advance(0.010)  # after the dispatch: the device has work
        tick.enter("harvest", device=True)
        clk.advance(0.100)
        tick.enter("accept")
        prof.note_harvest(prof.dispatched, tick)
        prof.end_tick(tick, worked=True, demand=False)
        got = self._starved(reg)
        assert got == {
            "admit": pytest.approx(0.030),
            "prefill_dispatch": pytest.approx(0.004),
        }

    def test_a_pipelined_second_block_leaves_no_starvation(self, tmp_path):
        """Block 2 is dispatched before block 1 is harvested: the device
        always has one queued, whatever the host does in between."""
        clk, reg, prof = self._prof(tmp_path)
        tick = prof.begin_tick(demand=True)
        tick.enter("decode_dispatch")
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        first = prof.dispatched
        prof.end_tick(tick, worked=True, demand=True)
        for _ in range(3):
            tick = prof.begin_tick(demand=True)
            tick.enter("decode_dispatch")
            clk.advance(0.002)
            prof.dispatch("block", "s4k8", lambda: None, (), {})
            second = prof.dispatched
            tick.enter("harvest", device=True)
            clk.advance(0.050)
            tick.enter("accept")
            prof.note_harvest(first, tick)  # the OLDER block: one still runs
            clk.advance(0.005)
            prof.end_tick(tick, worked=True, demand=True)
            first = second
        assert self._starved(reg) == {}

    def test_harvest_of_the_last_block_starts_the_account(self, tmp_path):
        """Nothing queued behind the harvested block: accept, the next
        tick's bookkeeping and its dispatch preparation all starve the
        device, each under its own phase, until the next dispatch."""
        clk, reg, prof = self._prof(tmp_path)
        tick = prof.begin_tick(demand=True)
        tick.enter("decode_dispatch")
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        tick.enter("harvest", device=True)
        clk.advance(0.050)
        tick.enter("accept")
        prof.note_harvest(prof.dispatched, tick)
        clk.advance(0.003)
        prof.end_tick(tick, worked=True, demand=True)
        clk.advance(0.001)  # between ticks: goes to the next one's first phase
        tick = prof.begin_tick(demand=True)
        tick.enter("ctrl")
        clk.advance(0.002)
        tick.enter("decode_dispatch")
        clk.advance(0.016)
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        clk.advance(0.004)
        prof.end_tick(tick, worked=True, demand=True)
        assert self._starved(reg) == {
            "accept": pytest.approx(0.003),
            "ctrl": pytest.approx(0.003),
            "decode_dispatch": pytest.approx(0.016),
        }

    def test_a_block_finished_but_unread_counts_as_running(self, tmp_path):
        """The bound is a LOWER one. The closed cells' stall: a decode
        block and a first token are dispatched and unread, the eager
        sampler has drained the device, and admission then takes 0.85 s of
        host time. By dispatch and harvest numbers the device still has
        work, so none of it is counted (the trace annotations see it:
        ``tpurun profile --xplane``); the account opens at the harvest
        that finds nothing queued behind it."""
        clk, reg, prof = self._prof(tmp_path)
        tick = prof.begin_tick(demand=True)
        tick.enter("decode_dispatch")
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        tick.enter("prefill_resume")
        prof.dispatch("sample", "first_token", lambda: None, (), {})
        clk.advance(0.400)
        tick.enter("admit")
        clk.advance(0.850)  # the device ran dry in here; nothing was read
        tick.enter("harvest", device=True)
        clk.advance(0.001)
        tick.enter("accept")
        prof.note_harvest(prof.dispatched, tick)
        assert self._starved(reg) == {}
        clk.advance(0.005)
        prof.end_tick(tick, worked=True, demand=True)
        assert self._starved(reg) == {"accept": pytest.approx(0.005)}

    def test_an_idle_engine_accrues_none(self, tmp_path):
        clk, reg, prof = self._prof(tmp_path)
        # a finished engine: the last block was harvested, no request left
        tick = prof.begin_tick(demand=True)
        tick.enter("decode_dispatch")
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        tick.enter("harvest", device=True)
        tick.enter("accept")
        prof.note_harvest(prof.dispatched, tick)
        prof.end_tick(tick, worked=True, demand=False)
        for _ in range(100):
            clk.advance(0.002)
            assert prof.begin_tick(demand=False) is None
        assert self._starved(reg) == {}
        # after a warm-up outside the loop nothing is outstanding either
        prof.dispatch("block", "s4k8", lambda: None, (), {})
        prof.note_drained()
        clk.advance(5.0)
        assert prof.begin_tick(demand=False) is None
        assert self._starved(reg) == {}


# ---------------------------------------------------------------------------
# compile telemetry: ledger schema + cache-hit accounting
# ---------------------------------------------------------------------------


class TestCompileTelemetry:
    def test_ledger_schema_and_cache_hit_accounting(self, tmp_path):
        clk = ManualClock()
        reg = Registry()
        ledger = tmp_path / "compiles.jsonl"
        prof = P.HotPathProfiler(
            clock=clk, name="t-cc", registry=reg, ledger_path=ledger
        )
        # first dispatch: a miss — timed, ledgered (begin THEN end)
        assert prof.dispatch(
            "block", "s4k8", lambda: clk.advance(1.5) or "out", (), {}
        ) == "out"
        # second dispatch of the same key: a hit — counted, not ledgered
        prof.dispatch("block", "s4k8", lambda: None, (), {})

        rows = [json.loads(l) for l in ledger.read_text().splitlines()]
        assert [r["event"] for r in rows] == ["begin", "end"]
        begin, end = rows
        assert {"at", "event", "replica", "program", "shape_key"} <= set(
            begin
        )
        assert {"at", "event", "replica", "program", "shape_key", "seconds",
                "cache"} <= set(end)
        assert end["program"] == "block" and end["shape_key"] == "s4k8"
        assert end["seconds"] == pytest.approx(1.5)
        assert end["cache"] == "miss" and end["replica"] == "t-cc"

        assert reg.value(
            C.COMPILES_TOTAL, labels={"program": "block", "cache": "miss"}
        ) == 1.0
        assert reg.value(
            C.COMPILES_TOTAL, labels={"program": "block", "cache": "hit"}
        ) == 1.0
        q = reg.histogram_quantiles(
            C.COMPILE_SECONDS, labels={"program": "block"}
        )
        assert q["count"] == 1 and q["sum"] == pytest.approx(1.5)
        summary = prof.overhead_summary()
        assert summary["compiles_n"] == 1
        assert summary["compile_total_s"] == pytest.approx(1.5)

    def test_unfinished_builds_name_the_ceiling(self, tmp_path):
        """A begin event with no matching end — the process died or hung
        mid-build — is exactly what the ≥40-slot ceiling repro needs named
        offline."""
        clk = ManualClock()
        prof = P.HotPathProfiler(
            clock=clk, name="t-dead", registry=Registry(),
            ledger_path=tmp_path / "compiles.jsonl",
        )
        prof.dispatch("prefill", "b256x4", lambda: None, (), {})

        class Died(BaseException):
            """The process dying mid-build, as far as a test can."""

        def build_and_die():
            raise Died

        with pytest.raises(Died):
            prof.dispatch("block", "s44k8", build_and_die, (), {})
        rows = P.read_ledger(tmp_path / "compiles.jsonl")
        open_builds = P.unfinished_builds(rows)
        assert [(r["program"], r["shape_key"]) for r in open_builds] == [
            ("block", "s44k8")
        ]
        # the failed build is forgotten: the retry is a fresh miss
        prof.dispatch("block", "s44k8", lambda: None, (), {})
        assert not P.unfinished_builds(
            P.read_ledger(tmp_path / "compiles.jsonl")
        )

    def test_a_program_built_ahead_is_its_keys_one_build(self, tmp_path):
        """``build`` (lower and compile, nothing run) books the build under
        the dispatch's own (program, shape_key), from whatever thread, as
        ``ahead`` where no dispatch waits for it and as a miss where one
        does: the compiled program's first dispatch is then a hit and
        numbers one program for the starvation account; a build that raises
        is forgotten."""
        import threading

        import jax
        import jax.numpy as jnp

        clk, reg = ManualClock(), Registry()
        prof = P.HotPathProfiler(
            clock=clk, name="t-aot", registry=reg,
            ledger_path=tmp_path / "compiles.jsonl",
        )
        fn = jax.jit(lambda x: x + 1)
        built = {}

        def ahead(n):
            built[n] = prof.build(
                "prefill_chunk", f"off64w{n}",
                lambda: clk.advance(2.0) or fn.lower(jnp.zeros((n,))).compile(),
                ahead=n == 32,
            )

        threads = [threading.Thread(target=ahead, args=(n,)) for n in (16, 32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert prof.dispatched == 0
        for n in (16, 32, 16):
            out = prof.dispatch(
                "prefill_chunk", f"off64w{n}", built[n], (jnp.zeros((n,)),), {}
            )
            assert out.shape == (n,)
        assert prof.dispatched == 3

        def count(cache):
            return reg.value(
                C.COMPILES_TOTAL, labels={"program": "prefill_chunk", "cache": cache}
            )

        assert (count("miss"), count("ahead"), count("hit")) == (1.0, 1.0, 3.0)
        rows = P.read_ledger(tmp_path / "compiles.jsonl")
        assert sorted((r["event"], r["shape_key"], r.get("cache")) for r in rows) == [
            ("begin", "off64w16", None), ("begin", "off64w32", None),
            ("end", "off64w16", "miss"), ("end", "off64w32", "ahead"),
        ]
        assert not P.unfinished_builds(rows)
        with pytest.raises(ZeroDivisionError):
            prof.build("prefill_chunk", "off64w64", lambda: 1 / 0)
        prof.dispatch("prefill_chunk", "off64w64", lambda: None, (), {})
        assert count("miss") == 2.0  # the retry is a fresh miss

    def test_a_rebuild_under_a_seen_key_is_a_miss(self, tmp_path):
        """XLA builds a program again when an argument's shape changes,
        whatever (program, shape_key) the engine calls it by: the jitted
        function's own cache grows during the call, and that is a miss."""
        import jax
        import jax.numpy as jnp

        reg = Registry()
        prof = P.HotPathProfiler(
            clock=ManualClock(), registry=reg,
            ledger_path=tmp_path / "compiles.jsonl",
        )
        fn = jax.jit(lambda x: x + 1)
        for n in (4, 4, 8, 8, 4):
            prof.dispatch("block", "s4k8", fn, (jnp.zeros((n,)),), {})
        miss = reg.value(
            C.COMPILES_TOTAL, labels={"program": "block", "cache": "miss"}
        )
        hit = reg.value(
            C.COMPILES_TOTAL, labels={"program": "block", "cache": "hit"}
        )
        assert (miss, hit) == (2.0, 3.0)
        ends = [
            r for r in P.read_ledger(tmp_path / "compiles.jsonl")
            if r["event"] == "end"
        ]
        assert len(ends) == 2


# ---------------------------------------------------------------------------
# the real engine: end-to-end attribution + zero-cost disabled gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profiled_engine(tmp_path_factory):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine, SamplingParams

    eng = LLMEngine(
        llama.LlamaConfig.tiny(),
        max_slots=4,
        max_model_len=128,
        prefill_buckets=(32, 64),
        profile=True,  # explicit arg beats env: no monkeypatching needed
    )
    # every engine of every xdist worker writes the one session ledger now
    # that the profiler is on by default: a name of its own tells this
    # engine's builds from one still compiling in another worker
    eng.trace_name = f"profiled-{os.getpid()}"
    eng.start()
    reqs = [
        eng.submit(
            "the quick brown fox " * 3,
            SamplingParams(max_tokens=10, temperature=0.0),
        )
        for _ in range(3)
    ]
    for r in reqs:
        "".join(eng.stream(r))
    eng.stop()
    return eng


class TestEngineIntegration:
    def test_phases_attributed_and_sum_to_tick(self, profiled_engine):
        """The CPU path-proof of the acceptance criterion: per-phase
        attribution is present for the whole serving anatomy and sums to
        ~the tick duration (sequential marks partition the tick, so cover
        can never exceed 1)."""
        summary = profiled_engine.profiler.overhead_summary()
        assert summary["ticks"] >= 1
        # a real decode run exercises the full non-spec anatomy
        for phase in (
            "ctrl", "policy", "admit", "prefill_dispatch",
            "decode_dispatch", "harvest", "detokenize", "accept",
        ):
            assert phase in summary["phases"], (phase, summary["phases"])
        assert 0.8 <= summary["attribution_cover"] <= 1.0 + 1e-6
        assert 0.0 <= summary["host_fraction"] <= 1.0
        assert 0.0 <= summary["detok_share"] <= 1.0
        assert summary["tick_p50"] <= summary["tick_p95"]

    def test_engine_compiles_are_ledgered(self, profiled_engine):
        """Nonzero compile ledger: the block program and at least one
        prefill bucket built through the chokepoint, and re-dispatches
        counted as cache hits."""
        summary = profiled_engine.profiler.overhead_summary()
        assert summary["compiles_n"] >= 2
        assert summary["compile_total_s"] > 0
        snap = profiled_engine.profiler.perfetto_snapshot()
        programs = {c["program"] for c in snap["compiles"]}
        assert {"block", "prefill"} <= programs
        rows = P.read_ledger()
        mine = [
            r for r in rows
            if r.get("replica") == profiled_engine.profiler.replica
        ]
        assert {"begin", "end"} <= {r["event"] for r in mine}
        assert not P.unfinished_builds(mine)

    def test_a_chunked_prompt_builds_nothing_and_never_dispatches_the_sampler(self):
        """Its first token comes out of the last chunk's program: once the
        programs are built, a chunked prompt makes JAX trace, lower, compile
        or read back nothing on the scheduler's thread (the eager sampler
        traced its ``lax.cond`` anew at every call: 61 events a prompt), no
        dispatch is of program ``sample``, and what follows the last chunk's
        call is the decode block."""
        import threading

        import chunk_tail
        import jax

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=chunk_tail.MAX_MODEL_LEN,
            prefill_buckets=chunk_tail.BUCKETS, profile=True,
        )
        eng.trace_name = f"chunked-{os.getpid()}"
        n_prompt = 2 * chunk_tail.C + 1  # chunks at offsets 0, C and 2C
        built, recording, me = [], [False], threading.get_ident()

        def on_seconds(event, seconds, **_kw):  # registered for the life of the process
            if recording[0] and threading.get_ident() == me and event in P.COMPILE_EVENT_KIND:
                built.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_seconds)
        try:
            chunk_tail.warmed(eng)
            recording[0] = True
            with chunk_tail.dispatched(eng) as seen:
                assert chunk_tail.serve(eng, n_prompt, seed=3)[1]
        finally:
            recording[0] = False
            eng.stop()
        programs = [program for program, _key in seen]
        assert programs.count("prefill_chunk") == 3 and "sample" not in programs
        last_chunk = max(i for i, p in enumerate(programs) if p == "prefill_chunk")
        assert programs[last_chunk + 1] == "block"
        assert built == []
        from modal_examples_tpu.utils.prometheus import default_registry

        registry = eng.profiler._registry or default_registry
        assert not [
            labels for name, labels, *_ in registry.all_series()
            if name == C.COMPILE_PHASE_SECONDS_TOTAL and labels.get("program") == "sample"
        ]

    def test_disabled_engine_has_no_profiler(self):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine

        eng = LLMEngine(
            llama.LlamaConfig.tiny(),
            max_slots=2,
            max_model_len=64,
            prefill_buckets=(32,),
            profile=False,
        )
        assert eng.profiler is None
        assert eng._tick is None

    def test_the_roofline_meter_keeps_its_seconds_with_the_profiler_off(self):
        """`MTPU_PROFILE=0` switches the spans off, not the device seconds
        under the MFU/MBU gauges: unprofiled, the blocking reads are timed
        on the engine's clock directly."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(32,), profile=False,
        )
        eng.start()
        try:
            params = SamplingParams(max_tokens=6, temperature=0.0)
            "".join(eng.stream(eng.submit("the quick brown fox", params)))
        finally:
            eng.stop()
        seconds = eng.usage._phase_seconds
        assert seconds["prefill"] > 0 and seconds["decode"] > 0

    def test_on_by_default_and_the_env_is_the_off_switch(self, monkeypatch):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine

        def build(**kw):
            return LLMEngine(
                llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
                prefill_buckets=(32,), **kw,
            )

        monkeypatch.delenv("MTPU_PROFILE", raising=False)
        assert P.profiling_enabled() is True
        assert build().profiler is not None  # unset means on
        monkeypatch.setenv("MTPU_PROFILE", "1")
        assert build().profiler is not None
        monkeypatch.setenv("MTPU_PROFILE", "0")
        eng = build()
        assert eng.profiler is None and eng._tick is None
        # explicit arg beats env, both ways
        assert build(profile=True).profiler is not None
        monkeypatch.setenv("MTPU_PROFILE", "1")
        assert build(profile=False).profiler is None


class TestDisabledGateShape:
    """The zero-cost contract pinned at the AST level, like
    test_static.test_disabled_fault_gate_is_structurally_a_no_op: with
    profiling off the hot path is a None-check — no timestamp, no
    allocation, no dict write."""

    def _engine_tree(self):
        return ast.parse((PKG_ROOT / "serving" / "engine.py").read_text())

    def _fn(self, tree, name):
        return next(
            n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef) and n.name == name
        )

    @staticmethod
    def _body(fn):
        return [
            n for n in fn.body
            if not (
                isinstance(n, ast.Expr) and isinstance(n.value, ast.Constant)
            )
        ]

    def test_tm_helpers_are_one_branch(self):
        tree = self._engine_tree()
        for name in ("_tm", "_tm_device", "_tm_inner"):
            body = self._body(self._fn(tree, name))
            assert len(body) == 1, f"{name} must be ONE statement"
            guard = body[0]
            assert isinstance(guard, ast.If) and not guard.orelse
            test = guard.test
            assert (
                isinstance(test, ast.Compare)
                and isinstance(test.left, ast.Name)
                and test.left.id == "tick"
                and isinstance(test.ops[0], ast.IsNot)
                and test.comparators[0].value is None
            ), f"{name} must test `tick is not None` and nothing else"

    def test_profiled_opens_with_none_fast_path(self):
        body = self._body(self._fn(self._engine_tree(), "_profiled"))
        first, second = body[0], body[1]
        assert (
            isinstance(first, ast.Assign)
            and isinstance(first.value, ast.Attribute)
            and first.value.attr == "profiler"
        ), "_profiled must read self.profiler first"
        assert isinstance(second, ast.If)
        test = second.test
        assert (
            isinstance(test, ast.Compare)
            and isinstance(test.ops[0], ast.Is)
            and test.comparators[0].value is None
        ), "_profiled must test `prof is None` second"
        ret = second.body[0]
        assert (
            isinstance(ret, ast.Return)
            and isinstance(ret.value, ast.Name)
            and ret.value.id == "fn"
        ), "the disabled path must return fn UNWRAPPED (no closure alloc)"

    def test_step_creates_tick_conditionally(self):
        step = self._fn(self._engine_tree(), "step")
        ifexps = [
            n for n in ast.walk(step)
            if isinstance(n, ast.IfExp)
            and isinstance(n.test, ast.Compare)
            and isinstance(n.test.ops[0], ast.Is)
            and n.test.comparators[0].value is None
            and isinstance(n.body, ast.Constant)
            and n.body.value is None
        ]
        assert ifexps, (
            "step() must create the tick via `None if prof is None else "
            "prof.begin_tick(...)` — the disabled tick path takes no timestamp"
        )


# ---------------------------------------------------------------------------
# surfaces: CLI + gateway
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_cli_profile_renders_phase_table_and_ledger(
        self, profiled_engine, tmp_path, capsys
    ):
        from modal_examples_tpu._internal import config as _config
        from modal_examples_tpu.core.cli import main as cli_main
        from modal_examples_tpu.observability.export import push_metrics_file

        root = tmp_path / "state"
        (root / "metrics").mkdir(parents=True)
        push_metrics_file("bench-profiled", root=root / "metrics")
        shutil.copy(
            _config.state_dir() / P.LEDGER_NAME, root / P.LEDGER_NAME
        )
        assert cli_main(["profile", "--dir", str(root)]) == 0
        out = capsys.readouterr().out
        for phase in ("decode_dispatch", "harvest", "detokenize", "total"):
            assert phase in out, out
        assert "top compiles" in out
        assert "block" in out

        assert cli_main(["profile", "--json", "--dir", str(root)]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["compiles_n"] >= 2
        assert payload["phases"]["total"]["count"] >= 1
        name = profiled_engine.profiler.replica
        assert not [
            r for r in payload["unfinished_builds"] if r["replica"] == name
        ]

    def test_cli_profile_empty_state_says_so(self, tmp_path, capsys):
        from modal_examples_tpu.core.cli import main as cli_main

        root = tmp_path / "empty"
        (root / "metrics").mkdir(parents=True)
        assert cli_main(["profile", "--dir", str(root)]) == 0
        assert "no tick-phase series" in capsys.readouterr().out

    def test_gateway_profile_snapshot(self, profiled_engine):
        from modal_examples_tpu.web.gateway import _profile_snapshot

        snap = _profile_snapshot()
        name = profiled_engine.profiler.replica
        assert name in snap["replicas"]
        node = snap["replicas"][name]
        assert node["summary"]["ticks"] >= 1
        assert node["perfetto"]["ticks"]
        assert {"at", "total", "device", "phases"} <= set(
            node["perfetto"]["ticks"][0]
        )
        assert isinstance(snap["ledger"], list)
        assert not [
            r for r in snap["unfinished_builds"] if r["replica"] == name
        ]


# ---------------------------------------------------------------------------
# the spans in a device trace: `tpurun profile --xplane`
# ---------------------------------------------------------------------------


class TestXplane:
    def test_idle_seconds_go_to_the_phase_that_covered_them(self):
        from modal_examples_tpu.observability import xplane as X

        # device: busy 0-1, idle 1-1.5, busy 1.5-2 (two overlapping ops),
        # idle 2-2.1, busy 2.1-3
        ops = [(0.0, 1.0), (1.5, 1.8), (1.7, 2.0), (2.1, 3.0)]
        spans = [
            ("harvest", 0.2, 1.1),           # covers 1.0-1.1 of the first gap
            ("accept", 1.1, 1.3),            # 1.1-1.3
            ("decode_dispatch", 1.35, 1.6),  # 1.35-1.5; 1.3-1.35 uncovered
            ("admit", 1.95, 2.5),            # all of the second gap
        ]
        got = X.idle_by_phase(ops, spans)
        assert got["window_s"] == pytest.approx(3.0)
        assert got["busy_s"] == pytest.approx(2.4)
        assert got["idle_s"] == pytest.approx(0.6)
        idle = {p: row["idle_s"] for p, row in got["phases"].items()}
        assert idle == {
            "harvest": pytest.approx(0.1), "accept": pytest.approx(0.2),
            "decode_dispatch": pytest.approx(0.15),
            X.UNCOVERED: pytest.approx(0.05), "admit": pytest.approx(0.1),
        }
        assert sum(idle.values()) == pytest.approx(got["idle_s"])
        assert got["phases"]["accept"]["longest_s"] == pytest.approx(0.2)
        # whose the longest gap is: every phase that covered part of it
        first, second = got["longest_gaps"]
        assert first["seconds"] == pytest.approx(0.5)
        assert first["at_s"] == pytest.approx(1.0)
        assert first["phases"] == {
            "harvest": pytest.approx(0.1), "accept": pytest.approx(0.2),
            "decode_dispatch": pytest.approx(0.15),
            X.UNCOVERED: pytest.approx(0.05),
        }
        assert second["phases"] == {"admit": pytest.approx(0.1)}

    def test_idle_total_agrees_with_the_benchmarks_reduction(self):
        """On the benchmark's own recorded trace: the same busy union, the
        same window, so the same idle time (the issue allows 1%)."""
        import sys

        from modal_examples_tpu.observability import xplane as X

        bench = PKG_ROOT.parent / "benchmarks" / "serving"
        recorded = json.loads(
            (PKG_ROOT.parent / "tests" / "bench_serving"
             / "recorded_trace.json").read_text()
        )
        sys.path.insert(0, str(bench))
        try:
            import trace_reduce
        finally:
            sys.path.remove(str(bench))
        theirs = trace_reduce.reduce_events(recorded)
        chips = {
            name: [(s, s + d) for _n, s, d in chip["ops"]]
            for name, chip in recorded["chips"].items()
        }
        mine = X.reduce_chips(chips, spans=[])
        their_idle = theirs["window_s"] - theirs["busy_s"]
        assert mine["idle_s"] == pytest.approx(their_idle, rel=0.01)
        assert mine["busy_s"] == pytest.approx(theirs["busy_s"], rel=1e-6)
        # no spans in a trace from before the annotations: all uncovered
        assert set(mine["phases"]) == {X.UNCOVERED}
        assert "no mtpu.tick/* events" in "\n".join(X.render(mine))

    def test_render_ranks_phases_by_idle_time(self):
        from modal_examples_tpu.observability import xplane as X

        report = X.reduce_chips(
            {"/device:TPU:0": [(0.0, 1.0), (1.5, 2.0), (2.1, 3.0)]},
            [("prefill_dispatch", 0.9, 1.6), ("admit", 1.9, 2.2)],
        )
        report["dispatches"] = {"block": 3}
        lines = X.render(report)
        assert lines[0].startswith("device: busy 2.400s  idle 0.600s  (20.00%)")
        assert lines[2].split()[0] == "prefill_dispatch"
        assert lines[3].split()[0] == "admit"
        assert lines[-1] == "dispatches in the trace: block x3"

    @staticmethod
    def _xspace(planes) -> bytes:
        """A serialized XSpace, hand-encoded (xplane.proto's field numbers):
        ``planes`` = [(name, {stat id: stat name}, [(op name, [stat])])],
        a stat ``(stat id, "str" | "ref", value)``."""

        def varint(n):
            out = bytearray()
            while True:
                out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
                n >>= 7
                if not n:
                    return bytes(out)

        def field(number, value):
            if isinstance(value, int):
                return varint(number << 3) + varint(value)
            value = value.encode() if isinstance(value, str) else value
            return varint(number << 3 | 2) + varint(len(value)) + value

        def entry(key, message):
            return field(1, key) + field(2, message)

        space = b""
        for name, stat_names, ops in planes:
            plane = field(1, 7) + field(2, name)
            plane += field(3, field(2, "XLA Ops"))  # a line: skipped whole
            for sid, sname in stat_names.items():
                plane += field(5, entry(sid, field(1, sid) + field(2, sname)))
            for mid, (op_name, stats) in enumerate(ops, start=1):
                meta = field(1, mid) + field(2, op_name)
                for sid, kind, value in stats:
                    meta += field(5, field(1, sid) + field(
                        5 if kind == "str" else 7, value
                    ))
                plane += field(4, entry(mid, meta))
            space += field(1, plane)
        return space

    def test_an_operations_scope_is_read_from_its_metadata(self):
        from modal_examples_tpu.observability import xplane as X

        stat_names = {
            1: "flops", 2: "tf_op",
            300: "jit(decode)/while/body/mtpu.dense_mlp/dot_general:",
        }
        data = self._xspace([
            ("/device:TPU:0", stat_names, [
                ("%fusion.1 = bf16[8] fusion()", [
                    (1, "ref", 300),  # not the tf_op stat: ignored
                    (2, "str", "jit(decode)/mtpu.attention/mtpu.page_gather/gather:"),
                ]),
                ("%fusion.2 = bf16[8] fusion()", [(2, "ref", 300)]),
                ("%copy.3 = bf16[8] copy()", []),
            ]),
            ("/host:CPU", {2: "tf_op"}, [("python", [(2, "str", "mtpu.x")])]),
        ])
        got = X.op_scopes(data)
        assert got == {"/device:TPU:0": {
            "%fusion.1 = bf16[8] fusion()":
                "jit(decode)/mtpu.attention/mtpu.page_gather/gather:",
            "%fusion.2 = bf16[8] fusion()":
                "jit(decode)/while/body/mtpu.dense_mlp/dot_general:",
        }}
        scopes = got["/device:TPU:0"]
        # the innermost scope names the part; what has none is kept apart
        assert X.scope_of(scopes["%fusion.1 = bf16[8] fusion()"]) == "mtpu.page_gather"
        assert X.scope_of("jit(f)/transpose") == X.UNSCOPED
        rows = X.time_by_scope(
            [("%fusion.1 = bf16[8] fusion()", 0.002),
             ("%fusion.2 = bf16[8] fusion()", 0.005),
             ("%fusion.2 = bf16[8] fusion()", 0.005),
             ("%copy.3 = bf16[8] copy()", 0.001)],
            scopes,
        )
        assert rows == {
            "mtpu.page_gather": {"busy_s": pytest.approx(0.002), "ops": 1},
            "mtpu.dense_mlp": {"busy_s": pytest.approx(0.010), "ops": 2},
            X.UNSCOPED: {"busy_s": pytest.approx(0.001), "ops": 1},
        }

    def test_every_scope_the_program_writes_is_one_the_reader_finds(self):
        from modal_examples_tpu.observability import xplane as X
        from modal_examples_tpu.ops import scopes

        for name in scopes.ALL:
            assert X.scope_of(f"jit(step)/while/body/{name}/dot_general:") == name

    def test_render_ranks_scopes_by_device_time(self):
        from modal_examples_tpu.observability import xplane as X

        report = X.reduce_chips({"/device:TPU:0": [(0.0, 1.0), (1.5, 2.0)]}, [])
        report["scopes"] = {
            "mtpu.attention": {"busy_s": 0.3, "ops": 40},
            "mtpu.page_gather": {"busy_s": 0.9, "ops": 64},
            X.UNSCOPED: {"busy_s": 0.3, "ops": 7},
        }
        lines = X.render(report)
        at = next(i for i, l in enumerate(lines) if l.startswith("DEVICE TIME BY SCOPE"))
        assert [l.split()[0] for l in lines[at + 1 : at + 4]] == [
            "mtpu.page_gather", "mtpu.attention", "(no",
        ]
        assert "60.0%" in lines[at + 1] and lines[at + 1].split()[-1] == "64"

    def test_a_trace_holds_the_tick_and_dispatch_events(
        self, tmp_path, capsys
    ):
        """Under a profiler session the engine's spans are events on the
        host plane of the .xplane.pb, and `tpurun profile --xplane` reads
        the file (here a CPU's: no device plane, so no table)."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from modal_examples_tpu.core.cli import main as cli_main
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=4, max_model_len=128,
            prefill_buckets=(32, 64),
        )
        eng.start()
        try:
            params = SamplingParams(max_tokens=10, temperature=0.0)
            "".join(eng.stream(eng.submit("warm up " * 3, params)))
            jax.profiler.start_trace(str(tmp_path))
            try:
                "".join(eng.stream(eng.submit("the quick fox " * 3, params)))
            finally:
                jax.profiler.stop_trace()
        finally:
            eng.stop()
        [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        names = {
            ev.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events
            if ev.name.startswith("mtpu.")
        }
        assert {
            "mtpu.tick/admit", "mtpu.tick/prefill_dispatch",
            "mtpu.tick/decode_dispatch", "mtpu.tick/harvest",
            "mtpu.tick/accept", "mtpu.dispatch/prefill",
            "mtpu.dispatch/block",
        } <= names, names
        assert "mtpu.tick/detokenize" not in names  # accounted, not traced
        assert cli_main(["profile", "--xplane", str(tmp_path)]) == 0
        assert "no device operations" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the host_overhead alert: now fed on every replica (the profiler is on by
# default), so its threshold has to mean what its description says
# ---------------------------------------------------------------------------


class TestHostOverheadAlert:
    def _ratio(self, harvest_s: float, host_s: float) -> float:
        """What the profiler's gauge reads after ticks of this shape."""
        clk = ManualClock()
        reg = Registry()
        prof = P.HotPathProfiler(clock=clk, registry=reg)
        for _ in range(8):
            tick = prof.begin_tick()
            tick.enter("decode_dispatch")
            clk.advance(host_s)
            tick.enter("harvest", device=True)
            clk.advance(harvest_s)
            prof.end_tick(tick, worked=True)
        prof.flush()
        return reg.value(C.HOST_OVERHEAD_RATIO)

    def _fires(self, ratio: float, tmp_path) -> list[str]:
        from modal_examples_tpu.observability import alerts as al

        [rule] = [r for r in al.DEFAULT_RULES if r.name == "host_overhead"]

        class Src:
            records: list = []

            def recent(self, window_s=None):
                return list(self.records)

        src = Src()
        src.records = []
        ev = al.AlertEvaluator(
            (rule,), source=src, registry=Registry(),
            journal_path=tmp_path / "alerts.jsonl",
        )
        events = []
        for at in (10.0, 25.0, 41.0, 60.0):
            src.records.append({"at": at, "series": [
                [C.HOST_OVERHEAD_RATIO, {}, "gauge", ratio, 0.0],
            ]})
            events += [t["event"] for t in ev.evaluate_once(now=at)]
        return events

    def test_a_device_bound_replica_never_fires(self, tmp_path):
        """The paced cell's shape on the chip: the scheduler thread waits
        on the device for ~96% of a busy tick (PERF.md section 5)."""
        ratio = self._ratio(harvest_s=0.345, host_s=0.012)
        assert ratio == pytest.approx(0.012 / 0.357, rel=1e-3)
        assert self._fires(ratio, tmp_path) == []

    def test_a_host_bound_replica_fires_after_its_hold(self, tmp_path):
        """Ticks that are ~all host work: the device is starved, which is
        what the rule's description says it reports."""
        ratio = self._ratio(harvest_s=0.001, host_s=0.099)
        assert ratio > 0.97
        assert self._fires(ratio, tmp_path) == ["fire"]
