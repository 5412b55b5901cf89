"""Chaos acceptance (ISSUE 8, docs/faults.md): ONE seeded command drives
the default episode schedule through EVERY cataloged fault point against a
real mixed fleet (unified + disagg prefill/decode, CPU-sized models) and
all fleet invariants hold — zero wedged requests, reservations and pages
drained to zero, request conservation, router recovered, and fault-free
outputs token-identical. The run itself raises ChaosInvariantError on any
violation, so the fixture IS the acceptance; the tests below pin each
contract clause to a named assertion."""

import json
import time

import pytest


@pytest.fixture(scope="module")
def chaos_report(jax_cpu):
    from modal_examples_tpu.faults.chaos import run_chaos

    # strict=False: a violated invariant is in the report, and fails the tests
    # that read it (test_all_invariants_hold_after_every_episode names the
    # episode), not every test of the module
    return run_chaos(seed=0, strict=False)


class TestChaosAcceptance:
    def test_every_cataloged_fault_point_fires(self, chaos_report):
        """Catalog reachability: the default seeded schedule reaches AND
        fires every declared FaultPoint — a dead injection point (wired
        out by a refactor, never exercised) fails here, not in prod."""
        from modal_examples_tpu.faults import ALL_FAULT_POINTS

        assert chaos_report["points_missed"] == []
        assert set(chaos_report["points_fired"]) == set(ALL_FAULT_POINTS)
        assert chaos_report["injected_total"] >= len(ALL_FAULT_POINTS)

    def test_zero_wedged_requests(self, chaos_report):
        assert chaos_report["wedged"] == 0

    def test_all_invariants_hold_after_every_episode(self, chaos_report):
        assert chaos_report["invariants"] == "ok"
        for ep in chaos_report["episodes"]:
            assert ep["invariants"] == "ok", ep

    def test_request_conservation_per_episode(self, chaos_report):
        """admitted == finished + shed, per episode: nothing vanishes —
        aborted and deadline-expired requests still FINISH."""
        for ep in chaos_report["episodes"]:
            finished = sum(ep["finished"].values())
            assert finished + ep["shed"] > 0, ep
            assert ep["wedged"] == 0, ep

    def test_faults_recovered_not_just_survived(self, chaos_report):
        """Most injected faults must end in RECOVERY (requests finishing
        normally despite the fault), not merely honest failure."""
        assert chaos_report["recovered"] >= len(chaos_report["episodes"])

    def test_router_readmission_happened(self, chaos_report):
        """The flap episode must exercise the re-probe re-admission path
        (the PR's one-way-door bugfix), observable in the metric."""
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.utils.prometheus import default_registry

        assert default_registry.total(C.ROUTER_READMISSIONS_TOTAL) >= 1

    def test_injected_metric_covers_every_point(self, chaos_report):
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.utils.prometheus import default_registry

        counted = {
            labels.get("point"): v
            for labels, v in default_registry.series(C.FAULTS_INJECTED_TOTAL)
        }
        for point, n in chaos_report["injected"].items():
            assert counted.get(point, 0) >= n, (point, counted)

    def test_episode_journal_written(self, chaos_report, state_dir):
        """Every episode appends one JSON record to <state_dir>/chaos.jsonl
        — the `tpurun chaos` / gateway `/chaos` data source."""
        path = state_dir / "chaos.jsonl"
        assert path.exists()
        records = [
            json.loads(line)
            for line in path.read_text().splitlines()
            if line.strip()
        ]
        episodes = {r.get("episode") for r in records}
        for ep in chaos_report["episodes"]:
            assert ep["episode"] in episodes
        for rec in records:
            assert "injected" in rec and "invariants" in rec

    def test_chaos_cli_renders_the_journal(self, chaos_report, capsys):
        """`tpurun chaos` renders the last episodes without error."""
        from modal_examples_tpu.core.cli import main

        assert main(["chaos", "--last", "20"]) == 0
        out = capsys.readouterr().out
        assert "FAULT POINT" in out or "EPISODE" in out
        assert "VIOLATED" not in out

    def test_gateway_chaos_snapshot_shape(self, chaos_report):
        from modal_examples_tpu.web.gateway import _chaos_snapshot

        snap = _chaos_snapshot()
        assert snap["injected_total"] >= chaos_report["injected_total"]
        assert snap["episodes"], "journal episodes must surface"
        assert snap["wedged"] == 0


class TestChaosUnderLoad:
    """ISSUE 11: chaos driven CONCURRENTLY with the open-loop load
    generator — self-healing measured, not just asserted. A two-replica
    fleet serves a fixed offered load for a fault-free baseline window and
    again with a seeded fault episode armed (health flap, decode stall,
    page pressure); the PR-8 fleet invariants must hold afterwards AND the
    goodput dip during the fault window must be bounded: recovery is a
    throughput statement, not a liveness one (docs/fleet.md)."""

    def test_goodput_dip_under_faults_is_bounded(
        self, jax_cpu, state_dir, monkeypatch
    ):
        monkeypatch.setenv("MTPU_TRACE_SAMPLE", "0")
        from modal_examples_tpu.faults.chaos import (
            settle_drained,
            settle_recovered,
        )
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.fleet.loadgen import LoadGenerator, RequestClass
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import LLMEngine
        from modal_examples_tpu.serving.openai_api import OpenAIServer

        cfg = llama.LlamaConfig.tiny()
        eng_a = LLMEngine(
            cfg, seed=0, max_slots=2, max_model_len=384, page_size=16,
            prefill_buckets=(64, 128),
        )
        # second replica shares the weight buffers: one init, two engines
        eng_b = LLMEngine(
            cfg, params=eng_a.params, max_slots=2, max_model_len=384,
            page_size=16, prefill_buckets=(64, 128),
        )
        router = PrefixAffinityRouter(
            [
                EngineReplica(eng_a, "uni-a", role="unified"),
                EngineReplica(eng_b, "uni-b", role="unified"),
            ],
            reprobe_s=0.2,
        )
        server = OpenAIServer(router=router, host="127.0.0.1", port=0)
        server.start()
        try:
            classes = (
                RequestClass(
                    "interactive", "interactive", 0.7, (1, 2), 16, 5.0, 1.0
                ),
                RequestClass(
                    "batch", "batch", 0.3, (2, 3), 16, 30.0, 2.0,
                    stream=False,
                ),
            )
            lg = LoadGenerator(
                f"http://127.0.0.1:{server.port}", classes=classes, seed=3,
                request_timeout_s=60.0,
            )
            lg.warm(n_per_class=1)
            capacity = lg.calibrate(duration_s=1.5)
            rate = 0.6 * capacity  # comfortable: the dip isolates the faults
            baseline = lg.run_step(rate, 4.0, label="baseline")
            plan = FaultPlan(
                {
                    "router.health_flap": {"on_hit": 2},
                    "engine.slow_decode": {"on_hit": 3},
                    "engine.out_of_pages": {"on_hit": 4},
                },
                seed=3,
            )
            with active(plan):
                faulted = lg.run_step(rate, 4.0, label="faulted")
            recovered = lg.run_step(rate, 2.0, label="recovered")

            fired = plan.fired()
            assert fired, "the episode never injected anything"
            assert fired.get("router.health_flap"), fired
            # liveness: nothing wedges or errors in ANY window
            for step in (baseline, faulted, recovered):
                assert step["wedged"] == 0, step
                assert step["errors"] == 0, step
            # fleet invariants (PR 8) after the fault window drained
            assert settle_drained({"uni-a": eng_a, "uni-b": eng_b}) == []
            assert settle_recovered(router) == []
            # the measured self-healing clause: the fault window still
            # delivered a bounded fraction of fault-free goodput
            assert baseline["goodput_rps"] > 0
            assert faulted["goodput_rps"] >= 0.25 * baseline["goodput_rps"], (
                baseline, faulted,
            )
        finally:
            server.stop()


class TestDecodeReplicaDeathMidStream:
    """ISSUE 12: kill a decode replica mid-stream — idle fleet AND under
    the PR-11 loadgen — and assert the PR-8 invariants plus the new one:
    every affected stream finishes with its fault-free token sequence,
    zero client-visible errors, zero wedges (docs/failover.md)."""

    def test_idle_fleet_streams_survive_death_token_identical(self, jax_cpu):
        import threading

        from modal_examples_tpu.faults.chaos import (
            settle_drained,
            settle_recovered,
        )
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig.tiny()

        def engine(**kw):
            return LLMEngine(
                cfg, seed=0, max_slots=4, max_model_len=128, page_size=8,
                prefill_buckets=(16, 32), **kw,
            )

        sp = SamplingParams(max_tokens=48, temperature=0.0)
        prompts = [
            "the quick brown fox jumps over the lazy dog",
            "the quick brown fox naps in the warm sun",
            "a completely different prompt about thundering herds",
        ]
        ref_engine = engine()
        try:
            reference = {
                p: ref_engine.generate(p, sp) for p in prompts
            }
        finally:
            ref_engine.stop()

        eng_a = engine()
        eng_b = engine(params=eng_a.params)
        rep_a = EngineReplica(eng_a, "death-a", role="unified")
        rep_b = EngineReplica(eng_b, "death-b", role="unified")
        router = PrefixAffinityRouter([rep_a, rep_b], reprobe_s=0.2)
        try:
            eng_a.start()  # the victim; B boots lazily at takeover
            reqs, outs, threads = [], {}, []
            for p in prompts:
                req = rep_a.submit(p, sp)  # all streams on the victim
                req._router_replica = rep_a
                reqs.append(req)
                outs[req.request_id] = pieces = []

                t = threading.Thread(
                    target=lambda r=req, buf=pieces: buf.extend(
                        router.stream(r)
                    )
                )
                t.start()
                threads.append(t)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not all(
                len(r.generated_tokens) >= 3 for r in reqs
            ):
                time.sleep(0.005)
            # ONLY the victim's loop is running: the injected crash lands
            # on it deterministically, releasing every stream with "error"
            plan = FaultPlan({"engine.scheduler_crash": {"on_hit": 1}})
            with active(plan):
                deadline = time.monotonic() + 30
                while not plan.fired() and time.monotonic() < deadline:
                    time.sleep(0.005)
            assert plan.fired().get("engine.scheduler_crash") == 1
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "stream wedged after replica death"
            for req in reqs:
                # zero client-visible errors + the fault-free sequence
                assert req.finish_reason in ("stop", "length"), req.request_id
                assert "".join(outs[req.request_id]) == reference[req.prompt]
            # PR-8 fleet invariants after the episode
            assert settle_drained({"death-a": eng_a, "death-b": eng_b}) == []
            assert settle_recovered(router) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_streams_survive_death_under_load(
        self, jax_cpu, state_dir, monkeypatch
    ):
        """The same death under the PR-11 open-loop load generator: the
        SSE clients observe zero errors and zero wedges through the crash
        window — failover is measured under production-shaped traffic,
        not just asserted on a quiet fleet."""
        monkeypatch.setenv("MTPU_TRACE_SAMPLE", "0")
        from modal_examples_tpu.faults.chaos import (
            settle_drained,
            settle_recovered,
        )
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.fleet.loadgen import LoadGenerator, RequestClass
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import LLMEngine
        from modal_examples_tpu.serving.openai_api import OpenAIServer

        cfg = llama.LlamaConfig.tiny()
        eng_a = LLMEngine(
            cfg, seed=0, max_slots=2, max_model_len=384, page_size=16,
            prefill_buckets=(64, 128),
        )
        eng_b = LLMEngine(
            cfg, params=eng_a.params, max_slots=2, max_model_len=384,
            page_size=16, prefill_buckets=(64, 128),
        )
        router = PrefixAffinityRouter(
            [
                EngineReplica(eng_a, "dload-a", role="unified"),
                EngineReplica(eng_b, "dload-b", role="unified"),
            ],
            reprobe_s=0.2,
        )
        server = OpenAIServer(router=router, host="127.0.0.1", port=0)
        server.start()
        try:
            classes = (
                RequestClass(
                    "interactive", "interactive", 1.0, (1, 2), 16, 5.0, 1.0
                ),
            )
            lg = LoadGenerator(
                f"http://127.0.0.1:{server.port}", classes=classes, seed=5,
                request_timeout_s=60.0,
            )
            lg.warm(n_per_class=1)
            capacity = lg.calibrate(duration_s=1.5)
            rate = 0.5 * capacity
            # a decode replica dies mid-window: several in-flight SSE
            # streams fail over to the surviving one
            plan = FaultPlan({"engine.scheduler_crash": {"on_hit": 20}})
            with active(plan):
                faulted = lg.run_step(rate, 5.0, label="death")
            assert plan.fired().get("engine.scheduler_crash"), plan.hits()
            # the new invariant: the crash is CLIENT-INVISIBLE — no SSE
            # error events, no wedged streams, and the fleet drained
            assert faulted["wedged"] == 0, faulted
            assert faulted["errors"] == 0, faulted
            assert faulted["goodput_rps"] > 0
            assert settle_drained({"dload-a": eng_a, "dload-b": eng_b}) == []
            assert settle_recovered(router) == []
        finally:
            server.stop()


class TestSilentHangUnderLoad:
    """ISSUE 13 (docs/health.md): a SILENT scheduler freeze — no crash, no
    error, ``healthy()`` stays true — under the PR-11 open-loop load
    generator. The progress watchdog must detect the wedge from stale
    watermarks, error-stop the replica so every live SSE stream takes the
    PR-12 reactive failover, and the fleet must drain with zero wedges and
    zero client-visible errors. (The idle-fleet token-identity half lives
    in tests/test_health.py; detection-latency numbers live in the
    fake-clock unit matrix — no wall-clock direction asserts here.)"""

    def test_freeze_under_load_recovers(self, jax_cpu, state_dir, monkeypatch):
        monkeypatch.setenv("MTPU_TRACE_SAMPLE", "0")
        from modal_examples_tpu.faults.chaos import (
            settle_drained,
            settle_recovered,
        )
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.fleet.loadgen import LoadGenerator, RequestClass
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import LLMEngine
        from modal_examples_tpu.serving.health import (
            FleetWatchdog,
            WatchdogPolicy,
        )
        from modal_examples_tpu.serving.openai_api import OpenAIServer

        cfg = llama.LlamaConfig.tiny()
        eng_a = LLMEngine(
            cfg, seed=0, max_slots=2, max_model_len=384, page_size=16,
            prefill_buckets=(64, 128),
        )
        eng_b = LLMEngine(
            cfg, params=eng_a.params, max_slots=2, max_model_len=384,
            page_size=16, prefill_buckets=(64, 128),
        )
        router = PrefixAffinityRouter(
            [
                EngineReplica(eng_a, "hang-a", role="unified"),
                EngineReplica(eng_b, "hang-b", role="unified"),
            ],
            reprobe_s=0.2,
        )
        server = OpenAIServer(router=router, host="127.0.0.1", port=0)
        server.start()
        watchdog = None
        try:
            classes = (
                RequestClass(
                    "interactive", "interactive", 1.0, (1, 2), 16, 5.0, 1.0
                ),
            )
            lg = LoadGenerator(
                f"http://127.0.0.1:{server.port}", classes=classes, seed=7,
                request_timeout_s=60.0,
            )
            lg.warm(n_per_class=1)
            capacity = lg.calibrate(duration_s=1.5)
            rate = 0.5 * capacity
            # the watchdog starts AFTER warm/calibrate — and after BOTH
            # engines compiled their own jits (a takeover onto a cold
            # standby would otherwise stall in its first trace and read
            # as a wedge — the watchdog-vs-compile rule, docs/health.md)
            from modal_examples_tpu.serving import SamplingParams

            for eng in (eng_a, eng_b):
                eng.generate(
                    "watchdog warm probe", SamplingParams(max_tokens=4)
                )
            watchdog = FleetWatchdog(
                router,
                policy=WatchdogPolicy(
                    degraded_after_s=1.0, wedged_after_s=2.0,
                    quarantine_after=99,
                ),
                poll_s=0.1,
            ).start()
            # one loop silently freezes mid-window; its in-flight SSE
            # streams must fail over with the crash invisible to clients
            plan = FaultPlan(
                {"engine.scheduler_freeze": {"p": 1.0, "max_fires": 1}}
            )
            with active(plan):
                faulted = lg.run_step(rate, 6.0, label="freeze")
            assert plan.fired().get("engine.scheduler_freeze") == 1
            recovered = lg.run_step(rate, 2.0, label="recovered")
            for step in (faulted, recovered):
                assert step["wedged"] == 0, step
                assert step["errors"] == 0, step
            assert faulted["goodput_rps"] > 0
            # the ladder actually ran: a wedge transition + an error-stop
            acted = {e["action"] for e in watchdog.events}
            assert "stop_revive" in acted, watchdog.events
            assert settle_drained({"hang-a": eng_a, "hang-b": eng_b}) == []
            assert settle_recovered(router) == []
        finally:
            if watchdog is not None:
                watchdog.stop()
            server.stop()


class TestTraceUnderChaos:
    def test_chaos_requests_carry_fault_events(self, chaos_report):
        """Acceptance: a chaos episode's injected faults appear as span
        EVENTS on the affected requests' distributed traces — the fleet
        timeline shows per-request what was injected, not just a
        counter."""
        from modal_examples_tpu.observability.trace import default_store

        points_seen = set()
        for tid in default_store.list_traces(limit=2000):
            if not tid.startswith("req-"):
                continue
            for s in default_store.read(tid):
                if s["name"] == "fault":
                    points_seen.add(s["attrs"].get("point"))
        assert points_seen, (
            "no request trace recorded a fault event during the chaos run"
        )


    """ISSUE 9: trace-context propagation under failure — an injected
    scheduler-thread crash must still close every open span of every
    in-flight traced request (no dangling span leak), mark the crash as a
    ``fault`` event on each, and finish the roots with the same honest
    finish_reason="error" the stream reports."""

    def test_scheduler_crash_closes_all_spans_and_marks_fault(self, jax_cpu):
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.observability import reqtrace as rt
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(16, 32), page_size=4,
        )
        try:
            # crash a few ticks in: the request is mid-decode, its queue
            # span closed and its decode span OPEN when the crash lands
            plan = FaultPlan({"engine.scheduler_crash": {"on_hit": 4}})
            with active(plan):
                req = eng.submit(
                    "crash victim", SamplingParams(max_tokens=64)
                )
                out = "".join(eng.stream(req))
            assert req.finish_reason == "error"
            assert plan.fired().get("engine.scheduler_crash") == 1
            assert req.trace is not None
            assert req.trace.open_spans() == [], "dangling span leaked"
            spans = rt.read_trace(req.request_id)
            assert all(s["end"] is not None for s in spans)
            by = {}
            for s in spans:
                by.setdefault(s["name"], []).append(s)
            root = by["request"][0]
            assert root["attrs"]["finish_reason"] == "error"
            faults = by.get("fault", [])
            assert faults and faults[0]["attrs"]["point"] == (
                "engine.scheduler_crash"
            )
            # the decode span was open at the crash: swept closed with the
            # terminal status, not abandoned
            if "decode" in by:
                assert by["decode"][0]["status"] == "error"
            del out  # partial output is fine; the contract is closure
        finally:
            eng.stop()
