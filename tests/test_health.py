"""Gray-failure watchdog acceptance (ISSUE 13, docs/health.md).

Three layers, matching the subsystem's layering:

- **fake-clock unit matrix** — watermark ages, the pure classifier, the
  hysteresis/flap-damping state machine, the quarantine window, the ladder
  ordering, and the journal+metrics closure, all driven tick-by-tick under
  an injectable clock (the ONLY place detection latency is asserted — no
  wall-clock direction asserts, per the tier-1 timing policy).
- **transfer watermarks** — the seq-watermark registry, stall detection,
  and the watchdog abort surfacing as ``TransportError`` inside a live
  ``transfer()`` held by the injected ``disagg.transfer_stall`` fault.
- **E2E** — a real two-replica fleet where a SILENT scheduler freeze (not
  an error) triggers detection, error-stop, and token-identical stream
  resumption via the PR-12 reactive failover.
"""

import threading
import time

import pytest

from modal_examples_tpu.serving.health import (
    ACTIONS,
    STATES,
    EngineWatermarks,
    FleetWatchdog,
    ReplicaMonitor,
    TransferWatermarks,
    WatchdogPolicy,
    classify,
    progress_age,
    replica_snapshot,
)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


class _FakeSlot:
    def __init__(self, request=None, decodable=False):
        self.request = request
        self.decodable = decodable


class _FakeRequest:
    def __init__(self, rid="req-x", last_token_at=None, generated=()):
        self.request_id = rid
        self.last_token_at = last_token_at
        self.generated_tokens = list(generated)


class _FakePolicy:
    def __init__(self):
        self.oldest = None

    def oldest_enqueued_at(self):
        return self.oldest

    def total_depth(self):
        return 0


class _FakeEngine:
    def __init__(self, clock):
        self.watermarks = EngineWatermarks(clock=clock)
        self._clock = clock
        self._running = True
        self.slots = []
        self.policy = _FakePolicy()
        self._trace_store = None
        self.stopped_with = None

    def stop(self, *, reason="stop"):
        self._running = False
        self.stopped_with = reason


class _FakeReplica:
    def __init__(self, name, clock, outstanding=0):
        self.name = name
        self.engine = _FakeEngine(clock)
        self._outstanding = outstanding
        self.serves_requests = True
        self.health_state = "healthy"
        self.quarantined = False

    def outstanding(self):
        return self._outstanding


class _FakeRouter:
    def __init__(self, replicas):
        self.replicas = replicas
        self.weights = {}

    def set_health_weight(self, name, weight):
        self.weights[name] = weight


def _watchdog(replicas, clock, tmp_path, **policy_kw):
    policy = WatchdogPolicy(**policy_kw) if policy_kw else WatchdogPolicy()
    return FleetWatchdog(
        _FakeRouter(replicas),
        policy=policy,
        clock=clock,
        journal_path=tmp_path / "watchdog.jsonl",
        transfer_watermarks=TransferWatermarks(clock=clock),
    )


class TestWatermarks:
    def test_ages_track_the_injected_clock(self):
        clock = FakeClock()
        wm = EngineWatermarks(clock=clock)
        wm.note_tick()
        wm.note_dispatch()
        clock.advance(2.0)
        wm.note_accept()
        clock.advance(1.0)
        snap = wm.snapshot()
        assert snap["tick_seq"] == 1
        assert snap["tick_age"] == pytest.approx(3.0)
        assert snap["dispatch_age"] == pytest.approx(3.0)
        assert snap["accept_age"] == pytest.approx(1.0)

    def test_unset_watermarks_are_none_not_huge(self):
        wm = EngineWatermarks(clock=FakeClock())
        snap = wm.snapshot()
        assert snap["dispatch_age"] is None
        assert snap["accept_age"] is None

    def test_note_start_resets_stale_ages(self):
        """A restarted engine must not present its previous life's ages:
        in the window between start() and the first tick, with resumed
        work already queued, stale watermarks would read as an instant
        wedge of the engine the watchdog just recovered."""
        clock = FakeClock()
        wm = EngineWatermarks(clock=clock)
        wm.note_tick()
        wm.note_dispatch()
        wm.note_accept()
        clock.advance(30.0)  # the engine was stopped for 30s
        wm.note_start()
        snap = wm.snapshot()
        assert snap["tick_age"] == 0.0
        assert snap["dispatch_age"] is None
        assert snap["accept_age"] is None
        policy = WatchdogPolicy(degraded_after_s=1.0, wedged_after_s=2.0)
        snap.update({"outstanding": 4, "decodable": 0,
                     "queue_head_age": None})
        assert classify(snap, policy) == "healthy"

    def test_engine_restart_resets_watermarks(self, jax_cpu):
        """The engine-level half: stop + start clears the stale ages
        (LLMEngine.start calls note_start)."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(16, 32), page_size=8,
        )
        try:
            eng.generate("restart probe", SamplingParams(max_tokens=2))
            eng.stop()
            time.sleep(0.05)
            eng.start()
            snap = eng.watermarks.snapshot()
            # dispatch/accept reset to None; tick age restarts near zero
            assert snap["dispatch_age"] is None
            assert snap["accept_age"] is None
            assert snap["tick_age"] < 5.0
        finally:
            eng.stop()

    def test_engine_publishes_watermarks_through_real_serving(self, jax_cpu):
        """A real tiny engine's generate() moves every watermark, readable
        ONLY through the health API (replica_snapshot)."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(16, 32), page_size=8,
        )
        rep = EngineReplica(eng, "wm-0")
        try:
            out = eng.generate(
                "watermark probe", SamplingParams(max_tokens=4)
            )
            assert out is not None
            snap = replica_snapshot(rep)
            assert snap["tick_seq"] > 0
            assert snap["dispatch_age"] is not None
            assert snap["accept_age"] is not None
            assert snap["outstanding"] == 0
            # EngineReplica.stats() carries the same last-progress fields
            stats = rep.stats()
            assert stats["state"] == "healthy"
            assert stats["progress"]["tick_seq"] >= snap["tick_seq"]
        finally:
            eng.stop()


class TestClassification:
    def _snap(self, **kw):
        base = {
            "tick_seq": 10, "tick_age": 0.0, "dispatch_age": 0.0,
            "accept_age": 0.0, "outstanding": 1, "decodable": 1,
            "queue_head_age": None,
        }
        base.update(kw)
        return base

    def test_idle_is_always_healthy(self):
        policy = WatchdogPolicy()
        snap = self._snap(outstanding=0, tick_age=1e9)
        assert progress_age(snap) is None
        assert classify(snap, policy) == "healthy"

    def test_stale_tick_escalates_degraded_then_wedged(self):
        policy = WatchdogPolicy(degraded_after_s=2.0, wedged_after_s=10.0)
        assert classify(self._snap(tick_age=1.0), policy) == "healthy"
        assert classify(self._snap(tick_age=2.0), policy) == "degraded"
        assert classify(self._snap(tick_age=10.0), policy) == "wedged"

    def test_accept_and_dispatch_only_count_with_decodable_slots(self):
        policy = WatchdogPolicy(degraded_after_s=2.0, wedged_after_s=10.0)
        # decodable slot starved of accepts: degraded even though ticks flow
        snap = self._snap(tick_age=0.0, accept_age=3.0, dispatch_age=0.1)
        assert classify(snap, policy) == "degraded"
        # no decodable slots (all mid-prefill): accept age is meaningless
        snap = self._snap(
            tick_age=0.0, accept_age=3.0, dispatch_age=3.0, decodable=0
        )
        assert classify(snap, policy) == "healthy"

    @pytest.mark.parametrize("waited, state", [
        (0.2, "healthy"),  # idle 12 s, then a request 0.2 s into decode
        (3.0, "degraded"),  # the slot itself has been kept waiting
        (11.0, "wedged"),
        (None, "wedged"),  # unknown (no token accepted here yet): no bound
    ])
    def test_idle_spell_does_not_age_the_decode_watermarks(self, waited, state):
        """The dispatch and accept watermarks of an engine that sat idle
        are as old as the idle spell; they count against a decodable slot
        only for as long as that slot has waited."""
        policy = WatchdogPolicy(degraded_after_s=2.0, wedged_after_s=10.0)
        snap = self._snap(
            tick_age=0.15, dispatch_age=12.0, accept_age=12.0,
            decodable_wait_age=waited,
        )
        assert classify(snap, policy) == state

    def test_snapshot_reports_the_longest_decodable_wait(self):
        clock = FakeClock()
        rep = _FakeReplica("dw-0", clock, outstanding=3)
        eng = rep.engine
        t0 = clock()
        eng.slots = [
            _FakeSlot(_FakeRequest("a", last_token_at=t0), decodable=True),
            _FakeSlot(_FakeRequest("b", last_token_at=t0 + 1.0), decodable=True),
            _FakeSlot(_FakeRequest("c"), decodable=False),  # mid-prefill
            _FakeSlot(),
        ]
        clock.advance(3.0)
        assert replica_snapshot(rep)["decodable_wait_age"] == pytest.approx(3.0)
        # admitted 4 s ago, its synchronous prefill still running: no
        # token yet, and the slot already counts as decodable
        fresh = _FakeRequest("d")
        fresh.admitted_at = t0 - 1.0
        eng.slots.append(_FakeSlot(fresh, decodable=True))
        assert replica_snapshot(rep)["decodable_wait_age"] == pytest.approx(4.0)
        eng.slots.append(_FakeSlot(_FakeRequest("e"), decodable=True))
        assert replica_snapshot(rep)["decodable_wait_age"] is None

    def test_queue_head_age_is_degraded_only(self):
        policy = WatchdogPolicy(
            degraded_after_s=2.0, wedged_after_s=10.0,
            queue_age_degraded_s=5.0,
        )
        snap = self._snap(queue_head_age=6.0)
        assert classify(snap, policy) == "degraded"
        snap = self._snap(queue_head_age=1e9)
        assert classify(snap, policy) == "degraded"  # never wedged on it

    def test_progress_age_is_the_worst_mandatory_signal(self):
        snap = self._snap(tick_age=0.5, dispatch_age=4.0, accept_age=2.0)
        assert progress_age(snap) == pytest.approx(4.0)


class TestMonitorHysteresis:
    def test_downgrade_is_immediate_upgrade_needs_streak(self):
        policy = WatchdogPolicy(clear_ticks=3)
        mon = ReplicaMonitor("r", policy)
        assert mon.observe("degraded", 0.0) == ("degraded", True)
        # one healthy observation is NOT enough
        assert mon.observe("healthy", 1.0) == ("degraded", False)
        assert mon.observe("healthy", 2.0) == ("degraded", False)
        assert mon.observe("healthy", 3.0) == ("healthy", True)

    def test_flap_damping_holds_degraded(self):
        policy = WatchdogPolicy(clear_ticks=2)
        mon = ReplicaMonitor("r", policy)
        mon.observe("degraded", 0.0)
        # alternating healthy/degraded never builds the streak
        for i in range(6):
            raw = "healthy" if i % 2 == 0 else "degraded"
            state, _ = mon.observe(raw, float(i))
            assert state == "degraded"

    def test_wedged_never_softens_to_degraded(self):
        policy = WatchdogPolicy(clear_ticks=2)
        mon = ReplicaMonitor("r", policy)
        mon.observe("wedged", 0.0)
        state, changed = mon.observe("degraded", 1.0)
        assert (state, changed) == ("wedged", False)

    def test_wedge_window_counts(self):
        policy = WatchdogPolicy(clear_ticks=1, wedge_window_s=100.0)
        mon = ReplicaMonitor("r", policy)
        mon.observe("wedged", 0.0)
        mon.observe("healthy", 1.0)
        mon.observe("wedged", 50.0)
        assert mon.wedges_in_window(60.0) == 2
        assert mon.wedges_in_window(140.0) == 1  # the first aged out


class TestWatchdogLadder:
    def test_degraded_down_weights_and_healthy_restores(self, tmp_path):
        clock = FakeClock()
        rep = _FakeReplica("lad-0", clock, outstanding=1)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=2.0, wedged_after_s=100.0, clear_ticks=2,
            degraded_weight=0.25,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)  # stale tick while busy -> degraded
        wd.poll_once()
        assert rep.health_state == "degraded"
        assert wd.router.weights["lad-0"] == 0.25
        # progress resumes: two healthy polls restore the weight
        rep.engine.watermarks.note_tick()
        rep._outstanding = 0
        wd.poll_once()
        wd.poll_once()
        assert rep.health_state == "healthy"
        assert wd.router.weights["lad-0"] == 1.0
        actions = [e["action"] for e in wd.events]
        assert "down_weight" in actions and "restore_weight" in actions

    def test_wedged_error_stops_the_engine(self, tmp_path):
        clock = FakeClock()
        rep = _FakeReplica("lad-1", clock, outstanding=2)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=1.0, wedged_after_s=5.0, quarantine_after=99,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(6.0)
        wd.poll_once()
        assert rep.engine.stopped_with == "error"
        assert rep.health_state == "wedged"
        assert not rep.quarantined
        actions = [e["action"] for e in wd.events]
        assert actions[-1] == "stop_revive"

    def test_ladder_ordering_degraded_before_wedged(self, tmp_path):
        """A slowly-worsening replica walks the ladder IN ORDER: the
        journal shows down_weight strictly before stop_revive — detection
        latency asserted under the injectable clock only."""
        clock = FakeClock()
        rep = _FakeReplica("lad-2", clock, outstanding=1)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=2.0, wedged_after_s=8.0, quarantine_after=99,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)
        wd.poll_once()  # degraded at age 3
        assert rep.engine.stopped_with is None
        clock.advance(6.0)
        wd.poll_once()  # wedged at age 9
        actions = [e["action"] for e in wd.events]
        assert actions.index("down_weight") < actions.index("stop_revive")
        # detection latency bound, fake clock: wedged within one poll of
        # the threshold crossing (3.0 -> degraded, 9.0 -> wedged)
        transitions = [
            e for e in wd.events if e["action"] == "transition"
        ]
        assert [t["state"] for t in transitions] == ["degraded", "wedged"]

    def test_repeated_wedges_quarantine_and_expire(self, tmp_path):
        clock = FakeClock()
        rep = _FakeReplica("lad-3", clock, outstanding=1)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=1.0, wedged_after_s=2.0, clear_ticks=1,
            quarantine_after=2, wedge_window_s=1000.0, quarantine_s=30.0,
        )
        # first wedge: stop_revive only
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)
        wd.poll_once()
        assert not rep.quarantined
        # the replica revives (router probe analog) and wedges again
        rep.engine._running = True
        rep.engine.stopped_with = None
        rep.engine.watermarks.note_tick()
        wd.poll_once()  # healthy observation clears the wedge state
        assert rep.health_state == "healthy"
        clock.advance(3.0)
        wd.poll_once()
        assert rep.quarantined
        assert rep.engine.stopped_with == "error"
        actions = [e["action"] for e in wd.events]
        assert actions[-1] == "quarantine"
        # while quarantined: no new actions, state gauge says quarantined
        rep.engine._running = True
        wd.poll_once()
        assert rep.quarantined
        # expiry lifts the flag (the router's probe path may then revive)
        clock.advance(31.0)
        wd.poll_once()
        assert not rep.quarantined
        assert [e["action"] for e in wd.events].count("unquarantine") == 1

    def test_journal_and_metrics_closure(self, tmp_path):
        """Every transition journals AND counts; every ladder action
        journals AND counts; the state gauge is one-hot."""
        import json

        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.utils.prometheus import Registry

        reg = Registry()
        clock = FakeClock()
        rep = _FakeReplica("jm-0", clock, outstanding=1)
        wd = FleetWatchdog(
            _FakeRouter([rep]),
            policy=WatchdogPolicy(
                degraded_after_s=1.0, wedged_after_s=4.0, quarantine_after=99
            ),
            clock=clock,
            journal_path=tmp_path / "watchdog.jsonl",
            transfer_watermarks=TransferWatermarks(clock=clock),
            registry=reg,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(2.0)
        wd.poll_once()  # degraded
        clock.advance(3.0)
        wd.poll_once()  # wedged
        lines = [
            json.loads(l)
            for l in (tmp_path / "watchdog.jsonl").read_text().splitlines()
        ]
        journal_actions = [l["action"] for l in lines]
        assert journal_actions.count("transition") == 2
        assert "down_weight" in journal_actions
        assert "stop_revive" in journal_actions
        assert reg.value(
            C.WATCHDOG_TRANSITIONS_TOTAL, labels={"state": "degraded"}
        ) == 1
        assert reg.value(
            C.WATCHDOG_TRANSITIONS_TOTAL, labels={"state": "wedged"}
        ) == 1
        assert reg.value(
            C.WATCHDOG_RECOVERIES_TOTAL, labels={"action": "down_weight"}
        ) == 1
        assert reg.value(
            C.WATCHDOG_RECOVERIES_TOTAL, labels={"action": "stop_revive"}
        ) == 1
        # one-hot state gauge: exactly the wedged cell reads 1
        cells = {
            s: reg.value(
                C.WATCHDOG_REPLICA_STATE,
                labels={"replica": "jm-0", "state": s},
            )
            for s in STATES
        }
        assert cells == {
            "healthy": 0.0, "degraded": 0.0, "wedged": 1.0,
            "quarantined": 0.0,
        }
        assert reg.value(
            C.WATCHDOG_PROGRESS_AGE_SECONDS, labels={"replica": "jm-0"}
        ) >= 5.0
        # every journaled ladder action is a declared ACTIONS member
        for a in journal_actions:
            assert a == "transition" or a in ACTIONS

    def test_rewedge_after_revival_fires_the_ladder_again(self, tmp_path):
        """A revived engine that wedges AGAIN before any healthy streak
        accrues must get a SECOND stop_revive: the monitor resets when the
        engine is observed running after a stop, so the re-wedge is a new
        transition, not a masked continuation of the old one (whose
        streams would otherwise hang forever)."""
        clock = FakeClock()
        rep = _FakeReplica("rw-0", clock, outstanding=1)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=1.0, wedged_after_s=2.0, quarantine_after=99,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)
        wd.poll_once()  # wedge #1: error-stop
        assert rep.engine.stopped_with == "error"
        wd.poll_once()  # observes the stopped engine (saw_stopped)
        # probe revival: the engine runs again but wedges immediately —
        # its tick watermark goes stale before ANY healthy poll lands
        rep.engine._running = True
        rep.engine.stopped_with = None
        clock.advance(3.0)
        wd.poll_once()
        assert rep.engine.stopped_with == "error", (
            "re-wedge after revival was masked: no second stop"
        )
        actions = [e["action"] for e in wd.events]
        assert actions.count("stop_revive") == 2
        # the quarantine window kept BOTH wedges across the revival
        assert wd._monitors["rw-0"].wedges_in_window(clock()) == 2

    def test_stopped_engine_is_not_observed(self, tmp_path):
        """A stopped scheduler belongs to the router's probe cycle: the
        watchdog must not classify it wedged and double-fire the ladder."""
        clock = FakeClock()
        rep = _FakeReplica("st-0", clock, outstanding=1)
        rep.engine._running = False
        wd = _watchdog(
            [rep], clock, tmp_path, degraded_after_s=1.0, wedged_after_s=2.0
        )
        rep.engine.watermarks.note_tick()
        clock.advance(100.0)
        assert wd.poll_once() == []
        assert rep.engine.stopped_with is None

    def test_degraded_weight_restored_after_external_stop(self, tmp_path):
        """A replica down-weighted while DEGRADED whose engine then stops
        through a non-ladder path (strict-mode crash, fleet reap, operator
        restart) must get its placement weight back on revival: reset()
        forces the monitor healthy, so without an explicit restore the next
        healthy observation is changed=False, _act_recovered never fires,
        and the healthy replica competes at degraded_weight forever."""
        clock = FakeClock()
        rep = _FakeReplica("ex-0", clock, outstanding=1)
        wd = _watchdog(
            [rep], clock, tmp_path,
            degraded_after_s=2.0, wedged_after_s=100.0, degraded_weight=0.25,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)
        wd.poll_once()  # degraded -> down-weight
        assert wd.router.weights["ex-0"] == 0.25
        rep.engine.stop(reason="stop")  # NOT the watchdog's doing
        wd.poll_once()  # saw_stopped
        rep.engine._running = True  # probe revival
        rep.engine.watermarks.note_tick()
        rep._outstanding = 0
        wd.poll_once()
        assert wd.router.weights["ex-0"] == 1.0
        assert "restore_weight" in [e["action"] for e in wd.events]

    def test_removed_replica_is_forgotten(self, tmp_path):
        """Fleet scale-down/reap removes a replica from the router: the
        watchdog must drop its monitor, quarantine entry, and gauge cells
        — not report the ghost at its last state on every surface
        forever (and leak ``_quarantined_until`` for good)."""
        from modal_examples_tpu.serving.health import decode_watchdog_series
        from modal_examples_tpu.utils.prometheus import Registry

        reg = Registry()
        clock = FakeClock()
        rep = _FakeReplica("gh-0", clock, outstanding=1)
        wd = FleetWatchdog(
            _FakeRouter([rep]),
            policy=WatchdogPolicy(
                degraded_after_s=1.0, wedged_after_s=2.0,
                quarantine_after=1, quarantine_s=1000.0,
            ),
            clock=clock,
            journal_path=tmp_path / "watchdog.jsonl",
            transfer_watermarks=TransferWatermarks(clock=clock),
            registry=reg,
        )
        rep.engine.watermarks.note_tick()
        clock.advance(3.0)
        wd.poll_once()  # wedged -> immediate quarantine (quarantine_after=1)
        assert rep.quarantined
        assert "gh-0" in wd.stats()["replicas"]
        assert decode_watchdog_series(reg)["states"] == {"gh-0": "quarantined"}
        # the fleet reaps it mid-quarantine
        wd.router.replicas.remove(rep)
        wd.poll_once()
        assert "gh-0" not in wd.stats()["replicas"]
        assert wd._quarantined_until == {}
        assert decode_watchdog_series(reg)["states"] == {}


class TestTransferWatermarks:
    def test_stall_detection_and_abort_cycle(self):
        clock = FakeClock()
        tw = TransferWatermarks(clock=clock)
        tw.begin("t-1")
        tw.progress("t-1", 0)
        clock.advance(1.0)
        assert tw.stalled(5.0) == []
        clock.advance(5.0)
        assert tw.stalled(5.0) == ["t-1"]
        assert tw.request_abort("t-1") is True
        assert tw.request_abort("t-1") is False  # idempotent
        assert tw.abort_requested("t-1")
        assert tw.stalled(5.0) == []  # aborted transfers drop out
        tw.end("t-1")
        assert not tw.abort_requested("t-1")
        assert tw.snapshot() == []

    def test_watchdog_aborts_stalled_transfer_once(self, tmp_path):
        clock = FakeClock()
        tw = TransferWatermarks(clock=clock)
        wd = FleetWatchdog(
            _FakeRouter([]),
            policy=WatchdogPolicy(transfer_stall_s=2.0),
            clock=clock,
            journal_path=tmp_path / "watchdog.jsonl",
            transfer_watermarks=tw,
        )
        tw.begin("t-2")
        clock.advance(3.0)
        first = wd.poll_once()
        assert [a["action"] for a in first] == ["abort_transfer"]
        assert tw.abort_requested("t-2")
        assert wd.poll_once() == []  # armed once, journaled once

    def test_live_transfer_stall_breaks_into_transport_error(self, state_dir):
        """The injected ``disagg.transfer_stall`` holds a REAL transfer()
        between chunks with no error; the watchdog-style abort must
        surface as TransportError (the coordinator's unified-fallback
        trigger), not TransferAborted (the client-abort path)."""
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.serving.disagg.transport import (
            LoopbackChannel,
            TransportError,
            transfer,
        )
        from modal_examples_tpu.serving.health import transfers

        result: dict = {}

        def run():
            try:
                transfer(
                    b"x" * 4096,
                    LoopbackChannel(),
                    transfer_id="t-stall",
                    chunk_bytes=256,
                    backoff=None,
                )
            except Exception as e:  # noqa: BLE001 - recorded for assert
                result["exc"] = e

        plan = FaultPlan({"disagg.transfer_stall": {"on_hit": 1}})
        with active(plan):
            t = threading.Thread(target=run)
            t.start()
            deadline = time.monotonic() + 10
            while (
                time.monotonic() < deadline
                and not plan.fired().get("disagg.transfer_stall")
            ):
                time.sleep(0.005)
            assert plan.fired().get("disagg.transfer_stall") == 1
            # the watchdog's ladder action, driven directly
            assert transfers.request_abort("t-stall")
            t.join(timeout=30)
        assert not t.is_alive(), "stalled transfer never unblocked"
        assert isinstance(result.get("exc"), TransportError)
        assert "watchdog" in str(result["exc"])
        assert transfers.snapshot() == []  # registry drained


class TestRouterDownWeight:
    def _replicas(self, jax_cpu):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import LLMEngine

        cfg = llama.LlamaConfig.tiny()
        eng_a = LLMEngine(
            cfg, seed=0, max_slots=2, max_model_len=64,
            prefill_buckets=(16, 32), page_size=8,
        )
        eng_b = LLMEngine(
            cfg, params=eng_a.params, max_slots=2, max_model_len=64,
            prefill_buckets=(16, 32), page_size=8,
        )
        rep_a = EngineReplica(eng_a, "dw-a")
        rep_b = EngineReplica(eng_b, "dw-b")
        return rep_a, rep_b, PrefixAffinityRouter([rep_a, rep_b])

    def test_degraded_replica_loses_placement(self, jax_cpu):
        rep_a, rep_b, router = self._replicas(jax_cpu)
        try:
            prompt = "shared system prompt for the affinity key"
            preferred = router._preferred(
                router._prompt_key(prompt), router._serving
            )
            other = rep_b if preferred is rep_a else rep_a
            # healthy: affinity wins
            assert router.route(prompt) is preferred
            # degraded: the preferred replica is down-weighted away
            router.set_health_weight(preferred.name, 0.25)
            assert router.health_weight(preferred.name) == 0.25
            assert router.route(prompt) is other
            # restore: affinity returns
            router.set_health_weight(preferred.name, 1.0)
            assert router.route(prompt) is preferred
            # stats carry the graded surface
            stats = router.stats()["replicas"][preferred.name]
            assert stats["weight"] == 1.0
            assert stats["state"] == "healthy"
            assert "progress" in stats
        finally:
            rep_a.engine.stop()
            rep_b.engine.stop()

    def test_quarantined_replica_refuses_probe_and_health(self, jax_cpu):
        rep_a, rep_b, router = self._replicas(jax_cpu)
        try:
            rep_a.quarantined = True
            assert not rep_a.healthy()
            assert not rep_a.probe()
            # placement never lands on it
            for i in range(6):
                assert router.route(f"probe prompt {i}") is rep_b
            rep_a.quarantined = False
            assert rep_a.healthy()
        finally:
            rep_a.engine.stop()
            rep_b.engine.stop()


class TestFleetQuarantineReplacement:
    def test_quarantine_triggers_scale_up(self, tmp_path):
        """A watchdog-quarantined replica is benched capacity: the fleet
        autoscaler must exclude it from the signals AND scale out a
        replacement with trigger="quarantine" (docs/health.md)."""
        from modal_examples_tpu.fleet.autoscaler import FleetAutoscaler

        class _Policy:
            def total_depth(self):
                return 0

        class _Cache:
            def occupancy(self):
                return {"pages_used": 0, "pages_free": 64, "pages_total": 64}

        class _Eng:
            def __init__(self):
                self.policy = _Policy()
                self.cache = _Cache()
                self.prefix_cache = None
                self.admission = type("A", (), {"reserved_pages": 0})()

            def start(self):
                return self

            def stop(self):
                pass

        class _Rep:
            def __init__(self, name):
                self.name = name
                self.role = "unified"
                self.engine = _Eng()
                self.serves_requests = True
                self.quarantined = False

            def outstanding(self):
                return 0

            def capacity(self):
                return 2

            def healthy(self):
                return not self.quarantined

        class _Router:
            def __init__(self, replicas):
                self.replicas = replicas

            def add_replica(self, r):
                self.replicas.append(r)

        built = []

        def factory(name, role):
            r = _Rep(name)
            built.append(name)
            return r, "warm"

        router = _Router([_Rep("seed-0"), _Rep("seed-1")])
        scaler = FleetAutoscaler(
            router,
            factory,
            max_replicas={"decode": 4},
            up_ticks=1,
            cooldown_s=0.0,
            slos=(),
            journal_path=tmp_path / "fleet.jsonl",
        )
        # healthy fleet: no action
        assert scaler.tick() == []
        # the watchdog benches seed-1
        router.replicas[1].quarantined = True
        sig = scaler.signals(consume_sheds=False)["decode"]
        assert sig["quarantined"] == 1
        assert sig["replicas"] == 1  # benched capacity excluded
        actions = scaler.tick()
        assert [a["trigger"] for a in actions] == ["quarantine"]
        assert built, "no replacement replica was built"
        # the trigger is per-BENCHING, not per-tick: the benched replica is
        # compensated exactly once — a 30s quarantine window must not buy a
        # fresh build every cooldown expiry
        assert scaler.tick() == []
        assert scaler.tick() == []
        assert len(built) == 1
        # quarantine lifts (handled set prunes), the SAME replica is
        # benched again later: a new edge, a new replacement
        router.replicas[1].quarantined = False
        assert scaler.tick() == []
        router.replicas[1].quarantined = True
        actions = scaler.tick()
        assert [a["trigger"] for a in actions] == ["quarantine"]
        assert len(built) == 2


class TestHangFailoverE2E:
    def test_silent_freeze_resumes_streams_token_identical(self, jax_cpu):
        """The acceptance E2E (docs/health.md): a HANG — not an error —
        on the replica holding live streams. The watchdog classifies it
        wedged from stale watermarks, error-stops it, and the PR-12
        reactive failover resumes every stream on the peer with the exact
        fault-free token sequence. Recovery is asserted to HAPPEN (bounded
        by the drain timeout), never how fast — wall-clock latency lives
        in the fake-clock matrix and the benchdiff-gated `recovery`
        section."""
        from modal_examples_tpu.faults.chaos import (
            check_drained,
            check_router_recovered,
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
            reference = {p: ref_engine.generate(p, sp) for p in prompts}
        finally:
            ref_engine.stop()

        eng_a = engine()
        eng_b = engine(params=eng_a.params)
        # warm the STANDBY's own jits before any watchdog runs: its
        # first-ever compile otherwise happens at takeover, where the
        # trace stall reads as a wedge of the engine the failover is
        # recovering onto (the watchdog-vs-compile rule, docs/health.md)
        eng_b.generate(prompts[0], sp)
        eng_b.stop()
        rep_a = EngineReplica(eng_a, "hang-a", role="unified")
        rep_b = EngineReplica(eng_b, "hang-b", role="unified")
        router = PrefixAffinityRouter([rep_a, rep_b], reprobe_s=0.2)
        watchdog = FleetWatchdog(
            router,
            policy=WatchdogPolicy(
                degraded_after_s=1.0, wedged_after_s=2.0, quarantine_after=99
            ),
            poll_s=0.1,
        )
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
            # engines warm, streams mid-decode: NOW the watchdog starts
            # (first-compile stalls must never read as a wedge) and the
            # ONLY running loop silently freezes — no exception, no
            # crash, healthy() still true
            watchdog.start()
            plan = FaultPlan(
                {"engine.scheduler_freeze": {"p": 1.0, "max_fires": 1}}
            )
            with active(plan):
                deadline = time.monotonic() + 30
                while not plan.fired() and time.monotonic() < deadline:
                    time.sleep(0.005)
                assert plan.fired().get("engine.scheduler_freeze") == 1
                for t in threads:
                    t.join(timeout=120)
                    assert not t.is_alive(), "stream wedged after the hang"
            for req in reqs:
                # zero client-visible errors + the fault-free sequence
                assert req.finish_reason in ("stop", "length"), req.request_id
                assert "".join(outs[req.request_id]) == reference[req.prompt]
            # the ladder ran: wedge detected, error-stop taken
            actions = [e["action"] for e in watchdog.events]
            assert "stop_revive" in actions, watchdog.events
            # the stitched timelines show the watchdog seam on at least
            # one affected request (the `watchdog` span event)
            from modal_examples_tpu.observability import reqtrace as rt

            seen_watchdog_event = False
            for req in reqs:
                for s in rt.read_trace(req.request_id):
                    if s["name"] == "watchdog":
                        seen_watchdog_event = True
            assert seen_watchdog_event
            # PR-8 fleet invariants + the router revival leg: a placement
            # after reprobe_s probes, revives, and restarts the victim
            time.sleep(router.reprobe_s + 0.2)
            assert router.route(prompts[0]) is not None
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and (
                check_router_recovered(router)
                or check_drained({"hang-a": eng_a, "hang-b": eng_b})
            ):
                time.sleep(0.1)
                router.route(prompts[0])
            assert check_drained({"hang-a": eng_a, "hang-b": eng_b}) == []
            assert check_router_recovered(router) == []
        finally:
            watchdog.stop()
            eng_a.stop()
            eng_b.stop()
