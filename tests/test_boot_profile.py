"""Set-up timed from inside (observability/profiler.py, core/executor.py,
docs/observability.md): the container's boot as phase spans on one clock,
the supervisor's ``boot`` span recorded when the container reports ready
(a server replica gets no input), and a program build split into trace,
lowering, XLA's compile and a cache read by JAX's own monitoring events."""

import ast
import json
import socket
import threading
from pathlib import Path

import pytest

import modal_examples_tpu as mtpu
from modal_examples_tpu.observability import catalog as C
from modal_examples_tpu.observability import profiler as P
from modal_examples_tpu.observability.trace import default_store
from modal_examples_tpu.utils.prometheus import Registry, default_registry

PKG_ROOT = Path(__file__).resolve().parents[1] / "modal_examples_tpu"


class ManualClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def advance(self, dt: float) -> None:
        self.t += dt

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# BootProfile: phases partition the boot, marks nest
# ---------------------------------------------------------------------------


class TestBootProfile:
    def _boot(self):
        """spawn 2.0 | attach 3.0 | enter 1.0 | restore 0.5 | enter 6.0, with
        engine_init 4.0 (kv_alloc 1.5 inside it) and server_start 0.25."""
        clk = ManualClock()
        boot = P.BootProfile(spawned=98.0, clock=clk)  # the Popen, 2 s ago
        boot.enter("attach")
        clk.advance(3.0)
        boot.enter("enter")
        clk.advance(1.0)
        boot.enter("restore")
        clk.advance(0.5)
        boot.enter("enter")
        clk.advance(0.75)
        with boot.mark("engine_init"):
            clk.advance(1.0)
            with boot.mark("kv_alloc"):
                clk.advance(1.5)
            clk.advance(1.5)
        with boot.mark("server_start"):
            clk.advance(0.25)
        clk.advance(1.0)
        return clk, boot

    def test_top_level_phases_partition_the_boot(self):
        clk, boot = self._boot()
        reg = Registry()
        info = boot.finish(registry=reg)
        assert info["phases"] == pytest.approx(
            {"spawn": 2.0, "attach": 3.0, "restore": 0.5, "enter": 7.0}
        )
        assert set(info["phases"]) <= set(C.BOOT_PHASES)
        assert sum(info["phases"].values()) == pytest.approx(
            info["ready"] - info["spawned"]
        )
        assert info["spawned"] == 98.0 and info["ready"] == clk.t

    def test_nested_marks_do_not_break_the_sum(self):
        _clk, boot = self._boot()
        info = boot.finish(registry=Registry())
        assert info["marks"] == pytest.approx(
            {"engine_init": 4.0, "kv_alloc": 1.5, "server_start": 0.25}
        )
        assert set(info["marks"]) <= set(C.BOOT_MARKS)
        # the marks lie inside ``enter`` and add nothing to the partition
        assert info["phases"]["enter"] == pytest.approx(7.0)
        assert sum(info["phases"].values()) == pytest.approx(12.5)
        # a span names what it opened in: a phase nothing, a mark the mark
        # or the phase around it
        parents = {(name, parent) for name, _s, _e, parent in info["spans"]}
        assert parents == {
            ("spawn", None), ("attach", None), ("restore", None), ("enter", None),
            ("engine_init", "enter"), ("kv_alloc", "engine_init"), ("server_start", "enter"),
        }

    def test_finish_writes_the_gauges_once(self):
        _clk, boot = self._boot()
        reg = Registry()
        boot.finish(registry=reg)
        for phase, want in (("spawn", 2.0), ("attach", 3.0), ("restore", 0.5),
                            ("enter", 7.0), ("engine_init", 4.0), ("kv_alloc", 1.5)):
            assert reg.value(C.BOOT_PHASE_SECONDS, labels={"phase": phase}) \
                == pytest.approx(want)
        assert reg.value(C.BOOT_MARK_SECONDS, labels={"mark": "spawned"}) == 98.0
        assert reg.value(C.BOOT_MARK_SECONDS, labels={"mark": "ready"}) == 110.5

    def test_no_boot_under_way_marks_nothing(self, monkeypatch):
        monkeypatch.setattr(P, "_boot", None)
        P.boot_enter("enter")  # the inline backend, a test process
        with P.boot_mark("engine_init"):
            pass
        assert P.finish_boot(registry=Registry()) == {}
        # and a boot that is over is closed to late marks
        clk = ManualClock()
        boot = P.begin_boot(spawned=99.0, clock=clk)
        with P.boot_mark("engine_init"):
            clk.advance(1.0)
        info = P.finish_boot(registry=Registry())
        assert info["marks"] == {"engine_init": 1.0} and P._boot is None
        with P.boot_mark("engine_init"):
            clk.advance(5.0)
        assert boot.marks == {"engine_init": 1.0}


# ---------------------------------------------------------------------------
# the supervisor's boot span: at ready, not at the first input
# ---------------------------------------------------------------------------

app = mtpu.App("boot-profile-tests")


@app.function()
def doubled(x: int) -> int:
    return 2 * x


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


PORT = _free_port()


@app.server(port=PORT, startup_timeout=120)
class Replica:
    @mtpu.enter()
    def start(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Ok(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                self.send_response(200)
                self.send_header("content-length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

        self.httpd = ThreadingHTTPServer(("127.0.0.1", PORT), Ok)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()


@pytest.fixture(scope="module")
def running():
    with app.run():
        yield


def _boots(tag: str) -> float:
    q = default_registry.histogram_quantiles(
        C.CALL_DURATION_SECONDS, labels={"function": tag, "phase": "boot"}
    )
    return (q or {}).get("count", 0)


def _boot_traces(tag: str) -> list[list[dict]]:
    out = []
    for tid in default_store.list_traces(limit=500):
        if tid.startswith("boot-"):
            spans = default_store.read(tid)
            if any(s["name"] == "boot" and s["attrs"].get("function") == tag
                   for s in spans):
                out.append(spans)
    return out


class TestSupervisorBootSpan:
    def test_a_server_replica_that_never_gets_an_input_records_its_boot_once(
        self, running
    ):
        """The regression for the repair: ``@app.server`` replicas are booted
        by the autoscaler and no input is ever dispatched to them, so a span
        emitted at the first dispatch never fired."""
        tag = Replica._cls._spec.tag
        before = _boots(tag)
        Replica.serve()
        try:
            assert _boots(tag) == before + 1
            traces = _boot_traces(tag)
            assert len(traces) == 1
            by_name = {s["name"]: s for s in traces[0]}
            root = by_name["boot"]
            assert root["parent_id"] is None and root["attrs"]["mode"] == "cold"
            # the container's phases, as child spans inside the boot's window
            assert {"spawn", "enter"} <= set(by_name) <= {"boot", *C.BOOT_PHASES, *C.BOOT_MARKS}
            for name in ("spawn", "enter"):
                child = by_name[name]
                assert child["parent_id"] == root["span_id"]
                assert root["start"] - 1e-3 <= child["start"] <= child["end"] <= root["end"] + 0.05
            assert by_name["spawn"]["end"] == pytest.approx(by_name["enter"]["start"], abs=1e-6)
            # the ready message carried them
            (container,) = Replica._obj._pool().containers
            phases = container.boot_info["phases"]
            assert set(phases["phases"]) == {"spawn", "enter"}
            assert sum(phases["phases"].values()) == pytest.approx(
                phases["ready"] - phases["spawned"]
            )
            assert phases["spawned"] == container.boot_spawned_at
        finally:
            Replica.stop()
        assert _boots(tag) == before + 1  # once: nothing observed it again

    def test_a_functions_first_input_still_links_to_the_boot(self, running):
        tag = doubled.spec.tag
        before = _boots(tag)
        first = doubled.spawn(4)
        assert first.get(timeout=60) == 8
        second = doubled.spawn(5)
        assert second.get(timeout=60) == 10
        assert _boots(tag) == before + 1  # observed at ready, not again at dispatch
        cold = [s for s in default_store.read(first.call_id) if s["name"] == "boot"]
        warm = [s for s in default_store.read(second.call_id) if s["name"] == "boot"]
        assert [s["attrs"]["mode"] for s in cold] == ["cold"]
        assert [s["attrs"]["mode"] for s in warm] == ["warm"]
        assert cold[0]["end"] > cold[0]["start"]  # the boot's window, as before
        linked = default_store.read(cold[0]["attrs"]["boot_trace"])
        root = next(s for s in linked if s["name"] == "boot")
        assert root["attrs"]["function"] == tag
        assert (root["start"], root["end"]) == (cold[0]["start"], cold[0]["end"])
        assert {s["name"] for s in linked} >= {"boot", "spawn", "enter"}


    def test_a_nested_mark_hangs_under_what_it_opened_in(self):
        """The container names each span's parent when it opens; the
        supervisor only places them on the wall clock. ``enter`` entered
        twice (a snapshot restore between) keeps each mark under its own."""
        from types import SimpleNamespace

        from modal_examples_tpu.core.executor import _Container

        clk = ManualClock()
        boot = P.BootProfile(spawned=100.0, clock=clk)
        boot.enter("enter")
        clk.advance(1.0)
        boot.enter("restore")
        clk.advance(0.5)
        boot.enter("enter")
        with boot.mark("engine_init"):
            clk.advance(1.0)
            with boot.mark("kv_alloc"):
                clk.advance(0.5)
        with boot.mark("server_start"):
            clk.advance(0.25)
        tag = "boot-profile-tests.stub"
        stub = SimpleNamespace(
            pool=SimpleNamespace(spec=SimpleNamespace(tag=tag)), idx=0,
            boot_info={"phases": boot.finish(registry=Registry())},
            boot_wall_start=5000.0, boot_spawned_at=100.0, ready_wall=5003.25,
            boot_trace_id=None,
        )
        _Container._record_boot(stub)
        spans = default_store.read(stub.boot_trace_id)
        by_id = {s["span_id"]: s for s in spans}
        parent = {
            (s["name"], round(s["start"], 2)): by_id[s["parent_id"]]
            for s in spans if s["parent_id"]
        }
        assert {k: v["name"] for k, v in parent.items()} == {
            ("spawn", 5000.0): "boot", ("enter", 5000.0): "boot", ("restore", 5001.0): "boot",
            ("enter", 5001.5): "boot", ("engine_init", 5001.5): "enter",
            ("kv_alloc", 5002.5): "engine_init", ("server_start", 5003.0): "enter",
        }
        # the second ``enter``, not the first
        assert parent[("engine_init", 5001.5)]["start"] == pytest.approx(5001.5)
        assert _boots(tag) == 1


# ---------------------------------------------------------------------------
# a program build split where JAX does the work
# ---------------------------------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


class FakeMonitoring:
    """What the profiler needs of ``jax.monitoring``, and JAX's way of
    recording a compile span: a scalar at entry, a duration at exit."""

    def __init__(self):
        self.scalar, self.duration, self.event = [], [], []

    def register_scalar_listener(self, cb):
        self.scalar.append(cb)

    def register_event_duration_secs_listener(self, cb):
        self.duration.append(cb)

    def register_event_listener(self, cb):
        self.event.append(cb)

    def enter(self, event):
        for cb in self.scalar:
            cb(event, 0.0, fun_name="f")

    def exit(self, event, seconds):
        for cb in self.duration:
            cb(event, seconds, fun_name="f")

    def span(self, event, seconds):
        self.enter(event)
        self.exit(event, seconds)

    def seconds(self, event, seconds):  # a bare duration: the cache read
        for cb in self.duration:
            cb(event, seconds)

    def fire(self, event):
        for cb in self.event:
            cb(event)


@pytest.fixture()
def listening(tmp_path):
    mon, reg = FakeMonitoring(), Registry()
    prof = P.HotPathProfiler(
        clock=ManualClock(), name="t-build", registry=reg,
        ledger_path=tmp_path / "compiles.jsonl", monitoring=mon,
    )
    return mon, reg, prof


def _phase_s(reg, program, kind):
    return reg.value(
        C.COMPILE_PHASE_SECONDS_TOTAL, labels={"program": program, "kind": kind}
    ) or 0.0


class TestBuildSplit:
    @pytest.mark.parametrize("event,kind", [
        (TRACE, "trace"), (LOWER, "lower"), (BACKEND, "xla_compile"),
        (CACHE_READ, "cache_load"),
    ])
    def test_each_event_maps_to_its_kind(self, listening, event, kind):
        mon, reg, prof = listening
        assert P.COMPILE_EVENT_KIND[event] == kind and kind in C.COMPILE_KINDS

        def build():
            if kind == "cache_load":
                mon.seconds(event, 0.75)
            else:
                mon.span(event, 0.75)

        prof.build("prefill_chunk", "off0w64", build)
        for k in C.COMPILE_KINDS:
            assert _phase_s(reg, "prefill_chunk", k) == (0.75 if k == kind else 0.0)
        mon.span("/jax/some/other/event", 9.0)  # not a compile event: ignored
        assert _phase_s(reg, C.EAGER_PROGRAM, kind) == 0.0

    def test_one_listener_a_process_however_many_profilers(self, listening):
        mon, reg, prof = listening
        P.HotPathProfiler(name="t-second", registry=reg, monitoring=mon)
        assert len(mon.scalar) == len(mon.duration) == len(mon.event) == 1
        known = list(P._listening)
        P.HotPathProfiler(name="t-off", registry=Registry())  # none handed over
        assert P._listening == known

    def test_seconds_land_on_the_program_open_on_that_thread(self, listening):
        """A scheduler dispatch and a helper's ``build()`` overlapping: each
        thread's events go to its own program, and outside any to (eager)."""
        mon, reg, prof = listening
        in_dispatch, helper_done = threading.Event(), threading.Event()

        def helper():
            in_dispatch.wait(10)

            def build():
                mon.span(TRACE, 2.0)
                mon.span(BACKEND, 3.0)

            prof.build("prefill_chunk", "off64w32", build, ahead=True)
            helper_done.set()

        t = threading.Thread(target=helper)
        t.start()

        def block_program():
            mon.span(TRACE, 0.5)
            in_dispatch.set()
            helper_done.wait(10)  # the helper builds while this one is open
            mon.span(LOWER, 0.25)

        prof.dispatch("block", "s4k8", block_program, (), {})
        t.join(10)
        mon.span(BACKEND, 0.125)  # no dispatch open: a one-operation helper
        assert _phase_s(reg, "block", "trace") == 0.5
        assert _phase_s(reg, "block", "lower") == 0.25
        assert _phase_s(reg, "block", "xla_compile") == 0.0
        assert _phase_s(reg, "prefill_chunk", "trace") == 2.0
        assert _phase_s(reg, "prefill_chunk", "xla_compile") == 3.0
        assert _phase_s(reg, C.EAGER_PROGRAM, "xla_compile") == 0.125
        assert _phase_s(reg, C.EAGER_PROGRAM, "trace") == 0.0

    def test_the_ledgers_end_row_holds_the_four_fields(self, listening, tmp_path):
        mon, reg, prof = listening

        def build():
            mon.span(TRACE, 1.0)
            mon.span(LOWER, 2.0)
            mon.enter(BACKEND)
            mon.seconds(CACHE_READ, 0.5)  # recorded inside the backend compile
            mon.exit(BACKEND, 0.75)       # whose duration holds the read

        prof.build("block", "s4k8", build)
        rows = [json.loads(line) for line in (tmp_path / "compiles.jsonl").read_text().splitlines()]
        end = rows[-1]
        assert end["event"] == "end" and end["cache"] == "miss"
        assert (end["trace_s"], end["lower_s"], end["xla_compile_s"], end["cache_load_s"]) \
            == (1.0, 2.0, 0.25, 0.5)
        # a build no listener saw anything of still carries them, as zeros
        prof.build("block", "s8k8", lambda: None)
        last = json.loads((tmp_path / "compiles.jsonl").read_text().splitlines()[-1])
        assert [last[k + "_s"] for k in C.COMPILE_KINDS] == [0.0] * 4

    def test_a_span_inside_another_counts_once(self, listening):
        """A jitted function traced inside another's trace, an operation
        compiled eagerly at trace time: the outer span's seconds hold them."""
        mon, reg, prof = listening

        def build():
            mon.enter(TRACE)
            mon.span(TRACE, 0.5)      # an inner jit
            mon.span(BACKEND, 0.25)   # constant folding, compiled at trace time
            mon.exit(TRACE, 2.0)
            mon.span(LOWER, 1.0)

        prof.build("prefill", "b64x4", build)
        assert _phase_s(reg, "prefill", "trace") == 2.0
        assert _phase_s(reg, "prefill", "xla_compile") == 0.0
        assert _phase_s(reg, "prefill", "lower") == 1.0

    def test_the_persistent_caches_answers_are_counted(self, listening):
        mon, reg, prof = listening
        for _ in range(3):
            mon.fire("/jax/compilation_cache/cache_hits")
        mon.fire("/jax/compilation_cache/cache_misses")
        mon.fire("/jax/compilation_cache/compile_requests_use_cache")  # not an answer
        assert reg.value(C.COMPILE_CACHE_TOTAL, labels={"result": "hit"}) == 3.0
        assert reg.value(C.COMPILE_CACHE_TOTAL, labels={"result": "miss"}) == 1.0

    def test_profile_off_hands_jax_monitoring_to_nobody(self):
        """The zero-cost gate, pinned at the AST level like the rest of it:
        the engine names ``jax.monitoring`` once, as an argument of the
        profiler it builds only when profiling is on."""
        tree = ast.parse((PKG_ROOT / "serving" / "engine.py").read_text())
        uses = [
            n for n in ast.walk(tree)
            if isinstance(n, ast.Attribute) and n.attr == "monitoring"
            and isinstance(n.value, ast.Name) and n.value.id == "jax"
        ]
        assert len(uses) == 1
        gates = [
            n for n in ast.walk(tree)
            if isinstance(n, ast.IfExp) and uses[0] in ast.walk(n.body)
        ]
        assert len(gates) == 1
        gate = gates[0]
        assert isinstance(gate.orelse, ast.Constant) and gate.orelse.value is None
        assert isinstance(gate.test, ast.Call) and gate.test.func.attr == "profiling_enabled"
        call = gate.body
        assert call.func.attr == "HotPathProfiler"
        assert any(kw.arg == "monitoring" and kw.value is uses[0] for kw in call.keywords)
