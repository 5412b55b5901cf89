"""Hot-path time attribution: per-tick host/device phase accounting and
compile telemetry for the serving engine (docs/observability.md).

ROADMAP #3 claims the biggest remaining throughput lever is amortizing the
per-token host overhead — one Python tick of dispatch/harvest/detokenize
per generated token — and ROADMAP #1 needs the ≥40-slot compile-helper
ceiling diagnosable offline. Neither was measurable: request traces show
WHERE a request went, progress watermarks show THAT the scheduler moves,
but nothing attributed where a scheduler tick's time actually goes or
recorded when/what XLA compiles. This module is that instrument — the
measurement foundation every subsequent perf PR is judged against.

Its legs:

- **Tick anatomy** — the scheduler thread accounts each ``step()`` into
  named phases (:data:`~.catalog.TICK_PHASES`) as SCOPED spans on the
  engine's injectable clock: :meth:`HotPathProfiler.begin_tick` hands the
  tick a :class:`TickProfile`, the engine's ``_tm(tick, "phase")`` helper
  ENTERS a phase (closing the one before it — one thread, so the spans
  partition the tick), and :meth:`~HotPathProfiler.end_tick` aggregates
  busy ticks into a ring buffer plus the
  ``mtpu_tick_phase_seconds{phase}`` histograms. Because the phase is
  known when its span opens, each span also opens the engine-supplied
  trace annotation ``mtpu.tick/<phase>`` (and each program dispatch
  ``mtpu.dispatch/<program>``): a no-op with no profiler session, an
  event on the host plane of the device trace under one — the program's
  spans and the device's operations on ONE clock. Blocking device reads
  enter with ``device=True``, so the ring carries a host-vs-device split
  and the ``mtpu_host_overhead_ratio`` gauge falls out: 1 - device-blocked
  over total — the number the decode block's rolled steps keep small.
- **Device starvation** — the profiler numbers every dispatch and the
  engine reports which number each blocking read harvested; the device
  runs one program after another, so everything up to a harvested number
  is done. Scheduler-thread time with nothing dispatched and unharvested,
  while a request is queued or running, accrues to
  ``mtpu_device_starved_seconds_total{phase}`` under the phase the thread
  was in: a lower bound on device idleness that needs no profiler session
  (a program that finished before the host read it is still counted as
  running; the trace annotations see those gaps, ``tpurun profile
  --xplane``).
- **Compile telemetry** — every jitted-program dispatch goes through ONE
  chokepoint, :meth:`~HotPathProfiler.dispatch`: a dispatch that BUILT
  its program — the first of a (program, shape_key), or any call during
  which the jitted function's own cache grew (XLA traced and built again
  because a shape or dtype changed under the same key) — is timed
  (``mtpu_compile_seconds{program}``,
  ``mtpu_compiles_total{program,cache="miss"}``) and appended to the
  ``<state_dir>/compiles.jsonl`` ledger (the journal pattern); every other
  dispatch counts as a cache hit. The ledger writes a ``begin`` event
  BEFORE the build and an ``end`` event after — so a compile helper that
  crashes or hangs mid-build (the ≥40-slot ceiling) leaves a
  begin-without-end row naming exactly which program/shape killed it,
  diagnosable offline from the ledger alone.
- **A build split where JAX does the work** — the engine also hands the
  profiler ``jax.monitoring`` (:func:`install_compile_listener`): JAX's own
  events time tracing, lowering, XLA's compile and a persistent-cache read
  (:data:`~.catalog.COMPILE_KINDS`), and the seconds go to
  ``mtpu_compile_phase_seconds_total{program,kind}`` under the program
  whose :meth:`~HotPathProfiler.dispatch` or :meth:`~HotPathProfiler.build`
  is open on the thread that did the work, else ``program="(eager)"``; the
  ledger's ``end`` row carries the four. It fires only when JAX traces or
  compiles.
- **Boot anatomy** — :class:`BootProfile`, of :class:`TickProfile`'s form:
  the container's one boot as top-level phases
  (:data:`~.catalog.BOOT_PHASES`) that partition it from the supervisor's
  ``Popen`` to ``ready``, on ``time.monotonic()`` (one clock for every
  process of a host), with nested marks (:data:`~.catalog.BOOT_MARKS`) the
  library opens where the work happens. A dozen clock reads a process.
- **Surfaces** — ``tpurun profile`` (phase table, host fraction, top
  compiles), the gateway's ``/profile`` route, Perfetto counter tracks +
  compile slices merged into the replica-aware trace export, and the
  BENCH ``overhead`` section via :meth:`~HotPathProfiler.overhead_summary`.

**On by default, zero-cost when disabled** (the ``faults/inject.py`` gate
pattern): ``LLMEngine.__init__`` resolves ``MTPU_PROFILE`` ONCE (explicit
arg beats env; unset means on, ``0`` off) and keeps ``self.profiler =
None`` when off — every hot-path touch point is then a ``tick is None``
branch with no timestamp, no allocation, no dict write.
``tests/test_profiler.py`` pins the no-op shape at the AST level like the
faults gate. On, a busy tick costs a clock read per phase entered, one
histogram observation per phase at its end, and two clock reads per
accepted token (the ``detokenize`` split, which writes nothing else); an
idle engine's ticks get no :class:`TickProfile` at all.

jax-free and import-light: ``observability/`` is imported by the jax-free
``core/`` layer, and ``tpurun profile`` must not attach a chip to render a
ledger.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import deque

from ..utils.stats import percentile_nearest_rank as _pct
from . import catalog as C
from . import metrics as _obs
from .journal import JOURNALS, DecisionJournal, named_journal

#: the one env switch (resolved once in ``LLMEngine.__init__``, the
#: MTPU_KV_DTYPE rule): the OFF switch — ``0`` = off, unset = on
PROFILE_ENV = "MTPU_PROFILE"

#: trace-annotation names, built once (an annotation per phase entered
#: must not format a string on the scheduler thread)
TICK_ANNOTATION_PREFIX = "mtpu.tick/"
DISPATCH_ANNOTATION_PREFIX = "mtpu.dispatch/"
TICK_ANNOTATION = {p: TICK_ANNOTATION_PREFIX + p for p in C.TICK_PHASES}

#: busy ticks retained in the in-memory ring (per profiler)
RING_TICKS = 512
#: completed compile records retained in memory for the Perfetto export
#: (the JSONL ledger is the unbounded-ish superset)
COMPILE_LOG_KEEP = 256
#: refresh the host-overhead gauge every N busy ticks (a gauge write per
#: tick would be pure lock traffic for a value that moves slowly)
_GAUGE_EVERY = 32

#: the ledger file name under ``<state_dir>`` — owned by the
#: ``JOURNALS`` table (journal.py) and resolved through
#: ``named_journal("compiles")``; re-exported here for readers
LEDGER_NAME = JOURNALS["compiles"]


def profiling_enabled(explicit=None) -> bool:
    """Resolve the profile switch ONCE: explicit arg beats
    :data:`PROFILE_ENV`; unset means ON and ``0`` off (the MTPU_KV_DTYPE
    rule — the env is never re-read on the hot path)."""
    import os

    if explicit is not None:
        return bool(explicit)
    return os.environ.get(PROFILE_ENV, "") != "0"


class BootProfile:
    """One container boot as a sequence of scoped phase spans, of
    :class:`TickProfile`'s form: :meth:`enter` opens a top-level phase
    (:data:`~.catalog.BOOT_PHASES`) and closes the one before it. The boot
    runs on one thread, so the phases partition it from ``spawned`` (the
    supervisor's monotonic stamp at ``Popen``, handed down in the
    container's config) to :meth:`finish`. :meth:`mark` times work nested
    inside a phase (:data:`~.catalog.BOOT_MARKS`) under its own name and
    adds nothing to the partition. ``time.monotonic()`` is Linux's
    CLOCK_MONOTONIC: one clock for the supervisor, the container and
    whoever reads the two ends from ``/metrics``."""

    def __init__(self, spawned: float | None = None, clock=None):
        self._clock = clock or time.monotonic
        self.spawned = self._clock() if spawned is None else float(spawned)
        self.ready: float | None = None
        # the boot opens in ``spawn``, at the supervisor's stamp
        self._last = self.spawned
        self._phase: str | None = C.BOOT_PHASES[0]
        self.phases: dict[str, float] = {}
        self.marks: dict[str, float] = {}
        #: the marks open now, innermost last: (name, start, parent)
        self._open: list[tuple[str, float, str | None]] = []
        #: (name, start, end, parent) on the clock, for the supervisor's
        #: child spans; a top-level phase has no parent
        self.spans: list[tuple[str, float, float, str | None]] = []

    def enter(self, phase: str | None) -> float:
        """Close the open phase and open ``phase`` (None: only close).
        Entering a phase twice adds to it. Returns the closed seconds."""
        now = self._clock()
        dt = max(0.0, now - self._last)
        old = self._phase
        if old is not None:
            self.phases[old] = self.phases.get(old, 0.0) + dt
            self.spans.append((old, self._last, now, None))
        self._last = now
        self._phase = phase
        return dt

    def open_mark(self, name: str) -> None:
        """Open a mark nested in the innermost open mark, else in the open
        phase."""
        parent = self._open[-1][0] if self._open else self._phase
        self._open.append((name, self._clock(), parent))

    def close_mark(self) -> None:
        """Close the innermost open mark."""
        if not self._open:
            return
        name, t0, parent = self._open.pop()
        now = self._clock()
        self.marks[name] = self.marks.get(name, 0.0) + max(0.0, now - t0)
        self.spans.append((name, t0, now, parent))

    @contextlib.contextmanager
    def mark(self, name: str):
        self.open_mark(name)
        try:
            yield
        finally:
            self.close_mark()

    def finish(self, registry=None) -> dict:
        """The boot is over: close the open phase, stamp ``ready``, write
        the gauges (once), and return what the ``ready`` message carries."""
        self.enter(None)
        self.ready = self._last
        ends = {"spawned": self.spawned, "ready": self.ready}
        _obs.set_boot_profile(
            {**self.phases, **self.marks}, ends, registry=registry
        )
        return {
            **ends,
            "phases": dict(self.phases),
            "marks": dict(self.marks),
            "spans": [list(sp) for sp in self.spans],
        }


#: this process's boot, while it is under way (a container between
#: ``_container_main``'s entry and its ``ready``); None everywhere else
_boot: BootProfile | None = None


def begin_boot(spawned: float | None = None, clock=None) -> BootProfile:
    """Open this process's boot profile (in the phase ``spawn``)."""
    global _boot
    _boot = BootProfile(spawned, clock)
    return _boot


def finish_boot(registry=None) -> dict:
    """Close this process's boot; what the ``ready`` message carries
    (empty where no boot was open)."""
    global _boot
    boot, _boot = _boot, None
    return boot.finish(registry) if boot is not None else {}


def boot_enter(phase: str) -> None:
    """Enter a top-level boot phase; nothing where no boot is under way
    (the inline backend, a process that is no container)."""
    if _boot is not None:
        _boot.enter(phase)


class boot_mark(contextlib.ContextDecorator):
    """Around work nested in the boot (the library's ``engine_init``,
    ``kv_alloc``, ``server_start``), as a ``with`` block or as a decorator
    of a whole function (an ``__init__``); nothing once the boot is over
    or where there is none."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        if _boot is not None:
            _boot.open_mark(self.name)

    def __exit__(self, *_exc):
        if _boot is not None:
            _boot.close_mark()


class TickProfile:
    """One scheduler tick as a sequence of scoped phase spans.

    :meth:`enter` opens a phase and closes the one before it — the
    scheduler runs one thread, so the spans partition the tick exactly and
    the per-phase sums can never exceed the tick total. ``device=True``
    additionally counts the span as device-blocked time (the host waiting
    on a device array), feeding the host-vs-device split. Each span closed
    while the device had nothing dispatched adds to the tick's
    ``starved`` seconds under its phase (see
    :meth:`HotPathProfiler.note_harvest`).
    """

    __slots__ = (
        "_prof", "_clock", "t0", "_last", "_phase", "_device", "_ann",
        "phases", "device_s", "starved",
    )

    def __init__(self, prof: "HotPathProfiler"):
        self._prof = prof
        self._clock = prof._clock
        self.t0 = self._last = self._clock()
        self._phase: str | None = None
        self._device = False
        self._ann = None
        self.phases: dict[str, float] = {}
        self.device_s = 0.0
        self.starved: dict[str, float] = {}

    def enter(
        self, phase: str | None, device: bool = False, annotate: bool = True
    ) -> float:
        """Close the open span into its phase and open ``phase`` (None:
        only close — the tick's end). Returns the closed span's seconds.
        ``annotate=False`` switches the accounting alone and leaves the
        open trace annotation as it is: the per-token ``detokenize`` split
        costs two clock reads and writes nothing into a trace."""
        now = self._clock()
        dt = now - self._last
        self._last = now
        old = self._phase
        if old is not None:
            if dt > 0:
                self.phases[old] = self.phases.get(old, 0.0) + dt
                if self._device:
                    self.device_s += dt
            since = self._prof._starved_since
            if since is not None:
                if now > since:
                    self.starved[old] = self.starved.get(old, 0.0) + now - since
                self._prof._starved_since = now
        self._phase = phase
        self._device = device
        if annotate:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            make = self._prof._annotate
            if make is not None and phase is not None:
                self._ann = make(TICK_ANNOTATION[phase])
                self._ann.__enter__()
        return dt


class HotPathProfiler:
    """Per-engine hot-path profiler: tick ring + compile telemetry.

    ``clock`` is the engine's injectable monotonic clock (fake-clock tests
    see real phase deltas); ``name`` is the replica name, or a zero-arg
    callable resolving it lazily (the engine's ``trace_name`` is assigned
    by the fleet AFTER construction); ``annotate`` is the engine's trace-
    annotation factory (``jax.profiler.TraceAnnotation`` — this package
    stays free of JAX), called as ``annotate(name, **attrs)`` for a context
    manager; ``monitoring`` is ``jax.monitoring``, handed over the same way
    (:func:`install_compile_listener`). Ticks and starvation belong to the
    scheduler thread; :meth:`dispatch` is also safe from ``prefill_sync``
    server threads.
    """

    def __init__(
        self,
        *,
        clock=None,
        name="engine",
        registry=None,
        ledger_path=None,
        ring: int = RING_TICKS,
        annotate=None,
        monitoring=None,
    ):
        self._clock = clock or time.monotonic
        self._name = name
        self._registry = registry
        self._annotate = annotate
        if monitoring is not None:
            install_compile_listener(monitoring)
        #: programs dispatched / the highest dispatch number a blocking
        #: read has harvested (the device runs them in order, so all up to
        #: it are done); equal = nothing on the device that the host knows of
        self.dispatched = 0
        self._harvested = 0
        #: start of the open starvation interval on ``clock``, or None
        self._starved_since: float | None = None
        self._tick: TickProfile | None = None  # the open tick, if profiled
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=ring)
        self._busy_ticks = 0
        #: (program, shape_key str) pairs already built in this process
        self._seen: set[tuple[str, str]] = set()
        self._compiles = 0
        self._compile_s = 0.0
        self._dispatches = 0
        self._dispatch_tokens = 0
        self._compile_log: deque[dict] = deque(maxlen=COMPILE_LOG_KEEP)
        self._ledger_path = ledger_path
        self._ledger: DecisionJournal | None = None
        register(self)

    @property
    def replica(self) -> str:
        return str(self._name() if callable(self._name) else self._name)

    # -- tick anatomy --------------------------------------------------------

    def begin_tick(self, demand: bool = True) -> TickProfile | None:
        """The tick's profile, or None for a tick of an idle engine
        (``demand`` false — no request queued or running — and nothing on
        the device): idle ticks take no timestamp at all. A tick that
        starts with demand and an empty device opens a starvation
        interval (a request reached an idle engine)."""
        empty = self.dispatched <= self._harvested
        if empty and not demand:
            self._starved_since = None
            return None
        tick = self._tick = TickProfile(self)
        if empty and self._starved_since is None:
            self._starved_since = tick.t0
        return tick

    def end_tick(
        self, tick: TickProfile, worked: bool, demand: bool = True
    ) -> None:
        """Close one tick. Idle ticks (``worked=False``, or no phase
        entered) record NOTHING in the ring and the phase histograms — an
        idle engine's profile stays empty instead of drowning the signal
        in sub-millisecond no-op loops. Starved seconds are counted either
        way; with no ``demand`` left the open starvation interval ends."""
        tick.enter(None)
        self._tick = None
        if not demand:
            self._starved_since = None
        for phase, seconds in tick.starved.items():
            _obs.record_device_starved(
                phase, seconds, registry=self._registry
            )
        if not worked or not tick.phases:
            return
        total = max(0.0, tick._last - tick.t0)
        entry = {
            "at": time.time(),  # wall clock: aligns with trace span starts
            "total": total,
            "device": tick.device_s,
            "phases": dict(tick.phases),
        }
        with self._lock:
            self._ring.append(entry)
            self._busy_ticks += 1
            refresh = self._busy_ticks % _GAUGE_EVERY == 0
        for phase, seconds in tick.phases.items():
            _obs.record_tick_phase(phase, seconds, registry=self._registry)
        _obs.record_tick_phase(
            C.TICK_TOTAL_PHASE, total, registry=self._registry
        )
        if refresh:
            self._refresh_ratio()

    def note_harvest(self, seq: int, tick: TickProfile | None = None) -> None:
        """A blocking read returned the output of dispatch number ``seq``:
        that program and every one before it are done. With nothing later
        dispatched the device is empty from the moment the read returned —
        the start of ``tick``'s open span, which the engine entered right
        after the read — until the next dispatch."""
        if seq > self._harvested:
            self._harvested = seq
        if self.dispatched <= self._harvested and self._starved_since is None:
            self._starved_since = (
                tick._last if tick is not None else self._clock()
            )

    def note_drained(self) -> None:
        """Everything dispatched is done or dropped (a ``block_until_ready``
        outside the scheduler loop, or the release sweep of a stopping
        engine): nothing outstanding, no starvation interval open."""
        self._harvested = self.dispatched
        self._starved_since = None

    def note_dispatch_tokens(self, n: int) -> None:
        """One harvested decode dispatch (a block or a speculative round)
        accepted ``n`` tokens."""
        with self._lock:
            self._dispatches += 1
            self._dispatch_tokens += int(n)

    def flush(self) -> None:
        """Force the host-overhead gauge current (engine stop / push time:
        a short run may never cross the every-N-ticks refresh)."""
        self._refresh_ratio()

    def _refresh_ratio(self) -> None:
        with self._lock:
            total = sum(e["total"] for e in self._ring)
            device = sum(e["device"] for e in self._ring)
        if total > 0:
            _obs.set_host_overhead_ratio(
                max(0.0, min(1.0, 1.0 - device / total)),
                registry=self._registry,
            )

    # -- compile telemetry ---------------------------------------------------

    def dispatch(self, program: str, shape_key, fn, args, kwargs):
        """THE dispatch chokepoint: call ``fn`` (a jitted program, async)
        under the ``mtpu.dispatch/<program>`` annotation, number it for
        the starvation account, and tell a build from a cache hit.

        A build is the first dispatch of a (program, shape_key) in this
        process, or ANY call during which the jitted function's own cache
        grew (``fn._cache_size()`` before and after): XLA traced and built
        the program again because an argument's shape or dtype changed
        under a key the engine thought it had seen. The first kind writes
        its ``begin`` ledger event BEFORE the call, so a crash or hang
        mid-compile still names its program/shape; a build that raises is
        forgotten, so the retry is timed as a fresh miss."""
        key = (program, str(shape_key))
        with self._lock:
            first = key not in self._seen
            self._seen.add(key)
        cache_size = getattr(fn, "_cache_size", None)
        n0 = cache_size() if cache_size is not None else 0
        if first:
            self._ledger_begin(key)
        ann = None
        if self._annotate is not None:
            ann = self._annotate(
                DISPATCH_ANNOTATION_PREFIX + program, shape=key[1]
            )
            ann.__enter__()
        kinds: dict[str, float] = {}
        _builds.open.append((self, program, kinds))
        t0 = self._clock()
        try:
            out = fn(*args, **kwargs)
        except BaseException:
            if first:
                with self._lock:
                    self._seen.discard(key)
            raise
        finally:
            _builds.open.pop()
            if ann is not None:
                ann.__exit__(None, None, None)
        built = first or (cache_size is not None and cache_size() > n0)
        self.note_compile(
            program, shape_key,
            self._clock() - t0 if built else 0.0, cache_hit=not built,
            kinds=kinds,
        )
        # the program is on the device's queue: a starvation interval ends
        # here, charged to the phase the scheduler thread is in
        self.dispatched += 1
        since = self._starved_since
        if since is not None:
            self._starved_since = None
            tick = self._tick
            if tick is not None and tick._phase is not None and t0 > since:
                tick.starved[tick._phase] = (
                    tick.starved.get(tick._phase, 0.0) + t0 - since
                )
        return out

    def build(self, program: str, shape_key, build_fn, *, ahead: bool = False):
        """Build a program off the jitted function's own dispatch
        (``build_fn``: lower and compile, nothing run) and book it as that
        (program, shape_key)'s one build: ``begin`` before, seconds and
        ``end`` after, so the dispatch that later runs the compiled program
        counts as a hit. ``ahead``: no dispatch is waiting for it (a helper
        thread's build), so it counts as ``cache="ahead"``, not as a miss at
        a dispatch site. Any thread may call it; nothing is numbered for the
        starvation account (the device was handed nothing)."""
        key = (program, str(shape_key))
        with self._lock:
            self._seen.add(key)
        self._ledger_begin(key)
        kinds: dict[str, float] = {}
        _builds.open.append((self, program, kinds))
        t0 = self._clock()
        try:
            out = build_fn()
        except BaseException:
            with self._lock:
                self._seen.discard(key)
            raise
        finally:
            _builds.open.pop()
        self.note_compile(
            program, shape_key, self._clock() - t0, cache_hit=False,
            ahead=ahead, kinds=kinds,
        )
        return out

    def note_compile(
        self, program: str, shape_key, seconds: float, cache_hit: bool,
        ahead: bool = False, kinds: dict | None = None,
    ) -> None:
        """THE chokepoint every build site reports through: counts the
        lookup (``mtpu_compiles_total{program,cache}``); a build (a miss at
        a dispatch site, or one made ``ahead`` of any) also observes
        ``mtpu_compile_seconds{program}`` and appends the ``end`` event to
        the ledger, with what JAX's events made of the seconds (``kinds``:
        ``trace_s``, ``lower_s``, ``xla_compile_s``, ``cache_load_s``; 0.0
        where no listener is)."""
        _obs.record_compile(
            program, seconds, cache_hit, ahead=ahead, registry=self._registry
        )
        if cache_hit:
            return
        rec = {
            "at": time.time(),
            "event": "end",
            "replica": self.replica,
            "program": program,
            "shape_key": str(shape_key),
            "seconds": round(float(seconds), 6),
            "cache": "ahead" if ahead else "miss",
            **{
                kind + "_s": round((kinds or {}).get(kind, 0.0), 6)
                for kind in C.COMPILE_KINDS
            },
        }
        with self._lock:
            self._compiles += 1
            self._compile_s += float(seconds)
            self._compile_log.append(rec)
        self._ledger_record(rec)

    def _ledger_begin(self, key: tuple[str, str]) -> None:
        self._ledger_record({
            "at": time.time(),
            "event": "begin",
            "replica": self.replica,
            "program": key[0],
            "shape_key": key[1],
        })

    def _ledger_record(self, rec: dict) -> None:
        with self._lock:  # build() records from helper threads
            if self._ledger is None:
                self._ledger = named_journal(
                    "compiles", path=self._ledger_path
                )
        self._ledger.record(rec)

    # -- read surfaces -------------------------------------------------------

    def overhead_summary(self) -> dict:
        """The BENCH ``overhead`` section / ``/profile`` payload: per-phase
        tick p50/p95 over the ring, the host fraction, the detokenize
        share, attribution coverage (attributed/total — structurally ≤ 1),
        and compile totals."""
        with self._lock:
            ring = list(self._ring)
            compiles_n, compile_s = self._compiles, self._compile_s
            dispatches = self._dispatches
            dispatch_tokens = self._dispatch_tokens
        tokens_per_dispatch = (
            round(dispatch_tokens / dispatches, 3) if dispatches else None
        )
        if not ring:
            return {
                "ticks": 0,
                "host_fraction": None,
                "tick_p50": None,
                "tick_p95": None,
                "detok_share": None,
                "attribution_cover": None,
                "phases": {},
                "compile_total_s": round(compile_s, 3),
                "compiles_n": compiles_n,
                "dispatches": dispatches,
                "tokens_per_dispatch": tokens_per_dispatch,
            }
        totals = sorted(e["total"] for e in ring)
        sum_total = sum(totals)
        sum_device = sum(e["device"] for e in ring)
        sum_detok = sum(e["phases"].get("detokenize", 0.0) for e in ring)
        sum_attr = sum(sum(e["phases"].values()) for e in ring)
        phases: dict[str, dict] = {}
        for phase in C.TICK_PHASES:
            vals = sorted(
                e["phases"][phase] for e in ring if phase in e["phases"]
            )
            if vals:
                phases[phase] = {
                    "p50": round(_pct(vals, 0.50), 6),
                    "p95": round(_pct(vals, 0.95), 6),
                    "count": len(vals),
                }
        return {
            "ticks": len(ring),
            "host_fraction": round(
                max(0.0, min(1.0, 1.0 - sum_device / sum_total)), 6
            ) if sum_total > 0 else None,
            "tick_p50": round(_pct(totals, 0.50), 6),
            "tick_p95": round(_pct(totals, 0.95), 6),
            "detok_share": round(sum_detok / sum_total, 6)
            if sum_total > 0 else None,
            "attribution_cover": round(sum_attr / sum_total, 6)
            if sum_total > 0 else None,
            "phases": phases,
            "compile_total_s": round(compile_s, 3),
            "compiles_n": compiles_n,
            "dispatches": dispatches,
            "tokens_per_dispatch": tokens_per_dispatch,
        }

    def perfetto_snapshot(self) -> dict:
        """Ring + in-memory compile log in the shape the Perfetto export's
        ``profile=`` parameter takes (wall-clock ``at`` fields align with
        request-span timestamps)."""
        with self._lock:
            return {
                "ticks": [dict(e) for e in self._ring],
                "compiles": [dict(r) for r in self._compile_log],
            }


# -- a build split where JAX does the work -----------------------------------

#: JAX's monitoring events (jax 0.9: ``jax/_src/dispatch.py``,
#: ``compiler.py``) -> :data:`~.catalog.COMPILE_KINDS`. The three
#: ``/jax/core/compile/`` events are spans: a scalar at entry, a duration
#: at exit, and they nest (a jitted function traced inside another's trace,
#: an eager operation compiled at trace time). The cache read is a bare
#: duration recorded inside ``backend_compile``, whose own duration holds it
COMPILE_EVENT_KIND = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: the kinds JAX records as spans (entered, then exited with a duration)
_SPAN_KINDS = frozenset(C.COMPILE_KINDS) - {"cache_load"}
#: the persistent cache's answers: a compiled program read back | one
#: compiled and written
CACHE_EVENT_HIT = {
    "/jax/compilation_cache/cache_hits": True,
    "/jax/compilation_cache/cache_misses": False,
}


class _ThreadBuilds(threading.local):
    """What the listener knows of the calling thread: the builds open on it
    (``dispatch()`` / ``build()`` push ``(profiler, program, kinds)``; helper
    threads build too, and overlap the scheduler's), how many of JAX's
    compile spans are open, and the cache-read seconds inside the open
    backend compile."""

    def __init__(self):
        self.open: list = []
        self.depth = 0
        self.loaded = 0.0


_builds = _ThreadBuilds()
#: the monitoring modules a listener is registered with (JAX's, or a
#: test's fake): one listener each, for the life of the process
_listening: list = []


def install_compile_listener(monitoring) -> None:
    """Register this module's listeners with ``monitoring``
    (``jax.monitoring``, which the engine hands over: this package stays
    free of JAX), once a process however many profilers ask. They fire only
    when JAX traces or compiles."""
    with _registry_lock:
        if any(m is monitoring for m in _listening):
            return
        _listening.append(monitoring)
    monitoring.register_scalar_listener(_on_compile_span_entered)
    monitoring.register_event_duration_secs_listener(_on_compile_seconds)
    monitoring.register_event_listener(_on_cache_answer)


def _on_compile_span_entered(event: str, value=None, **_kw) -> None:
    if COMPILE_EVENT_KIND.get(event) in _SPAN_KINDS:
        _builds.depth += 1


def _on_compile_seconds(event: str, seconds: float, **_kw) -> None:
    kind = COMPILE_EVENT_KIND.get(event)
    if kind is None:
        return
    tls = _builds
    if kind == "cache_load":
        if tls.depth <= 1:  # in a backend compile no other span encloses
            tls.loaded += seconds
            _credit(kind, seconds)
        return
    tls.depth = max(0, tls.depth - 1)
    if tls.depth:
        return  # the enclosing span's duration holds these seconds
    if kind == "xla_compile":
        seconds = max(0.0, seconds - tls.loaded)
    tls.loaded = 0.0
    _credit(kind, seconds)


def _credit(kind: str, seconds: float) -> None:
    open_builds = _builds.open
    if open_builds:
        prof, program, kinds = open_builds[-1]
        kinds[kind] = kinds.get(kind, 0.0) + seconds
        _obs.record_compile_phase(
            program, kind, seconds, registry=prof._registry
        )
        return
    for registry in _live_registries():
        _obs.record_compile_phase(
            C.EAGER_PROGRAM, kind, seconds, registry=registry
        )


def _on_cache_answer(event: str, **_kw) -> None:
    hit = CACHE_EVENT_HIT.get(event)
    if hit is not None:
        for registry in _live_registries():
            _obs.record_compile_cache(hit, registry=registry)


def _live_registries() -> list:
    """The registries of the live profilers, each once (None: the
    process's default): work no open build claims is the process's."""
    seen: dict[int, object] = {}
    for prof in active_profilers():
        seen.setdefault(id(prof._registry), prof._registry)
    return list(seen.values())


# -- process registry (the gateway's /profile source) ------------------------

_registry_lock = threading.Lock()
#: weak refs so the registry never pins a dead engine's profiler (the
#: profiler's lazy-name callable holds the engine)
_profilers: list = []


def register(profiler: HotPathProfiler) -> None:
    with _registry_lock:
        _profilers.append(weakref.ref(profiler))
        # drop dead refs opportunistically; cap the list
        _profilers[:] = [r for r in _profilers if r() is not None][-64:]


def active_profilers() -> list[HotPathProfiler]:
    with _registry_lock:
        return [p for p in (r() for r in _profilers) if p is not None]


def read_ledger(path=None, n: int = 200) -> list[dict]:
    """Newest-last slice of the compile ledger (jax-free — `tpurun
    profile` and the gateway read it without touching an engine)."""
    return named_journal("compiles", path=path).tail(n)


def unfinished_builds(records: list[dict]) -> list[dict]:
    """``begin`` events with no matching LATER ``end`` — the offline
    diagnosis for a compile helper that crashed or hung mid-build (the
    ≥40-slot ceiling's smoking gun). Pairing is strictly ordered: an
    ``end`` closes only begins that precede it, so a ledger spanning
    several runs (revalidate rounds append) still reports a later run's
    mid-build crash of a program/shape that built fine earlier."""
    open_begins: dict[tuple, dict] = {}
    for rec in records:
        key = (rec.get("replica"), rec.get("program"), rec.get("shape_key"))
        if rec.get("event") == "begin":
            open_begins[key] = rec
        elif rec.get("event") == "end":
            open_begins.pop(key, None)
    return list(open_begins.values())
