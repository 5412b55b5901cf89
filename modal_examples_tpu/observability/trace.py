"""Call-lifecycle tracing: spans, JSONL trace store, context propagation.

Every ``.remote/.map/.spawn`` call gets a trace whose id IS the call's input
id (``in-...``), so ``tpurun trace <call_id>`` needs no lookup table. The
executor opens phase spans on the supervisor side (queue, boot, dispatch);
the container worker emits its spans (execute, serialize) in the child
process and ships them back over the existing message pipe, where they
stitch into the same trace — one JSONL file per call under
``<state_dir>/traces/``, one JSON object per span (greppable, same spirit
as ``utils/tracking.RunLogger``).

Span timestamps are wall-clock (``time.time()``): supervisor and containers
share a host, so child spans land on the parent's timeline without clock
translation.

``MTPU_TRACE=0`` disables tracing entirely (span helpers return ``None``
and the executor skips every span call site).
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import dataclasses
import json
import os
import queue
import re
import threading
import time
import uuid
from pathlib import Path
from typing import Callable

from .._internal import config as _config

#: traces are retained this long (mirrors the spawned-call record retention)
_TRACE_RETENTION_S = 7 * 86400


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


#: hard bounds on the traces directory — age alone is not enough on a
#: long-running gateway (a week of traffic is unbounded files); LRU-deleted
#: oldest-first past either cap
_MAX_TRACE_FILES = _env_int("MTPU_TRACE_MAX_FILES", 2000)
_MAX_TRACE_BYTES = _env_int("MTPU_TRACE_MAX_BYTES", 256 * 1024 * 1024)
#: deferred spans a store holds in memory before it hands them all to the
#: writer thread, finished trace or not (a trace nobody finishes or reads
#: must not grow without bound)
_MAX_DEFERRED_SPANS = 4096


class _SpanWriter:
    """The one thread per process that appends deferred spans to their
    files, so that the threads recording them (the serving engine's
    scheduler first of all) never open a file. Started on first use."""

    def __init__(self):
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None

    def submit(self, store: "TraceStore", batches: dict) -> None:
        if not batches:
            return
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="mtpu-span-writer", daemon=True
                )
                self._thread.start()
                atexit.register(self.drain)
        self._q.put((store, batches))

    def drain(self, timeout: float = 10.0) -> None:
        """Return once everything submitted before this call is on disk."""
        with self._lock:
            alive = self._thread is not None and self._thread.is_alive()
        if not alive or threading.current_thread() is self._thread:
            return
        done = threading.Event()
        self._q.put(done)
        done.wait(timeout)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if isinstance(item, threading.Event):
                item.set()
                continue
            store, batches = item
            for trace_id, lines in batches.items():
                store._append(trace_id, "".join(lines))


_writer = _SpanWriter()


def tracing_enabled() -> bool:
    return os.environ.get("MTPU_TRACE", "1") not in ("0", "false", "off")


def _new_span_id() -> str:
    return f"sp-{uuid.uuid4().hex[:12]}"


@dataclasses.dataclass
class Span:
    """One timed phase of a call. ``finish()`` stamps the end and returns the
    duration; recording (JSONL write or cross-process shipping) is the
    caller's job via :class:`TraceStore` or a child-side buffer."""

    trace_id: str
    name: str
    span_id: str = dataclasses.field(default_factory=_new_span_id)
    parent_id: str | None = None
    start: float = dataclasses.field(default_factory=time.time)
    end: float | None = None
    status: str = "ok"
    attrs: dict = dataclasses.field(default_factory=dict)

    def finish(self, status: str = "ok", **attrs) -> float:
        if self.end is None:
            self.end = time.time()
        self.status = status
        if attrs:
            self.attrs.update(attrs)
        return self.duration

    @property
    def duration(self) -> float:
        return max(0.0, (self.end or time.time()) - self.start)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "status": self.status,
            "attrs": self.attrs,
        }


class TraceStore:
    """Per-trace JSONL files under ``<state_dir>/traces/``.

    Only *finished* spans are recorded; an abandoned span (e.g. a dispatch
    span whose container vanished without a death notification) simply never
    appears, it can't corrupt the file. Writes are append-only and
    line-atomic, so a concurrent ``tpurun trace`` reader sees a valid prefix.
    """

    def __init__(self, root: str | Path | None = None):
        self._root = Path(root) if root else None
        self._resolved: Path | None = None  # root after its one-time mkdir
        self._lock = threading.Lock()
        self._last_gc = 0.0
        #: trace id -> JSON lines recorded with :meth:`defer`, not yet
        #: handed to the writer thread
        self._deferred: dict[str, list[str]] = {}
        self._n_deferred = 0

    @property
    def root(self) -> Path:
        if self._resolved is None:
            root = self._root or (_config.state_dir() / "traces")
            root.mkdir(parents=True, exist_ok=True)
            self._resolved = root
        return self._resolved

    def record(self, span: "Span | dict") -> None:
        d = span.to_dict() if isinstance(span, Span) else dict(span)
        if d.get("end") is None:
            d["end"] = time.time()
        self._append(d["trace_id"], json.dumps(d) + "\n")

    def _append(self, trace_id: str, text: str) -> None:
        path = self.root / f"{trace_id}.jsonl"
        with self._lock:
            try:
                with open(path, "a") as f:
                    f.write(text)
            except FileNotFoundError:
                # traces dir deleted out from under us: re-create and retry
                # (record runs in the result-delivery path — never raise)
                self._resolved = None
                try:
                    with open(self.root / path.name, "a") as f:
                        f.write(text)
                except OSError:
                    pass
        self._maybe_gc()

    def defer(self, span: "Span | dict") -> None:
        """Record a finished span in memory only: no file is opened on the
        caller's thread. :meth:`settle` (when the trace finishes) hands
        the trace's spans to the process's writer thread; a reader of this
        store settles first, so it sees what :meth:`record` would have
        shown it."""
        d = span.to_dict() if isinstance(span, Span) else dict(span)
        if d.get("end") is None:
            d["end"] = time.time()
        line = json.dumps(d) + "\n"
        with self._lock:
            self._deferred.setdefault(d["trace_id"], []).append(line)
            self._n_deferred += 1
            over = self._n_deferred > _MAX_DEFERRED_SPANS
        if over:
            self.settle()

    def settle(self, trace_id: str | None = None) -> None:
        """Hand the deferred spans of ``trace_id`` (None: of every trace)
        to the writer thread."""
        with self._lock:
            if trace_id is None:
                batches, self._deferred = self._deferred, {}
            else:
                lines = self._deferred.pop(trace_id, None)
                batches = {trace_id: lines} if lines else {}
            self._n_deferred -= sum(len(v) for v in batches.values())
        _writer.submit(self, batches)

    def _settled(self, trace_id: str | None = None) -> None:
        """Reader side: everything deferred so far is on disk on return."""
        self.settle(trace_id)
        _writer.drain()

    def read(self, trace_id: str) -> list[dict]:
        self._settled(trace_id)
        path = self.root / f"{trace_id}.jsonl"
        if not path.exists():
            return []
        spans = []
        for line in path.read_text().splitlines():
            if not line.strip():
                continue
            try:
                spans.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line from a concurrent writer
        return spans

    #: the only shape a trace id can have (both namespaces); resolve()
    #: rejects anything else up front — the token reaches Path/glob, so a
    #: separator or glob metachar must mean "no such trace", not a
    #: traversal or an unhandled pattern error
    _ID_TOKEN_RE = re.compile(r"^[A-Za-z0-9._-]+$")

    def resolve(self, token: str) -> str | None:
        """Resolve ``token`` to a stored trace id: exact match first, then
        a UNIQUE prefix. Both id namespaces live in one store — executor
        calls (``in-…``) and serving requests (``req-…``) — so ``tpurun
        trace``/``explain`` take either kind, abbreviated."""
        if not token or not self._ID_TOKEN_RE.match(token):
            return None
        self._settled()
        if (self.root / f"{token}.jsonl").exists():
            return token
        matches = sorted(p.stem for p in self.root.glob(f"{token}*.jsonl"))
        return matches[0] if len(matches) == 1 else None

    def list_traces(self, limit: int = 50) -> list[str]:
        self._settled()
        files = sorted(
            self.root.glob("*.jsonl"),
            key=lambda p: p.stat().st_mtime,
            reverse=True,
        )
        return [p.stem for p in files[:limit]]

    def _maybe_gc(self) -> None:
        now = time.monotonic()
        with self._lock:
            if now - self._last_gc < 300:
                return
            self._last_gc = now
        # the sweep globs+stats the whole trace dir — run it off-thread so a
        # recording thread (often the container reader delivering a result)
        # never stalls on it
        threading.Thread(target=self._gc_sweep, daemon=True).start()

    def _gc_sweep(self) -> None:
        """Age out old traces, then enforce the count/byte caps LRU-first
        (oldest mtime deleted first) so a long-running gateway's traces
        directory stays bounded no matter the traffic rate."""
        cutoff = time.time() - _TRACE_RETENTION_S
        survivors: list[tuple[float, int, Path]] = []  # (mtime, size, path)
        for p in self.root.glob("*.jsonl"):
            try:
                st = p.stat()
                if st.st_mtime < cutoff:
                    p.unlink()
                else:
                    survivors.append((st.st_mtime, st.st_size, p))
            except OSError:
                pass
        survivors.sort()  # oldest first
        total = sum(size for _, size, _ in survivors)
        excess = len(survivors) - _MAX_TRACE_FILES
        for mtime, size, p in survivors:
            if excess <= 0 and total <= _MAX_TRACE_BYTES:
                break
            try:
                p.unlink()
            except OSError:
                continue
            excess -= 1
            total -= size


#: process-wide default store (state-dir backed)
default_store = TraceStore()


# --------------------------------------------------------------------------
# Context propagation — supervisor -> container worker -> user code
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TraceContext:
    """The ambient trace for the current execution context: new spans created
    with :func:`span` become children of ``span_id`` and are delivered to
    ``sink`` when finished (the store's ``record`` in the supervisor, a
    buffer shipped over the pipe in a container worker)."""

    trace_id: str
    span_id: str | None
    sink: Callable[[dict], None]


_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "mtpu-trace-ctx", default=None
)


def current_context() -> TraceContext | None:
    return _current.get()


def current_trace_id() -> str | None:
    ctx = _current.get()
    return ctx.trace_id if ctx else None


def set_context(ctx: TraceContext | None) -> contextvars.Token:
    return _current.set(ctx)


@contextlib.contextmanager
def span(name: str, **attrs):
    """User-facing span context manager: nests under the ambient trace (a
    no-op yielding None outside one). Works inside container workers — the
    span ships back with the call's execute/serialize spans — and in the
    supervisor process."""
    ctx = _current.get()
    if ctx is None or not tracing_enabled():
        yield None
        return
    sp = Span(
        trace_id=ctx.trace_id, name=name, parent_id=ctx.span_id, attrs=attrs
    )
    token = _current.set(TraceContext(ctx.trace_id, sp.span_id, ctx.sink))
    try:
        yield sp
        sp.finish("ok")
    except BaseException:
        sp.finish("error")
        raise
    finally:
        _current.reset(token)
        ctx.sink(sp.to_dict())
