"""Device idle time by scheduler phase and device time by model part,
from one profiler trace.

Under a profiler session the hot-path profiler's spans are events on the
host plane of the ``.xplane.pb`` (``mtpu.tick/<phase>``,
``mtpu.dispatch/<program>``: profiler.py), on the same clock as the device's
operations. This module holds the two against each other: the device's busy
time is the union of its operations' intervals, what lies between them is
idle, and every idle second is charged to the phase span that covered it on
the scheduler thread. The device's operations in turn carry the
``jax.named_scope`` they were traced under (``mtpu.page_gather``,
``mtpu.attention``, ...: ops/scopes.py), so their time is also summed by
the part of the model they belong to. ``tpurun profile --xplane`` prints
both tables.

The arithmetic works on plain tuples and never imports JAX; only the
command that reads the events does (``jax.profiler.ProfileData``). The
scope of an operation is a stat of its *metadata* (``tf_op``), which that
reader does not hand out, so :func:`op_scopes` takes it from the file's
bytes itself (the protobuf wire format, the few fields it needs).
"""

from __future__ import annotations

from .profiler import DISPATCH_ANNOTATION_PREFIX as DISPATCH_PREFIX
from .profiler import TICK_ANNOTATION_PREFIX as TICK_PREFIX

#: what ops/scopes.py's names start with, and where device time under none
#: of them goes
SCOPE_PREFIX = "mtpu."
UNSCOPED = "(no scope)"

#: idle time under no phase span: between two ticks, or under a span that
#: began before the trace did or was still open when it stopped (the
#: profiler keeps complete events only)
UNCOVERED = "(no span)"
#: the longest gaps kept with their phases, for the question "whose is it"
TOP_GAPS = 8


def busy_intervals(ops: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, in order."""
    merged: list[list[float]] = []
    for start, end in sorted(ops):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def idle_by_phase(
    ops: list[tuple[float, float]], spans: list[tuple[str, float, float]]
) -> dict:
    """One chip against the scheduler thread.

    ``ops`` are the device operations' ``(start_s, end_s)``; ``spans`` the
    phase spans ``(phase, start_s, end_s)``, which do not overlap one
    another (one thread). The window runs from the first operation's start
    to the last one's end, as the benchmark's trace reduction has it, so
    the two agree on busy and idle time. Returns busy_s, idle_s, window_s
    and per phase the idle seconds inside it, its longest single stretch,
    and the number of gaps that touched it."""
    busy = busy_intervals(ops)
    if not busy:
        return {"busy_s": 0.0, "idle_s": 0.0, "window_s": 0.0, "phases": {},
                "longest_gaps": []}
    window = busy[-1][1] - busy[0][0]
    busy_s = sum(b - a for a, b in busy)
    gaps = [(a_end, b_start) for (_a, a_end), (b_start, _b) in zip(busy, busy[1:])]
    spans = sorted(spans, key=lambda s: s[1])
    phases: dict[str, dict] = {}
    longest: list[tuple[float, float, dict]] = []  # (seconds, start, by phase)

    def charge(phase: str, seconds: float) -> None:
        if seconds <= 0:
            return
        row = phases.setdefault(phase, {"idle_s": 0.0, "longest_s": 0.0, "gaps": 0})
        row["idle_s"] += seconds
        row["longest_s"] = max(row["longest_s"], seconds)
        row["gaps"] += 1

    i = 0  # first span that may still reach the current gap
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][2] <= g0:
            i += 1
        covered = 0.0
        mine: dict[str, float] = {}
        j = i
        while j < len(spans) and spans[j][1] < g1:
            phase, s0, s1 = spans[j]
            inside = min(g1, s1) - max(g0, s0)
            charge(phase, inside)
            if inside > 0:
                covered += inside
                mine[phase] = mine.get(phase, 0.0) + inside
            j += 1
        charge(UNCOVERED, (g1 - g0) - covered)
        if len(longest) < TOP_GAPS or g1 - g0 > longest[-1][0]:
            if (g1 - g0) - covered > 0:
                mine[UNCOVERED] = (g1 - g0) - covered
            longest.append((g1 - g0, g0 - busy[0][0], mine))
            longest.sort(key=lambda g: -g[0])
            del longest[TOP_GAPS:]
    return {
        "busy_s": busy_s, "idle_s": window - busy_s, "window_s": window,
        "phases": phases,
        "longest_gaps": [
            {"seconds": s, "at_s": at, "phases": by} for s, at, by in longest
        ],
    }


def reduce_chips(chips: dict[str, list], spans: list[tuple[str, float, float]]) -> dict:
    """Every chip of the trace against the scheduler thread, averaged (one
    scheduler drives all the chips of a tensor-parallel engine)."""
    per_chip = [idle_by_phase(ops, spans) for ops in chips.values()]
    per_chip = [c for c in per_chip if c["window_s"] > 0]
    if not per_chip:
        return {"chips": 0, "busy_s": 0.0, "idle_s": 0.0, "window_s": 0.0,
                "phases": {}, "longest_gaps": []}
    n = len(per_chip)
    phases: dict[str, dict] = {}
    for c in per_chip:
        for phase, row in c["phases"].items():
            out = phases.setdefault(phase, {"idle_s": 0.0, "longest_s": 0.0, "gaps": 0})
            out["idle_s"] += row["idle_s"] / n
            out["longest_s"] = max(out["longest_s"], row["longest_s"])
            out["gaps"] += row["gaps"]
    return {
        "chips": n,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "idle_s": sum(c["idle_s"] for c in per_chip) / n,
        "window_s": sum(c["window_s"] for c in per_chip) / n,
        "phases": phases,
        "longest_gaps": sorted(
            (g for c in per_chip for g in c["longest_gaps"]),
            key=lambda g: -g["seconds"],
        )[:TOP_GAPS],
    }


# -- device time by named scope ----------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field (a string or a
    message, sliced without a copy); fixed-width fields give None."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        value = None
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i : i + size]
            i += size
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield tag >> 3, value


def _map_entries(plane, field: int):
    """The values of a ``map<int64, Message>`` field of an XPlane."""
    for number, entry in _fields(plane):
        if number == field:
            for k, v in _fields(entry):
                if k == 2:
                    yield v


def op_scopes(data: bytes) -> dict[str, dict[str, str]]:
    """Per device plane of a serialized XSpace, each operation's name (as
    ``ProfileData`` gives an ``XLA Ops`` event's) with the op name JAX gave
    it at trace time, named scopes included
    (``jit(f)/while/body/mtpu.attention/dot_general``): the ``tf_op`` stat
    of the event's metadata. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (xplane.proto)."""
    out: dict[str, dict[str, str]] = {}
    for number, plane in _fields(memoryview(data)):
        if number != 1:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane) if k == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names: dict[int, str] = {}
        for meta in _map_entries(plane, 5):
            f = dict(_fields(meta))
            stat_names[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
        ops = out.setdefault(name, {})
        for meta in _map_entries(plane, 4):
            op_name, tf_op = "", None
            for k, v in _fields(meta):
                if k == 2:
                    op_name = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if tf_op:
                ops[op_name] = tf_op
    return out


def scope_of(tf_op: str | None) -> str:
    """The innermost ``mtpu.*`` scope of an op name, or :data:`UNSCOPED`."""
    for part in reversed((tf_op or "").split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def time_by_scope(
    ops: list[tuple[str, float]], scopes: dict[str, str],
    rows: dict[str, dict] | None = None,
) -> dict[str, dict]:
    """Seconds and count of one chip's operations ``(name, seconds)`` by
    the scope each was traced under, added to ``rows`` (the chips of one
    trace share a table). Operations run one after another on a chip, so
    a chip's rows add up to its busy time but for overlapping asynchronous
    copies."""
    rows = {} if rows is None else rows
    for name, seconds in ops:
        row = rows.setdefault(scope_of(scopes.get(name)), {"busy_s": 0.0, "ops": 0})
        row["busy_s"] += seconds
        row["ops"] += 1
    return rows


def render(report: dict) -> list[str]:
    """The table ``tpurun profile --xplane`` prints."""
    window = report["window_s"]
    if not window:
        return ["no device operations in the trace"]
    idle = report["idle_s"]
    lines = [
        f"device: busy {report['busy_s']:.3f}s  idle {idle:.3f}s  "
        f"({100.0 * idle / window:.2f}%)  window {window:.3f}s  "
        f"chips {report['chips']}",
        f"{'IDLE BY PHASE':<20} {'IDLE ms':>10} {'SHARE':>7} {'LONGEST ms':>11} {'GAPS':>7}",
    ]
    rows = sorted(report["phases"].items(), key=lambda kv: -kv[1]["idle_s"])
    for phase, row in rows:
        share = 100.0 * row["idle_s"] / idle if idle else 0.0
        lines.append(
            f"{phase:<20} {row['idle_s'] * 1e3:>10.2f} {share:>6.1f}% "
            f"{row['longest_s'] * 1e3:>11.2f} {row['gaps']:>7}"
        )
    if report["longest_gaps"]:
        lines.append("longest gaps (ms, at s into the trace, phases that covered them):")
        for g in report["longest_gaps"]:
            by = ", ".join(
                f"{p} {s * 1e3:.2f}" for p, s in sorted(g["phases"].items(), key=lambda kv: -kv[1])
            )
            lines.append(f"  {g['seconds'] * 1e3:>8.2f}  at {g['at_s']:>7.3f}  {by}")
    scopes = report.get("scopes")
    if scopes:
        total = sum(row["busy_s"] for row in scopes.values())
        lines.append(f"{'DEVICE TIME BY SCOPE':<20} {'BUSY ms':>10} {'SHARE':>7} {'OPS':>11}")
        for scope, row in sorted(scopes.items(), key=lambda kv: -kv[1]["busy_s"]):
            lines.append(
                f"{scope:<20} {row['busy_s'] * 1e3:>10.2f} "
                f"{100.0 * row['busy_s'] / total if total else 0.0:>6.1f}% "
                f"{row['ops']:>11}"
            )
    dispatches = report.get("dispatches")
    if dispatches:
        lines.append("dispatches in the trace: " + ", ".join(
            f"{name} x{n}" for name, n in sorted(dispatches.items())
        ))
    elif not any(p != UNCOVERED for p in report["phases"]):
        lines.append(
            "no mtpu.tick/* events on the host plane: the trace was taken "
            "under MTPU_PROFILE=0, or from a program older than the spans"
        )
    return lines
