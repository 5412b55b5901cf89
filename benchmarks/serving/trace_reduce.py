"""From the profiler's trace to numbers: busy union, idle gaps, op ranking,
time per compiled program, device time by the program's named scopes.

Device time is attributed by what the profiler prints on its own, the XLA
module of each compiled program (``jit__decode_block_fn``,
``jit__prefill_and_sample``, ``jit_prefill_chunk_off<n>``) and the XLA
operation names inside it, and by the innermost ``mtpu.*`` scope
(``jax.named_scope``) each operation was traced under: the ``tf_op`` stat of
the operation's metadata, which ``jax.profiler.ProfileData`` does not hand
out, so :func:`op_scopes` takes it from the file's bytes. That is a copy of
what the program's ``tpurun profile --xplane`` does, not an import: no later
change to the program changes what the benchmark reads. The reduction works
on plain tuples, so the tests feed it small recorded traces; only
:func:`load_xplane` knows the profiler's file.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SCOPE_PREFIX = "mtpu."
#: device time of operations traced under no ``mtpu.*`` scope
UNSCOPED = "(no scope)"


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
    varint, a memoryview for a length-delimited field; fixed-width fields
    give None."""
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
    """Per device plane of a serialized XSpace: an operation's name (as
    ``ProfileData`` gives an ``XLA Ops`` event's) -> the op name JAX gave it
    at trace time, named scopes included
    (``jit(f)/while/body/mtpu.attention/dot_general``); a fusion carries its
    root's. XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (xplane.proto)."""
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


def scope_of(tf_op: str) -> str:
    """The innermost ``mtpu.*`` scope of an op name, or :data:`UNSCOPED`."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def load_xplane(path: str) -> dict:
    """{"chips": {plane name: {"modules": [(name, start_s, dur_s)],
    "ops": [(name, start_s, dur_s, scope)]}}, "lines": what the file holds}.
    ``scope`` as :func:`scope_of` gives it; None on a plane whose metadata
    names no operation's ``tf_op``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    chips: dict = {}
    seen: dict = {}
    for plane in data.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if not plane.name.startswith("/device:TPU:"):
            continue
        chip = {"modules": [], "ops": []}
        tf_ops = scopes.get(plane.name, {})
        for line in plane.lines:
            if line.name == MODULE_LINE:
                target, describe = chip["modules"], False
            elif line.name == OP_LINE:
                target, describe = chip["ops"], True
            else:
                continue
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if not describe:
                    target.append((ev.name, start, dur))
                    continue
                name, container = describe_op(ev.name)
                if not container:
                    scope = scope_of(tf_ops.get(ev.name, "")) if tf_ops else None
                    target.append((name, start, dur, scope))
        chips[plane.name] = chip
    return {"chips": chips, "lines": seen}


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?.*?\)? ?([a-z\-]+)\(")
#: operations outside every program's module event
NO_PROGRAM = "(no program)"
#: operations that only contain others (a scan's loop, a branch): their time
#: is their children's, so they stay out of the ranking
CONTAINERS = ("while", "conditional", "call")


def describe_op(name: str) -> tuple[str, bool]:
    """The profiler names a device operation by its whole HLO instruction.
    Returns (``fusion.300_bf16_4096_16_8_128__fusion``, the form of the
    ledger's PR 22 lines: name, result shape, opcode; whether it is a
    container)."""
    m = _HLO.match(name)
    if not m:
        return name.lstrip("%").split(" ")[0], False
    op, shape, opcode = m.group(1), m.group(2) or "", m.group(3)
    shape = re.sub(r"[^A-Za-z0-9]+", "_", shape).strip("_")
    return f"{op}_{shape}__{opcode}", opcode in CONTAINERS


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def module_base(name: str) -> str:
    """``jit__decode_block_fn(7423...)`` -> ``jit__decode_block_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_chip(chip: dict) -> dict:
    """One chip's share of the traced window."""
    ops = chip["ops"] or chip["modules"]
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": [], "programs": {},
                "program_scopes": {}}
    busy = _union([(s, s + d) for _n, s, d, *_ in ops])
    t0, t1 = busy[0][0], busy[-1][1]
    modules = sorted((s, s + d, module_base(n)) for n, s, d in chip["modules"])

    starts = [m[0] for m in modules]

    def module_at(t: float) -> str:
        """The program running at ``t``, or the next one to start."""
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and modules[i][1] >= t:
            return modules[i][2]
        return modules[i + 1][2] if i + 1 < len(modules) else "end"

    def module_before(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][2] if i >= 0 else "start"

    longest = sorted(
        ((b_start - a_end, a_end, b_start)
         for (_a, a_end), (b_start, _b) in zip(busy, busy[1:])),
        reverse=True,
    )[:5]
    gaps = [
        (f"{module_before(a_end)}_-_{module_at(b_start)}", length)
        for length, a_end, b_start in longest
    ]
    by_op: dict = defaultdict(float)
    program_scopes: dict = defaultdict(lambda: defaultdict(lambda: {"time_s": 0.0, "count": 0}))
    for n, start, d, *rest in ops:
        by_op[n] += d
        scope = rest[0] if rest else None  # a recording older than the scopes has none
        if scope is not None:
            i = bisect.bisect_right(starts, start) - 1
            inside = modules[i][2] if i >= 0 and start <= modules[i][1] else NO_PROGRAM
            row = program_scopes[inside][scope]
            row["time_s"] += d
            row["count"] += 1
    programs: dict = defaultdict(lambda: {"time_s": 0.0, "count": 0})
    for s, e, n in modules:
        programs[n]["time_s"] += e - s
        programs[n]["count"] += 1
    return {
        "busy_s": sum(b - a for a, b in busy),
        "window_s": t1 - t0,
        "ops": dict(by_op),
        "gaps": gaps,
        "programs": {k: dict(v) for k, v in programs.items()},
        "program_scopes": {
            p: {k: dict(v) for k, v in rows.items()} for p, rows in program_scopes.items()
        },
    }


def _add_scopes(total: dict, rows: dict, n: int) -> None:
    for scope, row in rows.items():
        out = total.setdefault(scope, {"time_s": 0.0, "count": 0.0})
        out["time_s"] += row["time_s"] / n
        out["count"] += row["count"] / n


def reduce_events(loaded: dict) -> dict:
    """Average the chips; rank operations and gaps. A share over 100% is a
    bug in the count, not a result."""
    per_chip = [reduce_chip(c) for c in loaded["chips"].values()]
    per_chip = [c for c in per_chip if c["window_s"] > 0]
    if not per_chip:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [],
                "programs": {}, "scopes": None, "program_scopes": {}, "chips": 0,
                "lines": loaded.get("lines", {})}
    n = len(per_chip)
    ops: dict = defaultdict(float)
    programs: dict = defaultdict(lambda: {"time_s": 0.0, "count": 0})
    gaps: list = []
    scopes: dict = {}
    program_scopes: dict = {}
    for c in per_chip:
        for p, rows in c["program_scopes"].items():
            _add_scopes(program_scopes.setdefault(p, {}), rows, n)
            _add_scopes(scopes, rows, n)
        for k, v in c["ops"].items():
            ops[k] += v / n
        for k, v in c["programs"].items():
            programs[k]["time_s"] += v["time_s"] / n
            programs[k]["count"] += v["count"] / n
        gaps.extend(c["gaps"])
    busy = sum(c["busy_s"] for c in per_chip) / n
    window = sum(c["window_s"] for c in per_chip) / n
    if busy > window * (1 + 1e-9):
        raise AssertionError(f"device busy {busy}s exceeds the traced window {window}s")
    return {
        "busy_s": busy,
        "window_s": window,
        "device_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda kv: -kv[1])[:5]],
        "programs": {k: dict(v) for k, v in programs.items()},
        # device seconds and operations by the innermost mtpu.* scope, the
        # rest under UNSCOPED, untruncated: they add up to the operations'
        # time, which is busy_s but for operations that overlap (asynchronous
        # copies). None where the trace carries no scope at all
        "scopes": scopes or None,
        "program_scopes": program_scopes,
        "chips": n,
        "lines": loaded.get("lines", {}),
    }


def sample(loaded: dict, seconds: float) -> dict:
    """The first ``seconds`` of each chip's events: a trace small enough to
    keep as a test's recording."""
    out = {}
    for plane, chip in loaded["chips"].items():
        starts = [e[1] for e in chip["ops"] + chip["modules"]]
        cut = min(starts, default=0.0) + seconds
        out[plane] = {k: [e for e in v if e[1] + e[2] <= cut] for k, v in chip.items()}
    return {"chips": out, "lines": loaded["lines"]}


def reduce_dir(trace_dir: str, sample_seconds: float = 0.0) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    loaded = load_xplane(paths[-1])
    reduced = reduce_events(loaded)
    if sample_seconds:
        reduced["sample"] = sample(loaded, sample_seconds)
    return reduced
