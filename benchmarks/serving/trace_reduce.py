"""From the profiler's trace to numbers: busy union, idle gaps, op ranking,
time per compiled program.

The program has no ``jax.named_scope`` and no ``TraceAnnotation``, so device
time is attributed by what the profiler prints on its own: the XLA module of
each compiled program (``jit__decode_block_fn``, ``jit__prefill_and_sample``,
``jit_prefill_chunk``) and the XLA operation names inside it. The reduction
works on plain tuples, so the tests feed it a small recorded trace; only
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


def load_xplane(path: str) -> dict:
    """{"chips": {plane name: {"modules": [(name, start_s, dur_s)],
    "ops": [(name, start_s, dur_s)]}}, "lines": what the file holds}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: dict = {}
    seen: dict = {}
    for plane in data.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if not plane.name.startswith("/device:TPU:"):
            continue
        chip = {"modules": [], "ops": []}
        for line in plane.lines:
            if line.name == MODULE_LINE:
                target, describe = chip["modules"], False
            elif line.name == OP_LINE:
                target, describe = chip["ops"], True
            else:
                continue
            for ev in line.events:
                name = ev.name
                if describe:
                    name, container = describe_op(name)
                    if container:
                        continue
                target.append((name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        chips[plane.name] = chip
    return {"chips": chips, "lines": seen}


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?.*?\)? ?([a-z\-]+)\(")
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
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "gaps": [], "programs": {}}
    busy = _union([(s, s + d) for _n, s, d in ops])
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
    for n, _s, d in ops:
        by_op[n] += d
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
    }


def reduce_events(loaded: dict) -> dict:
    """Average the chips; rank operations and gaps. A share over 100% is a
    bug in the count, not a result."""
    per_chip = [reduce_chip(c) for c in loaded["chips"].values()]
    per_chip = [c for c in per_chip if c["window_s"] > 0]
    if not per_chip:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [],
                "programs": {}, "chips": 0, "lines": loaded.get("lines", {})}
    n = len(per_chip)
    ops: dict = defaultdict(float)
    programs: dict = defaultdict(lambda: {"time_s": 0.0, "count": 0})
    gaps: list = []
    for c in per_chip:
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
        "chips": n,
        "lines": loaded.get("lines", {}),
    }


def sample(loaded: dict, seconds: float) -> dict:
    """The first ``seconds`` of each chip's events: a trace small enough to
    keep as a test's recording."""
    out = {}
    for plane, chip in loaded["chips"].items():
        starts = [s for _n, s, _d in chip["ops"] + chip["modules"]]
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
