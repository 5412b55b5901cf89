"""The scheduler's own spans and counters, as the program exports them on
/metrics (deltas over the window), and the trace's table of programs.

The program partitions every busy scheduler tick into phases
(``mtpu_tick_phase_seconds{phase}``), counts the seconds in which it had
nothing on the device while a request waited
(``mtpu_device_starved_seconds_total{phase}``), stamps admission and the
first token of every request, counts token positions at each prefill
dispatch, and tells a dispatch that built its program from one that found
it compiled. A reader returns None where the program exports no such series
(a commit from before these existed), and the result line leaves it out.
"""

import json
import re
from pathlib import Path

import stats

TICK = "mtpu_tick_phase_seconds"
STARVED = "mtpu_device_starved_seconds_total"
PROGRAMS = json.loads((Path(__file__).parent / "programs.json").read_text())


def _exported(run, name):
    return name in run.counters_close


def _window_s(run):
    return run.times["window_close"] - run.times["window_open"]


def tick_host_ms(run):
    """Host time of a busy scheduler tick: every phase but ``harvest`` (the
    blocking device read), over the busy ticks of the window."""
    if not _exported(run, TICK + "_sum"):
        return None
    ticks = run.counter_delta(TICK + "_count", phase="total")
    if ticks <= 0:
        return None
    host = (
        run.counter_delta(TICK + "_sum")
        - run.counter_delta(TICK + "_sum", phase="total")
        - run.counter_delta(TICK + "_sum", phase="harvest")
    )
    return 1000.0 * host / ticks


def harvest_wait_pct(run):
    """Share of the window the scheduler thread is blocked on a device
    read: the device, not the host, sets the pace."""
    if not _exported(run, TICK + "_sum"):
        return None
    return 100.0 * run.counter_delta(TICK + "_sum", phase="harvest") / _window_s(run)


def starved_pct(run):
    """Share of the window with nothing dispatched and unharvested while a
    request was queued or running: a lower bound on ``device_idle_pct``."""
    if not _exported(run, STARVED):
        return None
    return 100.0 * run.counter_delta(STARVED) / _window_s(run)


def starved_by_phase(run):
    """phase -> seconds starved in the window: no metric of the manifest,
    a table for ``probe.py --dump`` (PERF.md section 5)."""
    if not _exported(run, STARVED):
        return None
    phases = {lab.get("phase") for lab, _v in run.counters_close.get(STARVED, [])}
    return {p: run.counter_delta(STARVED, phase=p) for p in sorted(phases)}


def tick_by_phase(run):
    """phase -> seconds of the scheduler thread in the window, and the busy
    ticks under ``ticks``: as ``starved_by_phase_s``, a table for the dump."""
    if not _exported(run, TICK + "_sum"):
        return None
    phases = {lab.get("phase") for lab, _v in run.counters_close.get(TICK + "_sum", [])}
    out = {p: run.counter_delta(TICK + "_sum", phase=p) for p in sorted(phases)}
    out["ticks"] = run.counter_delta(TICK + "_count", phase="total")
    return out


def decode_step_gap_ms(run):
    """Dispatch-to-dispatch time of the decode blocks over the decode
    steps they ran: what a step costs a running request, prefill calls
    that came between included. Hold it against ``decode_dev_ms``."""
    steps = run.counter_delta("mtpu_decode_steps_total")
    if steps <= 0 or not _exported(run, "mtpu_decode_stall_seconds_sum"):
        return None
    return 1000.0 * run.counter_delta("mtpu_decode_stall_seconds_sum") / steps


def prefill_wait_p50_ms(run):
    """Admission to first token, median: the part of TTFT that lies behind
    the queue wait."""
    q = stats.histogram_quantile(
        run.histogram_delta("mtpu_engine_first_token_wait_seconds"), 0.5
    )
    return None if q is None else q * 1000.0


def prefill_useful_pct(run):
    """Prompt tokens that needed computing over the token positions the
    prefill programs computed (padding rows and columns, and prompt tokens
    that sat on cached pages, included)."""
    name = "mtpu_prefill_positions_total"
    computed = run.counter_delta(name, kind="computed")
    if not _exported(run, name) or computed <= 0:
        return None
    return 100.0 * run.counter_delta(name, kind="needed") / computed


def compiles_in_window(run):
    """Dispatches inside the window that built their program. 0 in a sound
    run: the warm-up compiled every shape."""
    name = "mtpu_compiles_total"
    if not any(lab.get("cache") == "miss" for lab, _v in run.counters_close.get(name, [])):
        return None
    return run.counter_delta(name, cache="miss")


def helper_programs_per_block(run):
    """Executions of compiled programs that are neither a decode nor a
    prefill program (the one-operation helpers the host path runs eagerly:
    key splits, concatenations, slices), per decode-block call, in the
    traced part."""
    if not run.trace or not run.trace.get("programs"):
        return None
    known = [re.compile(p) for p in PROGRAMS.values()]
    decode = re.compile(PROGRAMS["decode"])
    blocks = helpers = 0.0
    for name, v in run.trace["programs"].items():
        if decode.search(name):
            blocks += v["count"]
        elif not any(p.search(name) for p in known):
            helpers += v["count"]
    return helpers / blocks if blocks else None


def compile_s(run):
    """Seconds spent in dispatches that built (or loaded from the compile
    cache) their program, from process start until the window opened: the
    part of ``warmup_s`` and of boot that is programs."""
    series = run.counters_open.get("mtpu_compile_seconds_sum")
    if not series:
        return None
    return sum(v for _lab, v in series)


METRICS = {
    "tick_host_ms": tick_host_ms, "harvest_wait_pct": harvest_wait_pct,
    "starved_pct": starved_pct, "decode_step_gap_ms": decode_step_gap_ms,
    "prefill_wait_p50_ms": prefill_wait_p50_ms,
    "prefill_useful_pct": prefill_useful_pct,
    "compiles_in_window": compiles_in_window,
    "helper_programs_per_block": helper_programs_per_block,
    "compile_s": compile_s,
    # tables for ``probe.py --dump``, in no manifest
    "starved_by_phase_s": starved_by_phase, "tick_by_phase_s": tick_by_phase,
}
