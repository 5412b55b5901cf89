"""Model programs, kernels and the device, from the profiler's trace.

A compiled program is found by the name of its XLA module (patterns in
``programs.json``); its time is the device time of its module events in the
traced part of the window. Roofline shares hold that time against the least
the chip could take for the work the algorithm needs (``work_model.py``).
"""

import json
import re
from pathlib import Path

import work_model

PATTERNS = json.loads((Path(__file__).parent / "programs.json").read_text())


def _program(run, kind):
    """(device seconds, calls) of the programs of one kind in the trace."""
    if not run.trace:
        return None
    pat = re.compile(PATTERNS[kind])
    hits = [v for k, v in run.trace["programs"].items() if pat.search(k)]
    if not hits:
        return None
    return sum(v["time_s"] for v in hits), sum(v["count"] for v in hits)


def decode_dev_ms(run):
    got = _program(run, "decode")
    if not got or not got[1]:
        return None
    steps = got[1] * int(run.device["decode_block"])
    return 1000.0 * got[0] / steps


def prefill_dev_pct(run):
    got = _program(run, "prefill")
    if not got:
        return None
    return 100.0 * got[0] / run.trace["window_s"]


def _mean_context(run):
    """Mean tokens in the context of a sequence while it decodes."""
    done = [o for o in run.scored if o.ok and o.prompt_tokens]
    if not done:
        return None
    return sum(o.prompt_tokens + o.n_out / 2.0 for o in done) / len(done)


def decode_roofline(run):
    step_ms, batch, context = decode_dev_ms(run), run.decode_batch_mean(), _mean_context(run)
    if step_ms is None or batch is None or context is None:
        return None
    work = work_model.decode_step(run.config, batch, batch * context)
    return work_model.roofline_pct(work, step_ms / 1000.0, work_model.peaks_for(run.device["kind"]))


def prefill_roofline(run):
    """The window's prefilled prompts against the device time the prefill
    programs took: their share of the traced part, over the whole window."""
    got = _program(run, "prefill")
    window = run.times["window_close"] - run.times["window_open"]
    t0, t1 = run.times["window_open"], run.times["window_close"]
    prompts = [
        e["n_prompt"] for e in run.engine_log.values()
        if e.get("first_token_at") is not None and t0 <= e["first_token_at"] < t1
    ]
    if not got or not got[0] or not prompts:
        return None
    share = got[0] / run.trace["window_s"]
    calls = got[1] * window / run.trace["window_s"]
    work = work_model.prefill(run.config, prompts, calls)
    return work_model.roofline_pct(work, share * window, work_model.peaks_for(run.device["kind"]))


def device_idle_pct(run):
    if not run.trace or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def hbm_peak_pct(run):
    if not run.device.get("memory_limit_bytes"):
        return None
    return 100.0 * run.device["memory_peak_bytes"] / run.device["memory_limit_bytes"]


METRICS = {
    "decode_dev_ms": decode_dev_ms, "prefill_dev_pct": prefill_dev_pct,
    "decode_roofline": decode_roofline, "prefill_roofline": prefill_roofline,
    "device_idle_pct": device_idle_pct, "hbm_peak_pct": hbm_peak_pct,
}
