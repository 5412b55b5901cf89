"""Model programs, kernels and the device, from the profiler's trace.

A compiled program is found by the name of its XLA module (patterns in
``programs.json``); its time is the device time of its module events in the
traced part of the window. Roofline shares hold that time against the least
the chip could take (``work_model.py``) for the work the algorithm needs, as
the configuration's family counts it (``families/<family>.py``).
"""

import manifest
import work_model


def decode_dev_ms(run):
    got = run.program("decode")
    if not got or not got[1]:
        return None
    steps = got[1] * int(run.device["decode_block"])
    return 1000.0 * got[0] / steps


def prefill_dev_pct(run):
    got = run.program("prefill")
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
    work = manifest.load_family(run.config).decode_step(run.config, batch, batch * context)
    return work_model.roofline_pct(work, step_ms / 1000.0, work_model.peaks_for(run.device["kind"]))


def prefill_roofline(run):
    """The window's prefilled prompts against the device time the prefill
    programs took: their share of the traced part, over the whole window."""
    got = run.program("prefill")
    window = run.times["window_close"] - run.times["window_open"]
    prompts = run.prefilled_prompts()
    if not got or not got[0] or not prompts:
        return None
    share = got[0] / run.trace["window_s"]
    calls = got[1] * window / run.trace["window_s"]
    work = manifest.load_family(run.config).prefill(run.config, prompts, calls)
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
