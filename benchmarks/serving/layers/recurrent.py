"""Per-slot recurrent state (a Mamba-2 layer's): what the program adds for
such a model, read where it writes it.

``ssm_step_dev_pct``, ``ssm_scan_dev_pct`` and ``ssm_proj_dev_pct`` are the
shares of the traced device time under the scopes ``mtpu.ssm_step`` (decode:
the convolution's shift, one state update, the output), ``mtpu.ssm_scan``
(prefill: the causal convolution and the chunked scan) and ``mtpu.ssm_proj``
(the mixer's ``in_proj``, gated norm and ``out_proj``, both phases).
``ssm_step_roofline`` and ``ssm_scan_roofline`` hold a scope's device time
against the least the chip could take for the work the window needed under
it, as the configuration's family counts it (``SCOPE_WORK``): for the step,
the state of the decode steps' live sequences read and written once; for the
scan, the chunked form's flops over the window's prefilled tokens. Each is
handed its own phase only: the step the decode steps, the scan the prefill
calls. ``state_rows_live_pct`` is the share of the slot rows the decode
steps read and wrote that belonged to a running sequence
(``mtpu_state_rows_total{kind}``: ``live`` / ``stepped``, counted at each
block dispatch).

A program that writes no such scope or series (a commit before them, a model
without per-slot state) reads None, never 0, and the result line leaves the
metric out.
"""

import manifest
import work_model

ROWS = "mtpu_state_rows_total"


def _scope(run, part):
    """(device seconds under ``mtpu.<part>``, of all operations), or None."""
    scopes = (run.trace or {}).get("scopes")
    if not scopes or f"mtpu.{part}" not in scopes:
        return None
    return scopes[f"mtpu.{part}"]["time_s"], sum(row["time_s"] for row in scopes.values())


def _dev_pct(part):
    def reader(run):
        got = _scope(run, part)
        return 100.0 * got[0] / got[1] if got and got[1] else None
    return reader


def _work_of(run, part):
    table = getattr(manifest.load_family(run.config), "SCOPE_WORK", {})
    return table.get(f"mtpu.{part}")


def _scale(run):
    """The window over its traced part."""
    return (run.times["window_close"] - run.times["window_open"]) / run.trace["window_s"]


def _roofline(run, traced_s, work):
    """The window's ``work`` under a scope against the scope's traced seconds."""
    if not work:
        return None
    return work_model.roofline_pct(
        work, traced_s * _scale(run), work_model.peaks_for(run.device["kind"])
    )


def ssm_step_roofline(run):
    got, work_of = _scope(run, "ssm_step"), _work_of(run, "ssm_step")
    decode, batch = (run.program("decode"), run.decode_batch_mean()) if got else (None, None)
    if not got or not got[0] or work_of is None or not decode or not batch:
        return None
    steps = decode[1] * int(run.device["decode_block"]) * _scale(run)
    return _roofline(run, got[0], work_of(run.config, batch * steps, steps))


def ssm_scan_roofline(run):
    got, work_of = _scope(run, "ssm_scan"), _work_of(run, "ssm_scan")
    prefill, prompts = (run.program("prefill"), run.prefilled_prompts()) if got else (None, None)
    if not got or not got[0] or work_of is None or not prefill or not prompts:
        return None
    work = work_of(run.config, float(sum(prompts)), prefill[1] * _scale(run))
    return _roofline(run, got[0], work)


def state_rows_live_pct(run):
    if ROWS not in run.counters_close:
        return None
    stepped = run.counter_delta(ROWS, kind="stepped")
    return 100.0 * run.counter_delta(ROWS, kind="live") / stepped if stepped > 0 else None


METRICS = {
    "ssm_step_dev_pct": _dev_pct("ssm_step"),
    "ssm_scan_dev_pct": _dev_pct("ssm_scan"),
    "ssm_proj_dev_pct": _dev_pct("ssm_proj"),
    "ssm_step_roofline": ssm_step_roofline,
    "ssm_scan_roofline": ssm_scan_roofline,
    "state_rows_live_pct": state_rows_live_pct,
}
