"""Device time by the program's named scopes, from the profiler's trace.

Every operation the program traces under a ``jax.named_scope`` of its
``ops/scopes.py`` (``mtpu.attention``, ``mtpu.page_gather``, ...) carries the
scope in its metadata, and the trace reduction sums the device's operations
by the innermost one (``trace_reduce.py``: ``scopes``). ``<part>_dev_pct`` is
the share of the traced device time under ``mtpu.<part>``;
``<part>_roofline`` holds the scope's time against the least the chip could
take for the work the configuration's family counts under it
(``SCOPE_WORK`` of ``families/<family>.py``): the needed work, not what the
program happens to compute.

A scope that the trace does not show reads None, never 0: a program loaded
from a compile cache that a build without the scope filled carries that
build's metadata (JAX leaves metadata out of the cache's key).
"""

import manifest
import work_model


def _scope(run, part):
    """(device seconds under ``mtpu.<part>``, device seconds of all
    operations) of the traced part, or None."""
    scopes = (run.trace or {}).get("scopes")
    if not scopes or f"mtpu.{part}" not in scopes:
        return None
    total = sum(row["time_s"] for row in scopes.values())
    return scopes[f"mtpu.{part}"]["time_s"], total


def _dev_pct(part):
    def reader(run):
        got = _scope(run, part)
        return 100.0 * got[0] / got[1] if got and got[1] else None
    return reader


def _roofline(part):
    def reader(run):
        """The window's work under the scope (prefill calls and decode steps
        of the traced part, scaled to the window as ``prefill_roofline``
        does) against the scope's share of device time over the window."""
        got = _scope(run, part)
        work_of = manifest.load_family(run.config).SCOPE_WORK.get(f"mtpu.{part}")
        if not got or not got[0] or work_of is None:
            return None
        window = run.times["window_close"] - run.times["window_open"]
        scale = window / run.trace["window_s"]
        prompts = run.prefilled_prompts()
        prefill, decode = run.program("prefill"), run.program("decode")
        batch = run.decode_batch_mean()
        phases = []  # (tokens, program calls) over the window
        if prefill and prompts:
            phases.append((float(sum(prompts)), prefill[1] * scale))
        if decode and batch:
            steps = decode[1] * int(run.device["decode_block"]) * scale
            phases.append((batch * steps, steps))
        works = [w for w in (work_of(run.config, *phase) for phase in phases) if w]
        if not works:
            return None
        return work_model.roofline_pct(
            works, got[0] * scale, work_model.peaks_for(run.device["kind"])
        )
    return reader


METRICS = {
    "attention_dev_pct": _dev_pct("attention"),
    "page_gather_dev_pct": _dev_pct("page_gather"),
    "dense_mlp_dev_pct": _dev_pct("dense_mlp"),
    "expert_scan_dev_pct": _dev_pct("expert_scan"),
    "expert_scan_roofline": _roofline("expert_scan"),
}
