"""The rooflines' shared half: the table of peaks, the least time the chip
could take for a given work, and the share of it. The operations and bytes
themselves (of one decode step, of a prefill, of the work under a named
scope) come from the configuration's fields alone and are counted by its
family's file (``families/<family>.py``).
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks_for(device_kind: str) -> dict:
    """The published peaks of the device JAX reports. An unknown device is
    an error, not a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def roofline_pct(work: dict | list[dict], measured_s: float, peaks: dict) -> float:
    """A share over 100% is a bug in the count, not a result. A list is of
    phases that cannot overlap (prefill calls, decode steps): their least
    times add."""
    works = work if isinstance(work, list) else [work]
    least = sum(least_seconds(w, peaks)[0] for w in works)
    pct = 100.0 * least / measured_s
    if pct > 100.0:
        raise AssertionError(
            f"roofline share {pct:.1f}% > 100%: {work} against {measured_s}s measured"
        )
    return pct
