"""Tracing/profiling: the torch_profiling.py analog on jax.profiler.

Reference pattern (SURVEY.md §5.1): a generic ``profile`` Function wraps any
registered Function by name (app.registered_functions,
torch_profiling.py:131-135), runs it under the profiler with a warmup/active
schedule (:141-161), writes TensorBoard-compatible traces to a Volume
(:116,138-139), and returns a summary table (:164-167).

TPU translation: ``jax.profiler.trace`` emits XPlane traces readable by
TensorBoard's profile plugin / XProf and Perfetto; ``block_until_ready``
replaces the ``.cpu()`` host sync (:100).
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable


@dataclasses.dataclass
class ProfileResult:
    wall_s: float
    warmup_s: float
    iterations: int
    per_iter_s: float
    trace_dir: str | None

    def summary(self) -> str:
        lines = [
            f"iterations:     {self.iterations}",
            f"warmup:         {self.warmup_s * 1e3:.2f} ms",
            f"total:          {self.wall_s * 1e3:.2f} ms",
            f"per-iteration:  {self.per_iter_s * 1e3:.3f} ms",
        ]
        if self.trace_dir:
            lines.append(f"trace:          {self.trace_dir} (TensorBoard/XProf)")
        return "\n".join(lines)


def profile_call(
    fn: Callable,
    *args,
    warmup: int = 2,
    iterations: int = 10,
    trace_dir: str | Path | None = None,
    **kwargs,
) -> tuple[Any, ProfileResult]:
    """Run ``fn`` under the TPU profiler with a warmup/active schedule.

    Returns (last result, ProfileResult). When ``trace_dir`` is set, the
    active iterations are captured as an XPlane trace for TensorBoard's
    profile plugin.
    """
    import jax

    t0 = time.perf_counter()
    out = None
    for _ in range(max(warmup, 0)):
        out = jax.block_until_ready(fn(*args, **kwargs))
    warmup_s = time.perf_counter() - t0

    ctx = None
    if trace_dir is not None:
        trace_dir = str(trace_dir)
        ctx = jax.profiler.trace(trace_dir)
        ctx.__enter__()
    t0 = time.perf_counter()
    try:
        for _ in range(iterations):
            out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    wall = time.perf_counter() - t0
    return out, ProfileResult(
        wall_s=wall,
        warmup_s=warmup_s,
        iterations=iterations,
        per_iter_s=wall / max(iterations, 1),
        trace_dir=str(trace_dir) if trace_dir else None,
    )


def make_profile_function(app, trace_volume=None, mount_path: str = "/traces"):
    """Register a generic ``profile`` Function on ``app`` that wraps any of
    the app's registered functions by name — the torch_profiling.py:131-139
    pattern, with traces written to a Volume for a hosted TensorBoard.

    Call AFTER the functions you want profilable are registered: the wrapper
    snapshots their raw callables (the App object itself holds live run
    state and never crosses the container boundary).
    """

    volumes = {mount_path: trace_volume} if trace_volume is not None else {}
    targets = {n: f.raw_f for n, f in app.registered_functions.items()}

    @app.function(name="profile", volumes=volumes, timeout=600)
    def profile(function_name: str, *args, iterations: int = 10, **kwargs):
        fn = targets.get(function_name)
        if fn is None:
            raise KeyError(
                f"{function_name!r} is not registered; have {sorted(targets)}"
            )
        trace_dir = (
            f"{mount_path}/{function_name}-{int(time.time())}" if volumes else None
        )
        out, result = profile_call(
            fn, *args, iterations=iterations, trace_dir=trace_dir, **kwargs
        )
        if trace_volume is not None:
            trace_volume.commit()
        print(result.summary())
        return dataclasses.asdict(result)

    return profile


def export_call_trace(call_id: str, out_path: str | Path) -> dict:
    """Write one framework call's lifecycle trace as Chrome-trace/Perfetto
    JSON next to wherever your XPlane traces go — ``jax.profiler.trace``
    answers "what did the chip do", this answers "what did the *framework*
    do around it" (queue/boot/dispatch/execute spans), in the same UI
    (ui.perfetto.dev / chrome://tracing). ``call_id`` is the ``in-...`` id
    from ``FunctionCall.call_id``; raises KeyError when no such trace
    exists. Same converter as ``tpurun trace <id> --perfetto``."""
    from ..observability.export import export_chrome_trace

    doc = export_chrome_trace(call_id, out_path)
    if doc is None:
        raise KeyError(f"no trace recorded for call {call_id!r}")
    return doc


def device_memory_stats() -> dict:
    """HBM usage per device — the nvidia-smi replacement
    (install_cuda.py:17-20 analog)."""
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[str(d)] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
    return out
