"""Set-up, timed from inside the program: the container's boot by phase and
what its program builds are made of.

The container writes its one boot as phase spans on CLOCK_MONOTONIC, the
clock ``run.py``'s ``times`` are on (``mtpu_boot_phase_seconds{phase}``:
``spawn``, ``attach``, ``restore``, ``enter`` partition it from the
supervisor's ``Popen`` to ``ready``; ``engine_init``, ``kv_alloc``,
``server_start`` nest inside ``enter``; ``mtpu_boot_mark_seconds{mark}``
holds the two ends), and splits every program build where JAX does the work
(``mtpu_compile_phase_seconds_total{program,kind}``, kind = ``trace``,
``lower``, ``xla_compile``, ``cache_load``; ``program="(eager)"`` is work
outside any dispatch; ``mtpu_compile_cache_total{result}``). Everything here
reads the scrape at the window's opening and ``run.times``. A reader returns
None where the program exports no such series (a commit from before these
existed), and the result line leaves it out.

What the build readers do not say. The listener is registered by the
engine's profiler, so they cover the engine's construction to the window's
opening, not the process's start: what JAX builds before (the seeded weights'
generator) is in ``boot_enter_s`` and in no kind. A program's kinds can pass
its part of ``compile_s``: JAX also builds under dispatches counted as hits
(the eager sampler's helpers, ``program="sample"``). And on an empty cache
directory ``compile_cache_hit_pct`` is not near 0: a program lowered twice in
one process finds what the process wrote; ``compile_xla_s`` against
``compile_cache_load_s`` tells a cold boot from a warm one.

No manifest names these readers yet, so they reach a reader through
``run.py --dump`` (``all_metrics``), as ``tick_by_phase_s`` does.
"""

BOOT_PHASE = "mtpu_boot_phase_seconds"
BOOT_MARK = "mtpu_boot_mark_seconds"
COMPILE_PHASE = "mtpu_compile_phase_seconds_total"
COMPILE_CACHE = "mtpu_compile_cache_total"
#: the phases that partition a boot; the other labels of BOOT_PHASE nest
TOP_LEVEL = ("spawn", "attach", "restore", "enter")
#: what ``loadgen`` waits before the ramp starts (``run.py``: ``t_ramp``)
RAMP_LEAD_S = 0.25


def _labelled(run, name, **labels):
    """The sum of a family's series with these labels at the window's
    opening; None where the program exports no such family."""
    series = run.counters_open.get(name)
    if not series:
        return None
    return sum(v for lab, v in series if all(lab.get(k) == w for k, w in labels.items()))


def _phase(phase):
    def read(run):
        """Seconds of the boot in this phase (0 where it was never entered:
        a container that attaches no chip, a boot with no snapshot)."""
        return _labelled(run, BOOT_PHASE, phase=phase)
    return read


def boot_pre_spawn_s(run):
    """Process start until the supervisor's ``Popen``: the load generator's
    imports, ``App.run()`` and ``serve()`` up to the spawn."""
    spawned = _labelled(run, BOOT_MARK, mark="spawned")
    return None if spawned is None else spawned - run.times["process_start"]


def boot_unnamed_s(run):
    """``boot_s`` less what is named: ``ready`` until ``/health`` answered,
    and anything no span covers."""
    pre = boot_pre_spawn_s(run)
    if pre is None or _labelled(run, BOOT_PHASE) is None:
        return None
    named = sum(_labelled(run, BOOT_PHASE, phase=p) for p in TOP_LEVEL)
    return run.times["health_ok"] - run.times["process_start"] - pre - named


def warmup_requests_s(run):
    """The warm-up requests alone: ``warmup_s`` less the mix's ramp. The
    harness's own clock and nothing of the program's, so any commit reads it."""
    return (
        run.times["window_open"] - run.times["warmup_start"]
        - float(run.mix["ramp_s"]) - RAMP_LEAD_S
    )


def compile_trace_lower_s(run):
    """Python's share of the builds: tracing and lowering to MLIR."""
    trace = _labelled(run, COMPILE_PHASE, kind="trace")
    return None if trace is None else trace + _labelled(run, COMPILE_PHASE, kind="lower")


def compile_xla_s(run):
    return _labelled(run, COMPILE_PHASE, kind="xla_compile")


def compile_cache_load_s(run):
    return _labelled(run, COMPILE_PHASE, kind="cache_load")


def compile_eager_s(run):
    """Builds outside any dispatch, all kinds: the one-operation helpers
    the host path runs eagerly."""
    return _labelled(run, COMPILE_PHASE, program="(eager)")


def compile_cache_hit_pct(run):
    """The persistent compile cache's hits over its answers."""
    answers = _labelled(run, COMPILE_CACHE)
    if not answers:
        return None
    return 100.0 * _labelled(run, COMPILE_CACHE, result="hit") / answers


def boot_by_phase(run):
    """phase or nested mark -> seconds: a table for ``probe.py --dump``."""
    series = run.counters_open.get(BOOT_PHASE)
    if not series:
        return None
    return {lab.get("phase"): v for lab, v in series}


def compile_by_program(run):
    """program -> kind -> seconds, beside the wall seconds of its building
    dispatches (``built_s``: its part of ``compile_s``) and the count of
    builds made ahead of any dispatch (``ahead``); under ``(window)`` the
    seconds by kind inside the window. Which build grew, from a dump."""
    series = run.counters_open.get(COMPILE_PHASE)
    if not series:
        return None
    out: dict = {}
    for lab, v in series:
        out.setdefault(lab.get("program"), {})[lab.get("kind")] = v
    for lab, v in run.counters_open.get("mtpu_compile_seconds_sum", []):
        out.setdefault(lab.get("program"), {})["built_s"] = v
    for lab, v in run.counters_open.get("mtpu_compiles_total", []):
        if lab.get("cache") == "ahead":
            out.setdefault(lab.get("program"), {})["ahead"] = v
    # what JAX built inside the window, every program: in a sound run helper
    # programs met for the first time, and nothing else
    out["(window)"] = {
        kind: run.counter_delta(COMPILE_PHASE, kind=kind)
        for kind in sorted({lab.get("kind") for lab, _v in series})
    }
    return out


METRICS = {
    "boot_pre_spawn_s": boot_pre_spawn_s,
    "boot_spawn_s": _phase("spawn"), "boot_attach_s": _phase("attach"),
    "boot_enter_s": _phase("enter"), "boot_engine_init_s": _phase("engine_init"),
    "boot_unnamed_s": boot_unnamed_s, "warmup_requests_s": warmup_requests_s,
    "compile_trace_lower_s": compile_trace_lower_s, "compile_xla_s": compile_xla_s,
    "compile_cache_load_s": compile_cache_load_s, "compile_eager_s": compile_eager_s,
    "compile_cache_hit_pct": compile_cache_hit_pct,
    # tables for ``probe.py --dump``, in no manifest
    "boot_by_phase_s": boot_by_phase, "compile_by_program_s": compile_by_program,
}
