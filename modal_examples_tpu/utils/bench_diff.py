"""Bench regression detector: compare two ``BENCH_r*.json`` files
section-by-section and fail loudly past a threshold.

Every round's ``bench.py`` run leaves a structured JSON (headline tok/s
plus ``token_latency`` / ``scheduling`` / ``kv_cache`` / ``disagg`` /
``spec`` sections — docs/observability.md). This module diffs two of them
so a revalidation round lands with an automatic round-over-round
comparison instead of eyeballing: ``tpurun benchdiff OLD NEW`` (or
``benchmarks/bench_diff.py``) prints a per-metric table and exits nonzero
when any tracked metric regressed beyond the threshold.

Two comparison kinds:

- ``ratio`` metrics (throughputs, latencies) regress when the RELATIVE
  change in the bad direction exceeds the threshold;
- ``abs`` metrics (rates already in [0, 1], e.g. ``shed_rate``) regress on
  an ABSOLUTE change — a shed rate going 0.00 -> 0.15 is a regression no
  relative math can see.

jax-free by construction (``tpurun`` must not attach a chip to diff two
json files).
"""

from __future__ import annotations

import json
from pathlib import Path

DEFAULT_THRESHOLD = 0.10

#: tracked metrics: (dotted path into the bench json, lower_is_better,
#: comparison kind). Paths missing from EITHER file are skipped — configs
#: gain sections over rounds and a diff must not punish the older file.
METRICS: list[tuple[str, bool, str]] = [
    ("value", False, "ratio"),                       # headline tok/s
    ("token_latency.ttft.p50", True, "ratio"),
    ("token_latency.ttft.p95", True, "ratio"),
    ("token_latency.tpot.p50", True, "ratio"),
    ("token_latency.tpot.p95", True, "ratio"),
    ("scheduling.shed_rate", True, "abs"),
    ("disagg.migration_latency.p50", True, "ratio"),
    ("disagg.migration_latency.p95", True, "ratio"),
    ("spec.acceptance_rate", False, "abs"),
    # fused adaptive speculation (docs/speculative.md#series): harvested
    # tokens per fused round on the adaptive arm — the amortization
    # speculation buys; a drop means the controller stopped finding
    # profitable depth (or the fused round silently stopped accepting)
    ("spec.tokens_per_dispatch", False, "ratio"),
    # the "spec can never cost latency" escape hatch: spec-off TPOT p95
    # over adaptive TPOT p95 on the mixed-acceptance A/B — falling below
    # ~1 means adaptivity started taxing the hostile half of the traffic
    ("spec.adaptive_vs_off_tpot_p95", False, "ratio"),
    ("kv_cache.bytes_per_slot", True, "ratio"),
    # stall-free admission (docs/scheduling.md): the budgeted arm's
    # interactive-stream tail latency under long-prompt interference
    ("interference.budgeted.tpot_p95", True, "ratio"),
    # closed fleet loop (docs/fleet.md): the autoscaled arm's goodput and
    # client-observed p99 TPOT at the pinned fleet's saturation knee — a
    # regression here means the autoscaler stopped absorbing the load the
    # single replica cannot serve
    ("fleet.goodput", False, "ratio"),
    ("fleet.p99_tpot_at_knee", True, "ratio"),
    # fleet-wide shared prefix store (docs/prefix_store.md): a COLD
    # replica's TTFT tail over a shared-prefix corpus another replica
    # already spilled — a regression means cross-replica promotion
    # stopped paying and cold replicas recompute prefills again
    ("fleet.shared_prefix_ttft_p95", True, "ratio"),
    # in-flight failover (docs/failover.md): the client-observed takeover
    # tail — how long a stream stalls when its replica dies before a
    # healthy peer resumes it token-identically
    ("failover.takeover_latency.p95", True, "ratio"),
    # gray-failure recovery (docs/health.md): the end-to-end tail from a
    # SILENT wedge (no crash, no error) to every affected stream resumed
    # on a healthy peer — detection by progress watermarks plus the
    # failover takeover; a regression means hangs live longer
    ("recovery.time_to_mitigate.p95", True, "ratio"),
    # hot-path overhead (docs/observability.md#hot-path-profiling): the
    # host share of serving time and the scheduler-tick tail from the
    # profiler's `overhead` section. host_fraction is a 0..1 rate (abs
    # comparison, like shed_rate); a regression in either means the engine
    # got chattier per token — the exact lever the rolled
    # decode loop exists to shrink, so it must fail the gate loudly.
    ("overhead.host_fraction", True, "abs"),
    ("overhead.tick_p95", True, "ratio"),
    # roofline utilization (docs/observability.md#roofline-and-usage-
    # accounting): achieved-vs-peak fractions are 0..1 rates (abs, like
    # shed_rate); per-chip tok/s is the TP-normalized headline — a drop
    # means the mesh stopped paying for itself
    ("utilization.mfu", False, "abs"),
    ("utilization.mbu", False, "abs"),
    ("utilization.tokens_per_second_per_chip", False, "ratio"),
]

#: identity keys that make two bench jsons comparable AT ALL: a CPU run
#: diffed against a TPU run (or two different chips) produces nonsense
#: verdicts for every hardware-relative metric, so the diff refuses
#: instead of printing a table that looks authoritative.
IDENTITY_KEYS = ("backend",)


def identity_mismatches(old: dict, new: dict) -> list[str]:
    """Human-readable identity disagreements between two bench jsons.
    Keys absent from either side are not mismatches (older files predate
    them); only a present-and-different value disqualifies."""
    out = []
    for key in IDENTITY_KEYS:
        ov, nv = old.get(key), new.get(key)
        if ov is not None and nv is not None and ov != nv:
            out.append(f"{key}: {ov!r} != {nv!r}")
    return out


def load_bench(path: str | Path) -> dict:
    """Read one bench json — either the raw line ``bench.py`` prints or
    the driver's ``BENCH_r*.json`` wrapper (whose ``parsed`` key holds
    the same object)."""
    doc = json.loads(Path(path).read_text())
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a bench json object")
    return doc


def _get(doc: dict, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def compare(
    old: dict, new: dict, threshold: float = DEFAULT_THRESHOLD
) -> list[dict]:
    """Rows for every tracked metric present in BOTH files, plus one row
    per shared ``all_configs`` entry. Each row: ``{metric, old, new,
    delta, lower_is_better, regressed}`` — ``delta`` is relative for
    ratio metrics, absolute for rate metrics."""
    rows: list[dict] = []

    def add(metric: str, ov, nv, lower: bool, kind: str) -> None:
        if ov is None or nv is None:
            return
        if kind == "ratio" and ov == 0:
            # a zero baseline makes relative math meaningless: ANY
            # appearance in the bad direction regresses (0 -> 50ms
            # migration p95 must not pass a 10% relative gate), rendered
            # as an absolute delta
            delta = nv - ov
            kind = "abs"
            worse = delta > 0 if lower else delta < 0
            regressed = bool(worse and abs(delta) > 1e-12)
        else:
            delta = nv - ov if kind == "abs" else (nv - ov) / abs(ov)
            worse = delta > 0 if lower else delta < 0
            regressed = bool(worse and abs(delta) > threshold)
        rows.append({
            "metric": metric,
            "old": ov,
            "new": nv,
            "delta": delta,
            "kind": kind,
            "lower_is_better": lower,
            "regressed": regressed,
        })

    for dotted, lower, kind in METRICS:
        add(dotted, _get(old, dotted), _get(new, dotted), lower, kind)
    old_cfgs = old.get("all_configs") or {}
    new_cfgs = new.get("all_configs") or {}
    for cfg in sorted(set(old_cfgs) & set(new_cfgs)):
        ov, nv = old_cfgs[cfg], new_cfgs[cfg]
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            add(f"all_configs.{cfg}", ov, nv, False, "ratio")
    return rows


def render(rows: list[dict]) -> str:
    lines = [
        f"{'METRIC':<34} {'OLD':>12} {'NEW':>12} {'DELTA':>9}  VERDICT"
    ]
    for r in rows:
        delta = (
            f"{r['delta'] * 100:+8.1f}%"
            if r["kind"] == "ratio"
            else f"{r['delta']:+9.4f}"
        )
        verdict = "REGRESSED" if r["regressed"] else (
            "improved"
            if (r["delta"] < 0) == r["lower_is_better"] and r["delta"] != 0
            else "ok"
        )
        lines.append(
            f"{r['metric']:<34} {r['old']:>12.4f} {r['new']:>12.4f} "
            f"{delta:>9}  {verdict}"
        )
    return "\n".join(lines)


def run_diff(argv: list[str]) -> int:
    """CLI body shared by ``tpurun benchdiff`` and
    ``benchmarks/bench_diff.py``: 0 = no regression, 1 = regressed, 2 =
    usage/read error."""
    usage = (
        "usage: tpurun benchdiff OLD.json NEW.json "
        f"[--threshold PCT (default {DEFAULT_THRESHOLD * 100:.0f})] "
        "[--allow-backend-mismatch]"
    )
    threshold = DEFAULT_THRESHOLD
    args = list(argv)
    allow_mismatch = "--allow-backend-mismatch" in args
    if allow_mismatch:
        args.remove("--allow-backend-mismatch")
    if "--threshold" in args:
        i = args.index("--threshold")
        if i + 1 >= len(args):
            print(usage)
            return 2
        try:
            threshold = float(args[i + 1]) / 100.0
        except ValueError:
            print(usage)
            return 2
        args = args[:i] + args[i + 2:]
    if len(args) != 2:
        print(usage)
        return 2
    try:
        old, new = load_bench(args[0]), load_bench(args[1])
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"benchdiff: {e}")
        return 2
    mismatches = identity_mismatches(old, new)
    if mismatches:
        for m in mismatches:
            print(f"benchdiff: HARDWARE MISMATCH — {m}")
        if not allow_mismatch:
            print(
                "benchdiff: refusing to compare runs from different "
                "hardware (every hardware-relative verdict would be "
                "nonsense); pass --allow-backend-mismatch to override"
            )
            return 2
        print(
            "benchdiff: --allow-backend-mismatch set — verdicts below "
            "compare DIFFERENT hardware and are not regressions"
        )
    rows = compare(old, new, threshold)
    if not rows:
        print("benchdiff: no comparable metrics between the two files")
        return 2
    print(render(rows))
    regressed = [r for r in rows if r["regressed"]]
    if regressed:
        print(
            f"\n{len(regressed)} metric(s) regressed beyond "
            f"{threshold * 100:.0f}%: "
            + ", ".join(r["metric"] for r in regressed)
        )
        return 1
    print(f"\nno regressions beyond {threshold * 100:.0f}%")
    return 0
