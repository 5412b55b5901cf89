"""Driver-contract tests: bench.py must print exactly one JSON line with the
required schema, and must degrade (not hang) when a model config fails."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_bench_emits_schema_json():
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={**os.environ, "BENCH_CPU": "1", "BENCH_MODEL": "tiny"},
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE line, got {len(lines)}: {lines}"
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in payload, payload
    assert payload["value"] > 0
    assert payload["unit"] == "tok/s"
    # phase-attributed latency: every BENCH_*.json carries p50/p95/p99 per
    # scheduler-tick phase from the observability histograms
    # (docs/observability.md)
    pl = payload.get("phase_latency")
    assert pl, payload
    some = pl.get("harvest") or pl.get("decode_dispatch")
    assert some and {"p50", "p95", "p99", "count"} <= set(some)
    # token-level serving latency (ISSUE-3): TTFT/TPOT p50/p95 + tokens/s
    # ride alongside phase_latency in every BENCH json
    tl = payload.get("token_latency")
    assert tl and "ttft" in tl and "tpot" in tl, payload
    for key in ("ttft", "tpot"):
        assert {"p50", "p95", "count"} <= set(tl[key]), tl
        assert tl[key]["p50"] <= tl[key]["p95"]
        assert tl[key]["count"] >= 1
    # scheduling telemetry (ISSUE-4): per-class admission queue-wait
    # quantiles + shed rate ride in every BENCH json
    sched = payload.get("scheduling")
    assert sched, payload
    assert {"queue_wait", "shed_rate", "sheds_total"} <= set(sched), sched
    dq = sched["queue_wait"].get("default")  # bench traffic is default-class
    assert dq and {"p50", "p95", "count"} <= set(dq), sched
    assert dq["p50"] <= dq["p95"]
    assert 0.0 <= sched["shed_rate"] <= 1.0
    assert sched["shed_rate"] == 0.0  # bench must never overload itself
    # KV-cache footprint (ISSUE-5): dtype-aware bytes + the slots-at-HBM
    # headroom figure ride in every BENCH json (int8 KV shows ~2x here)
    kv = payload.get("kv_cache")
    assert kv, payload
    assert {"dtype", "bytes", "bytes_per_slot", "max_slots_at_hbm"} <= set(kv)
    assert kv["dtype"] in ("bfloat16", "int8", "float32")
    assert kv["bytes"] > 0 and kv["bytes_per_slot"] > 0
    assert kv["max_slots_at_hbm"] > 0  # tiny model: plenty of HBM headroom
    assert payload["tokens_per_second"] == payload["value"]
    # hot-path overhead attribution (docs/observability.md#hot-path-
    # profiling): EVERY bench config's json carries the `overhead` section
    # — the profiler is on by default — with per-phase attribution
    # summing to ~the tick duration (cover ≤ 1 structurally: sequential
    # marks partition the tick) and a nonzero compile ledger. Structure
    # only — wall-clock DIRECTION lives behind the on-chip benchdiff gate.
    ov = payload.get("overhead")
    assert ov, payload
    assert {"ticks", "host_fraction", "tick_p50", "tick_p95", "detok_share",
            "attribution_cover", "phases", "compile_total_s",
            "compiles_n"} <= set(ov), ov
    assert ov["ticks"] >= 1
    assert 0.0 <= ov["host_fraction"] <= 1.0
    assert 0 < ov["tick_p50"] <= ov["tick_p95"]
    assert 0.0 <= ov["detok_share"] <= 1.0
    assert 0.8 <= ov["attribution_cover"] <= 1.0 + 1e-6
    # the full non-spec tick anatomy shows up under real traffic
    for phase in ("admit", "prefill_dispatch", "decode_dispatch", "harvest",
                  "detokenize", "accept"):
        assert phase in ov["phases"], (phase, ov["phases"])
        assert ov["phases"][phase]["p50"] <= ov["phases"][phase]["p95"]
    # nonzero compile ledger: at least the block + one prefill bucket built
    assert ov["compiles_n"] >= 2
    assert ov["compile_total_s"] > 0
    # flight recorder ride-along (docs/observability.md#metrics-history):
    # bench children default MTPU_TSDB=1, so the overhead section carries
    # the sampler's own cost next to the host-overhead numbers the sampler
    # must not move (benchdiff's existing overhead.* gates are the proof)
    ts = ov.get("tsdb")
    assert ts, ov
    assert {"samples", "series", "scrape_p50", "scrape_p95"} <= set(ts), ts
    assert ts["samples"] >= 1
    assert ts["series"] >= 1
    if ts["scrape_p95"] is not None:
        assert 0.0 <= ts["scrape_p50"] <= ts["scrape_p95"]
    # roofline utilization accounting (docs/observability.md#roofline-and-
    # usage-accounting): EVERY bench json carries a deterministic
    # `utilization` section — the work model is analytic, so it exists even
    # on CPU (the achieved fractions are tiny there, but the SHAPE and the
    # work-model constants are the contract benchdiff gates against)
    util = payload.get("utilization")
    assert util, payload
    assert {"mfu", "mbu", "bound", "tokens_per_second_per_chip",
            "generation", "chips", "per_phase", "work_model"} <= set(util)
    assert 0.0 <= util["mfu"] <= 1.5, util  # sanity roof, not a target
    assert 0.0 <= util["mbu"] <= 1.5, util
    assert util["bound"] in ("compute", "bandwidth")
    assert util["tokens_per_second_per_chip"] > 0
    assert util["chips"] >= 1
    for phase in ("prefill", "decode"):
        p = util["per_phase"][phase]
        assert {"flops", "bytes", "device_seconds", "mfu", "mbu"} <= set(p)
        assert p["flops"] > 0 and p["bytes"] > 0
        assert p["device_seconds"] > 0  # the clock brackets really ran
    wm = util["work_model"]
    assert wm["n_params"] > 0 and wm["weight_bytes"] > 0
    assert wm["kv_bytes_per_token"] > 0


@pytest.mark.slow
def test_bench_disagg_config_emits_disagg_section():
    """The two-replica disagg config must ride the same schema plus a
    ``disagg`` section: migration volume, latency quantiles, and the tiered
    prefix cache's hit mix (docs/disagg.md)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-disagg",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    disagg = payload.get("disagg")
    assert disagg, payload
    assert {"pages_migrated", "migration_bytes", "migrations",
            "migration_latency", "tier_hits", "tier_hit_rates"} <= set(disagg)
    assert disagg["pages_migrated"] > 0
    assert disagg["migrations"]["ok"] > 0
    # bench traffic must migrate cleanly, not limp through fallback
    assert disagg["migrations"]["fallback"] == 0
    lat = disagg["migration_latency"]
    assert lat and lat["p50"] <= lat["p95"] and lat["count"] > 0
    rates = disagg["tier_hit_rates"]
    assert all(0.0 <= v <= 1.0 for v in rates.values())


@pytest.mark.slow
def test_bench_chaos_config_emits_faults_section():
    """The chaos config must ride the same schema plus a ``faults``
    section: the seeded episode schedule runs after the measured traffic
    and the report — injected per point, recoveries, zero wedged — rides
    in the json (docs/faults.md). A failure-handling regression breaks the
    bench contract, not just the test suite."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-chaos",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    faults = payload.get("faults")
    assert faults, payload
    assert {"injected", "per_point", "recovered", "wedged",
            "points_missed", "invariants", "episodes"} <= set(faults)
    assert faults["wedged"] == 0
    assert faults["invariants"] == "ok"
    assert faults["points_missed"] == []
    assert faults["injected"] >= len(faults["per_point"]) >= 12
    assert faults["recovered"] > 0
    # the measured number itself stays fault-free
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_fleet_config_emits_fleet_section():
    """The fleet config must ride the same schema plus a ``fleet``
    section: the calibrated saturating open-loop sweep (pinned vs
    autoscaled arms), the knee, the scaled-fleet A/B, shed rate, and the
    scale events with their snapshot-restored warm boots (docs/fleet.md).
    ``fleet.goodput`` / ``fleet.p99_tpot_at_knee`` are what benchdiff
    gates round over round."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-fleet",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    fleet = payload.get("fleet")
    assert fleet, payload
    assert {"arrival", "capacity_rps", "rates", "knee_rps", "goodput",
            "p99_tpot_at_knee", "shed_rate", "ab", "sweep",
            "scale_events"} <= set(fleet)
    assert fleet["capacity_rps"] > 0
    assert len(fleet["rates"]) == 3
    assert fleet["goodput"] > 0
    assert 0.0 <= fleet["shed_rate"] <= 1.0
    # the sweep arms: every step terminal, nothing wedged
    for arm in ("pinned", "autoscaled"):
        steps = fleet["sweep"][arm]
        assert len(steps) == 3
        for s in steps:
            assert s["wedged"] == 0, (arm, s)
            assert s["offered"] >= s["completed"] + s["shed"] - 1
    # the saturating step must actually saturate the pinned replica
    assert fleet["sweep"]["pinned"][-1]["shed"] > 0
    # scale-out happened, via snapshot-restored warm boots, and the
    # idle tail scaled the fleet back to its floor
    ev = fleet["scale_events"]
    assert ev["up"] >= 1 and ev["warm_boots"] == ev["up"]
    assert fleet["scaled_back_to"] == 1
    ab = fleet["ab"]
    assert ab["scaled_out"] is True
    for side in ("pinned", "autoscaled"):
        assert {"goodput_rps", "shed_rate", "ttft_p99", "tpot_p99",
                "wedged"} <= set(ab[side])
        assert ab[side]["wedged"] == 0
    assert ab["improvement_goodput"] > 0
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_failover_config_emits_failover_section():
    """The failover config must ride the same schema plus a ``failover``
    section: streams killed mid-decode on one replica and
    checkpoint-resumed on another — client-observed takeover latency
    p50/p95, generated-prefix tokens replayed, and the exactness verdict
    (docs/failover.md). ``failover.takeover_latency.p95`` is what
    benchdiff gates round over round."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-failover",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    fo = payload.get("failover")
    assert fo, payload
    assert {"streams", "failovers", "takeover_latency", "tokens_replayed",
            "resumed_identical"} <= set(fo)
    assert fo["streams"] >= 1
    assert fo["failovers"] >= 1
    lat = fo["takeover_latency"]
    assert {"p50", "p95", "count"} <= set(lat)
    assert 0 < lat["p50"] <= lat["p95"] and lat["count"] >= 1
    assert fo["tokens_replayed"] >= 1
    # the exactness contract IS the section's verdict: every resumed
    # stream byte-identical to its fault-free reference
    assert fo["resumed_identical"] is True
    # the measured headline number stays fault-free
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_recovery_config_emits_recovery_section():
    """The recovery config must ride the same schema plus a ``recovery``
    section: a replica's scheduler SILENTLY frozen (no crash, no error)
    with streams mid-decode — the progress watchdog detects the wedge from
    stale watermarks, error-stops the replica, and the failover resumes
    every stream token-identically (docs/health.md).
    ``recovery.time_to_mitigate.p95`` is what benchdiff gates round over
    round."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-recovery",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    rec = payload.get("recovery")
    assert rec, payload
    assert {"episodes", "streams", "time_to_detect", "time_to_mitigate",
            "goodput_dip", "wedged", "resumed_identical"} <= set(rec)
    assert rec["episodes"] >= 1 and rec["streams"] >= 1
    for key in ("time_to_detect", "time_to_mitigate"):
        assert {"p50", "p95"} <= set(rec[key]), rec
        assert 0 < rec[key]["p50"] <= rec[key]["p95"], rec
    # detection precedes mitigation on the same clock
    assert rec["time_to_detect"]["p50"] <= rec["time_to_mitigate"]["p50"]
    assert 0.0 <= rec["goodput_dip"] <= 1.0
    # the contract headline: a silent hang wedges NOTHING, and every
    # resumed stream is byte-identical to its fault-free reference
    # (on mismatch the bench prints per-request forensics to stderr)
    assert rec["wedged"] == 0, out.stderr[-1200:]
    assert rec["resumed_identical"] is True, out.stderr[-1200:]
    # the measured headline number stays fault-free
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_mixed_config_emits_interference_section():
    """The mixed-traffic config must ride the same schema plus an
    ``interference`` section: the budget-on vs budget-off TPOT A/B for an
    interactive stream under long-prompt arrivals, and the decode-stall
    dispatch-gap histogram (docs/scheduling.md, stall-free admission)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-mixed",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    inter = payload.get("interference")
    assert inter, payload
    assert {"budget_tokens", "chunk_tokens", "budgeted", "unbudgeted",
            "improvement_p95", "decode_stall"} <= set(inter)
    assert inter["budget_tokens"] == 64
    for arm in ("budgeted", "unbudgeted"):
        stats = inter[arm]
        assert {"tpot_p50", "tpot_p95", "tpot_max", "pieces"} <= set(stats)
        assert stats["pieces"] > 0
        assert 0.0 <= stats["tpot_p50"] <= stats["tpot_p95"] <= stats["tpot_max"]
    assert inter["improvement_p95"] > 0
    stall = inter["decode_stall"]
    assert {"p50", "p95", "count"} <= set(stall)
    assert stall["count"] >= 1 and stall["p50"] <= stall["p95"]
    # the stall-free contract itself is timing-sensitive on shared CI
    # hardware, so the hard direction assertion (budgeted p95 < unbudgeted)
    # lives in the on-chip revalidation stage, not here — but the mixed run
    # must never error
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_tp_config_emits_sharded_plan():
    """The TP=2 config must ride the same schema plus the resolved
    per-shard plan: ``tp`` at the top level and ``impl_plan`` reporting the
    variant each device actually runs (paged_impl_plan(mesh=...)) — the
    CPU path-proof of llama2-7b-tp2-int8-ctx1024's code shape."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-tp2",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    assert payload["tp"] == 2
    plan = payload.get("impl_plan")
    assert plan, payload
    assert plan["tp"] == 2
    # tiny (Hkv=2) shards to 1 head/device: the grouped formulation
    assert plan["attention"] == "ragged"
    assert plan["ragged_variant"] == "grouped"
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_spec_config_emits_spec_section():
    """The speculative configs must carry the acceptance-rate -> tok/s
    story: a ``spec`` section with mode/gamma/acceptance alongside the
    throughput number (ROADMAP open item #4's measurability half)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-spec-ngram",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    spec = payload.get("spec")
    assert spec, payload
    assert spec["mode"] == "ngram" and spec["gamma"] == 2
    assert spec["proposed"] >= 0 and spec["accepted"] >= 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_bench_spec_adaptive_config_emits_ab_section():
    """tiny-spec-adaptive is the A/B the fused adaptive runtime is gated
    on (docs/speculative.md): two populations (high-acceptance /
    hostile) x three arms (off / fixed-gamma / adaptive) plus the
    benchdiff scalars utils/bench_diff.py tracks. The amortization claim
    — tokens_per_dispatch > 1 at high acceptance — is asserted here;
    the latency claim (adaptive_vs_off_tpot_p95) is asserted present and
    positive but not >= 1, because sub-10ms CPU tails are too noisy for
    a hard absolute gate — benchdiff gates it round-over-round."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=500,
        env={
            **os.environ,
            "BENCH_CPU": "1",
            "BENCH_MODEL": "tiny-spec-adaptive",
            "BENCH_NO_SECONDARY": "1",
        },
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    payload = json.loads(lines[0])
    assert payload["value"] > 0 and payload["unit"] == "tok/s"
    spec = payload.get("spec")
    assert spec, payload
    assert spec["mode"] == "ngram" and spec["gamma"] == 4
    # benchdiff-gated scalars (utils/bench_diff.py METRICS)
    assert {"gamma_p50", "tokens_per_dispatch", "fallback_rounds",
            "adaptive_vs_off_tpot_p95"} <= set(spec), spec
    assert spec["adaptive_vs_off_tpot_p95"] > 0
    # the A/B grid itself
    for pop in ("accept", "hostile"):
        arms = spec.get(pop)
        assert arms and {"off", "fixed", "adaptive"} <= set(arms), spec
        for arm, stats in arms.items():
            assert {"spec_rounds", "fallback_rounds", "gamma_p50",
                    "acceptance_rate", "tpot_p95"} <= set(stats), stats
        # the off arm never dispatches a fused round
        assert arms["off"]["spec_rounds"] == 0
        assert arms["off"]["proposed"] == 0
    accept_ad = spec["accept"]["adaptive"]
    # acceptance gate: on the self-similar population the fused round
    # harvests strictly more than one token per dispatch, at depth > 0
    assert accept_ad["spec_rounds"] > 0, spec
    assert accept_ad["tokens_per_dispatch"] > 1, spec
    assert accept_ad["gamma_p50"] > 0, spec
    assert spec["tokens_per_dispatch"] == accept_ad["tokens_per_dispatch"]
    # the hostile population must actually be hostile (low acceptance on
    # the fixed arm) and the controller must shrink depth relative to it
    hostile = spec["hostile"]
    assert hostile["fixed"]["acceptance_rate"] < 0.6, spec
    assert (
        hostile["adaptive"]["gamma_p50"] <= hostile["fixed"]["gamma_p50"]
    ), spec
    assert payload["engine_errors"] == 0


@pytest.mark.slow
def test_image_child_emits_schema_json():
    """The images/sec secondary metric (BASELINE.json: 'SDXL images/sec'):
    the txt2img pipeline child must print one JSON line; the tiny CPU
    path-proof must never claim the SD baseline."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--child-image"],
        capture_output=True,
        text=True,
        timeout=500,
        env={**os.environ, "BENCH_CPU": "1", "BENCH_IMAGE_TINY": "1"},
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    payload = json.loads(lines[-1])
    assert payload["unit"] == "img/s"
    assert payload["value"] > 0
    assert payload["vs_baseline"] == 0.0  # tiny path-proof: no baseline claim
    assert payload["sec_per_image"] > 0


def test_bench_supervisor_degrades_on_bad_model():
    """An impossible child must yield the error JSON line, not a hang."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        timeout=180,
        env={**os.environ, "BENCH_CPU": "1", "BENCH_MODEL": "nonexistent"},
        cwd=str(REPO),
    )
    # unknown BENCH_MODEL: supervisor KeyErrors per config -> error line path
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    if lines:
        payload = json.loads(lines[-1])
        assert "metric" in payload
    else:
        assert out.returncode != 0


@pytest.mark.slow
@pytest.mark.parametrize("flag,unit", [
    ("--child-embed", "tok/s"),
    ("--child-asr", "x-realtime"),
    ("--child-finetune", "train tok/s"),
])
def test_secondary_children_emit_schema_json(flag, unit):
    """Every BASELINE-config secondary child must print one JSON line in
    tiny mode — the same code shape the real TPU run takes (the finetune
    child's quantized base included)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), flag],
        capture_output=True,
        text=True,
        timeout=500,
        env={**os.environ, "BENCH_CPU": "1", "BENCH_TINY": "1"},
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    payload = json.loads(lines[-1])
    assert payload["unit"] == unit
    assert payload["value"] > 0
    assert payload["vs_baseline"] == 0.0  # no hard single-chip ref numbers
