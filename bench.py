#!/usr/bin/env python
"""Headline bench: LLM decode throughput on the continuous-batching engine.

North star (BASELINE.md): Llama-2-7B tokens/sec/chip on TPU, vs the A100
class the reference's vLLM example assumes. Baseline constant below:
~1400 output tok/s is a representative public vLLM Llama-2-7B total decode
throughput on one A100-40GB at moderate batch. vs_baseline = value/1400.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Supervisor/child structure: the supervisor (which never touches JAX — the
chip belongs to one process at a time, the child) tries every model config
its wall-clock budget allows in subprocesses with timeouts (an OOM or a hung
config must cost one config, not the run), then prints the BEST result —
round 2 printed the first success, which could never be the int8 config
that actually has headroom. Extra keys report every config tried
(``all_configs``), the achieved weight-streaming rate as a fraction of the
v5e HBM ceiling (``pct_hbm_ceiling``), and warm-boot timings measured with
the persistent XLA compile cache (``warm_build_s``/``warm_compile_s``).
BENCH_MODEL env forces a config. A measurement needs a TPU: without one the
first child exits NO_TPU_EXIT and the supervisor stops, non-zero, printing
no result. BENCH_CPU=1 (the test suite's path-proofs) is the only way onto
the CPU, and its rows say ``backend: cpu``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

A100_LLAMA2_7B_TOK_S = 1400.0
#: a child's exit code when JAX found no TPU; the supervisor stops on it
NO_TPU_EXIT = 3


def _bench_jax():
    """Import JAX on the backend this child measures on: a TPU, or — only
    when BENCH_CPU=1 asks for a path-proof — the CPU. Never a fallback."""
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.stderr.write(
            f"bench.py measures on a TPU; JAX found {jax.default_backend()!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})\n"
        )
        raise SystemExit(NO_TPU_EXIT)
    return jax

V5E_HBM_GBPS = 819.0  # v5e HBM bandwidth ceiling, bytes streamed per second
V5E_HBM_BYTES = 16e9  # v5e HBM capacity: the slots-at-budget denominator

CONFIGS = {
    # name: engine kwargs + measurement shape. int8 weight-only quantization
    # halves weight-streaming bytes AND frees HBM for slots — the bf16 8-slot
    # config's ceiling is ~486 tok/s (8 tok per 16.5 ms weight read), so the
    # quantized high-slot configs are the only road to the 1400 target.
    "llama2-7b-int4-s36": dict(
        # int4 weights: ~3.5 GB floor (4.2 ms/step) — the unsloth 4-bit
        # load path analog (unsloth_finetune.py:187-197)
        slots=36, max_len=256, max_tokens=128, timeout=1200, quant="int4"
    ),
    "llama2-7b-int8-s36": dict(
        # 36 slots was the round-4 sweep's sweet spot with the ragged kernel
        slots=36, max_len=256, max_tokens=128, timeout=1200, quant="int8"
    ),
    "llama2-7b-int8-kv8-s36": dict(
        # int8 KV on top of int8 weights: KV reads at the headline shape
        # are ~4.3 GB/step (comparable to the int8 weight floor); int8 KV
        # halves them AND halves residency (docs/kv_cache.md). Same 36-slot
        # shape as the config above.
        slots=36, max_len=256, max_tokens=128, timeout=1200, quant="int8",
        kv_dtype="int8",
    ),
    "llama2-7b-int8-s44": dict(
        # the >=40-slot compile ceiling repro (ROADMAP S7): the round-4
        # sweep saw compiles fail somewhere past ~40 slots. NOT in the
        # supervisor's default order — run it by name (BENCH_MODEL) with
        # a local MTPU_STATE_DIR: the profiler
        # writes a `begin` ledger event BEFORE each program build, so even
        # when this run dies mid-compile the ledger's begin-without-end
        # row names exactly which program/shape hit the ceiling —
        # diagnosable offline from compiles.jsonl alone.
        slots=44, max_len=256, max_tokens=32, timeout=1500, quant="int8",
        kv_dtype="int8",
    ),
    "llama2-7b-int8-kv8-ctx1024": dict(
        # long-context decode: at ctx 1024 KV reads are ~34 GB/step and
        # DOMINATE the step (NOTES r5) — the config where int8 KV is the
        # whole game. 16 slots x 1024 ctx = ~4 GB int8 KV (bf16 would be
        # ~8 GB next to the ~7 GB int8 weights: right at the HBM edge).
        # prompt_mult pushes real contexts to ~500+ tokens so decode runs
        # at long positions (chunked prefill path), not just long tables.
        slots=16, max_len=1024, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", prompt_mult=40,
    ),
    "llama2-7b-tp2-int8-ctx1024": dict(
        # tensor parallelism on the sharded Pallas fast path (round 7): the
        # ROADMAP-named TP=2 on-chip A/B partner of the ctx-1024 int8
        # config — same slots/context/dtype, cache + kernels sharded over
        # the kv-head ICI axis via shard_map (ops.sharded). Per-shard
        # Hkv=16, so int8 runs the grouped ragged variant (the plan rides
        # in the json's impl_plan). Needs >= 2 chips; on a 1-chip host the
        # mesh build fails and the supervisor degrades to the next config.
        slots=16, max_len=1024, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", prompt_mult=40, tp=2,
    ),
    "llama2-7b-int8-spec-ngram": dict(
        # speculative decoding as a measured lever (ROADMAP open item #4):
        # prompt-lookup ngram proposals against the repetitive bench prompt
        # give high acceptance, so this is the config where acceptance-rate
        # -> tok/s becomes a real, driver-captured delta vs
        # llama2-7b-int8-kv8-s36 (same shape, no spec). The json's `spec`
        # section carries {mode, gamma, acceptance_rate}.
        slots=16, max_len=256, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", spec=("ngram", 4),
    ),
    "llama2-7b-int8-spec-draft1b": dict(
        # draft-model speculation: a 1B-shape draft (same 32000 vocab)
        # proposes, the 7B verifies. Random draft weights (zero-egress)
        # floor the acceptance rate, so this config measures the MECHANISM
        # cost (draft decode + verify pass per tick); the ngram config
        # above carries the acceptance-driven win. Real checkpoints would
        # only raise acceptance, never the per-tick cost.
        slots=16, max_len=256, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", spec=("draft-1b", 4),
    ),
    "llama2-7b-mixed-ctx1024": dict(
        # stall-free admission at the long-context shape (docs/scheduling.md):
        # the ctx-1024 int8-KV config under MIXED traffic — one interactive
        # stream's observed TPOT captured while ~1k-token prompts arrive and
        # chunk-prefill, with the per-tick prefill budget ON (256 = one
        # chunk per tick) vs OFF. The json's `interference` section carries
        # both arms' p50/p95 plus the decode-stall histogram.
        slots=16, max_len=1024, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", prompt_mult=40, mixed=True, budget=256,
    ),
    "llama2-7b-disagg-2rep": dict(
        # disaggregated prefill/decode at the ctx-1024 int8-KV shape (the
        # A/B partner of llama2-7b-int8-kv8-ctx1024): a prefill replica
        # computes prompt KV and ships int8 pages + scale rows to the
        # decode replica (docs/disagg.md). Weights are SHARED between the
        # two in-process engines (params= alias, read-only in the jits) so
        # HBM pays one int8 weight set + two caches; the prefill replica
        # runs 4 slots of transient claims (prefills are serialized).
        slots=16, max_len=1024, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", prompt_mult=40, disagg=True,
    ),
    "llama2-7b-int8-s32": dict(
        slots=32, max_len=256, max_tokens=128, timeout=1200, quant="int8"
    ),
    "llama2-7b-int8-s16": dict(
        slots=16, max_len=384, max_tokens=128, timeout=1200, quant="int8"
    ),
    "llama2-7b": dict(slots=8, max_len=256, max_tokens=128, timeout=1200),
    "llama3.1-8b-int8-s32": dict(
        # GQA on the fast path (VERDICT r4 #4): Hkv=8 runs the v4 "grouped"
        # ragged kernel (per-kv-head contraction — no Hkv%16 flatten). The
        # reference's serving targets are GQA-era (vllm_inference.py:54-58);
        # not baseline-comparable (different model) but must carry its own
        # on-chip number in all_configs.
        slots=32, max_len=256, max_tokens=128, timeout=1500, quant="int8"
    ),
    "llama-1b": dict(slots=16, max_len=512, max_tokens=128, timeout=900),
    "tiny": dict(slots=4, max_len=128, max_tokens=16, timeout=420),
    # CPU path-proof of the disagg pipeline (test_bench_contract): never the
    # headline, but the same two-replica code shape the 7B config runs
    "tiny-disagg": dict(
        slots=4, max_len=128, max_tokens=16, timeout=420, disagg=True
    ),
    # CPU path-proofs (test_bench_contract): the sharded-pallas TP=2 code
    # shape on a forced 8-device host mesh, and the ngram-spec code shape —
    # same engine wiring the 7B configs run on chip
    "tiny-tp2": dict(
        slots=4, max_len=128, max_tokens=16, timeout=420, tp=2,
    ),
    "tiny-spec-ngram": dict(
        slots=4, max_len=128, max_tokens=16, timeout=420, spec=("ngram", 2),
    ),
    # CPU path-proof of fused adaptive speculation (test_bench_contract,
    # docs/speculative.md#gamma-schedule): spec-off vs fixed-γ vs adaptive
    # on the same warm engine over a MIXED acceptance population
    # (repetitive prompts the n-gram proposer nails + prose it can't) —
    # the json's `spec` section carries gamma_p50 / acceptance_rate /
    # tokens_per_dispatch / fallback_rounds and the per-arm TPOT tails
    # benchdiff gates on (speculation pays where acceptance is high,
    # and the controller's retreat must keep the adaptive arm no slower
    # than spec-off where it isn't)
    # decode_block=1 isolates speculation from the block's amortization:
    # the spec-off arm pays one host round-trip per token, so the A/B
    # measures what the γ-deep verify round buys, not what block fusion buys
    "tiny-spec-adaptive": dict(
        slots=4, max_len=128, max_tokens=16, timeout=420,
        spec=("ngram", 4), spec_ab=True, decode_block=1,
    ),
    # CPU path-proof of stall-free admission (test_bench_contract): the
    # same mixed-traffic interference A/B the 7B config above runs on chip
    # — an interactive stream's TPOT while long prompts chunk-prefill,
    # budget on (64 tokens/tick) vs off
    "tiny-mixed": dict(
        slots=4, max_len=512, max_tokens=16, timeout=420, prompt_mult=12,
        mixed=True, budget=64,
    ),
    # the on-chip adaptive-speculation A/B at the int8 headline shape
    # (run by name, BENCH_MODEL): prompt-lookup
    # proposals against real llama2-7b weights, spec-off vs fixed-γ vs
    # the acceptance-driven controller on the same warm engine
    "llama2-7b-int8-spec-adaptive": dict(
        slots=16, max_len=256, max_tokens=128, timeout=1500, quant="int8",
        kv_dtype="int8", spec=("ngram", 4), spec_ab=True, decode_block=1,
    ),
    # CPU path-proof of the chaos harness (test_bench_contract): after the
    # measured run, the seeded fault-injection episode schedule drives a
    # fresh tiny fleet through every cataloged fault point and the json
    # carries a `faults` section {injected, recovered, wedged: 0}
    # (docs/faults.md) — proving the failure contract alongside the
    # throughput number
    "tiny-chaos": dict(
        slots=4, max_len=128, max_tokens=16, timeout=420, chaos=True
    ),
    # CPU path-proof of in-flight failover (test_bench_contract,
    # docs/failover.md): after the measured run, streams are killed
    # mid-decode by an injected scheduler crash and checkpoint-resumed on
    # a second replica; the json carries a `failover` section
    # {takeover_latency p50/p95, tokens_replayed, resumed_identical} —
    # the takeover p95 is what bench_diff gates round over round
    "tiny-failover": dict(
        slots=4, max_len=192, max_tokens=32, timeout=420, failover=True
    ),
    # CPU path-proof of gray-failure recovery (test_bench_contract,
    # docs/health.md): after the measured run, a replica's scheduler is
    # SILENTLY frozen (no crash, no error) with streams mid-decode; the
    # progress watchdog must detect the wedge from stale watermarks,
    # error-stop the replica, and the PR-12 failover must resume every
    # stream token-identically. The json carries a `recovery` section
    # {time_to_detect p50/p95, time_to_mitigate p50/p95, goodput_dip,
    # wedged: 0} — the mitigation p95 is what bench_diff gates round over
    # round
    "tiny-recovery": dict(
        slots=4, max_len=192, max_tokens=32, timeout=420, recovery=True
    ),
    # the on-chip gray-failure recovery A/B at the int8 headline shape
    # (run by name, BENCH_MODEL): what a silently
    # wedged llama2-7b replica costs real streams — detection + mitigation
    # latency with HBM-sized KV and real replay work
    "llama2-7b-recovery": dict(
        slots=16, max_len=384, max_tokens=64, timeout=1500, quant="int8",
        kv_dtype="int8", recovery=True,
    ),
    # the on-chip failover A/B at the int8 headline shape
    # (run by name, BENCH_MODEL): what a mid-stream
    # replica death costs a real llama2-7b stream — takeover latency and
    # replayed-prefill work with HBM-sized KV
    "llama2-7b-failover": dict(
        slots=16, max_len=384, max_tokens=64, timeout=1500, quant="int8",
        kv_dtype="int8", failover=True,
    ),
    # CPU path-proof of the closed fleet loop (test_bench_contract,
    # docs/fleet.md): after the measured run, the open-loop load generator
    # drives a calibrated saturating sweep against an OpenAI server fronting
    # the engine — pinned single replica first, then with the FleetAutoscaler
    # scaling decode replicas out via snapshot-restored warm boots — and the
    # json carries a `fleet` section (goodput, p99 TTFT/TPOT vs offered
    # load, shed rate, scale events, A/B at the knee)
    # max_len 384: the byte-level tokenizer makes the loadgen's
    # shared-prefix prompts 100-300 TOKENS, and a clipped prompt would
    # finish after one token and measure nothing but prefill
    # fleet_max 2: scaled replicas share the host's cores with the primary
    # on the CPU path-proof, and a third engine is pure contention there.
    # ONE slot per replica: the pinned replica is then slot-bound while
    # the host keeps CPU headroom, so scale-out adds real capacity — with
    # 2+ slots a single tiny engine is CPU-bound and the A/B flatlines
    "tiny-fleet": dict(
        slots=1, max_len=384, max_tokens=8, timeout=420, fleet=True,
        fleet_step_s=4.0, fleet_max=2,
    ),
    # the on-chip fleet sweep: the headline
    # int8 shape under production-shaped open-loop traffic. max 2 decode
    # replicas — each warm boot restores a full int8 weight set (~7 GB), so
    # v5e HBM holds two replicas plus caches and no more.
    "llama2-7b-fleet-sweep": dict(
        slots=16, max_len=384, max_tokens=64, timeout=1500, quant="int8",
        kv_dtype="int8", fleet=True, fleet_step_s=10.0, fleet_max=2,
    ),
}


def _measure_canary(engine) -> dict:
    """Golden-set canary rounds on the measured engine
    (docs/observability.md#correctness-canary): first contact with this
    (model, fingerprint) identity records the golden, then a compare round
    gates bit-exact — pass rate, probe latency quantiles, and a drift
    count (expected: 0) ride in every BENCH json, so a numerically
    drifting build fails loudly at bench time instead of in serving. A
    cross-identity golden raises CanaryIdentityError (the loud banner) —
    never a false drift verdict."""
    from modal_examples_tpu.observability import canary as _canary

    store = _canary.GoldenStore()
    model = _canary.model_id(engine.cfg)
    fp = _canary.fingerprint(engine)
    golden = store.load(model, fp)  # CanaryIdentityError propagates, loudly
    recorded_now = golden is None
    if recorded_now:
        rec = _canary.probe_engine(engine, replica="bench", golden=None)
        probes = {
            r["probe"]: {"tokens": r["tokens"]}
            for r in rec
            if r["result"] == "recorded"
        }
        if len(probes) == len(_canary.GOLDEN_SET):
            store.record(model, fp, probes)
            golden = store.load(model, fp)
    results = _canary.probe_engine(engine, replica="bench", golden=golden)

    def _q(vals: list, frac: float):
        vals = sorted(v for v in vals if v is not None)
        if not vals:
            return None
        return round(vals[min(len(vals) - 1, round(frac * (len(vals) - 1)))], 6)

    compared = [r for r in results if r["result"] in ("pass", "drift")]
    drifts = sum(1 for r in results if r["result"] == "drift")
    out = {
        "probes": len(results),
        "pass_rate": (
            round(sum(1 for r in compared if r["result"] == "pass")
                  / len(compared), 4)
            if compared else None
        ),
        "drift_count": drifts,
        "errors": sum(1 for r in results if r["result"] == "error"),
        "fingerprint": _canary.fingerprint_hash(fp),
        "recorded": recorded_now,
    }
    for key in ("ttft", "tpot", "e2e"):
        vals = [r.get(key) for r in results]
        out[f"{key}_p50"] = _q(vals, 0.5)
        out[f"{key}_p95"] = _q(vals, 0.95)
    return out


def _measure_interference(engine, spec: dict) -> dict:
    """Stall-free admission A/B (docs/scheduling.md): while one interactive
    stream decodes, long-prompt arrivals force chunked prefills; the gaps
    between the stream's emitted pieces are its OBSERVED inter-token
    latency. Arm one runs the classic unbudgeted admission, arm two the
    config's per-tick prefill budget — the p95 gap is exactly the
    prefill/decode interference the budget exists to bound (~one chunk
    instead of the whole prompt). Runs on the same warm engine as the
    measured throughput loop; chunk jits are pre-warmed so neither arm
    pays first-compile."""
    import time as _time

    from modal_examples_tpu.serving import SamplingParams

    budget = int(spec.get("budget") or engine.prefill_buckets[-1])
    long_prompt = (
        "The quick brown fox jumps over the lazy dog. "
        * spec.get("prompt_mult", 12)
    )
    warm = engine.submit(
        long_prompt, SamplingParams(max_tokens=2, temperature=1.0)
    )
    for _ in engine.stream(warm):
        pass

    def run_arm(arm_budget: int) -> dict:
        engine.prefill_budget = arm_budget
        fg = engine.submit(
            "interactive stream under interference",
            SamplingParams(max_tokens=6 * spec["max_tokens"], temperature=1.0),
            priority="interactive",
        )
        stamped: list[tuple[float, float]] = []  # (gap end, gap seconds)
        longs: list = []
        last = None
        t_submit = None
        n_pieces = 0
        for _piece in engine.stream(fg):
            now = _time.monotonic()
            if last is not None:
                stamped.append((now, now - last))
            last = now
            n_pieces += 1
            if n_pieces == 2:
                # the stream is demonstrably decoding: drop a burst of
                # long-prompt prefills on it
                t_submit = _time.monotonic()
                longs = [
                    engine.submit(
                        long_prompt,
                        SamplingParams(max_tokens=4, temperature=1.0),
                        priority="batch",
                    )
                    for _ in range(4)
                ]
        for r in longs:
            for _ in engine.stream(r):
                pass
        # quantiles over the INTERFERENCE WINDOW only — submission of the
        # long prompts until the last one's prefill completed (its first
        # token is engine-stamped) — so the stream's steady-state tail
        # can't dilute the stall the A/B exists to expose. A gap counts if
        # it overlaps the window.
        t_end = max(
            [r.first_token_at or 0.0 for r in longs] or [float("inf")]
        )
        gaps = [
            g for t, g in stamped
            if t_submit is not None and t >= t_submit and t - g <= t_end
        ] or [g for _, g in stamped]
        gaps.sort()

        def q(p: float) -> float:
            if not gaps:
                return 0.0
            return gaps[min(len(gaps) - 1, int(p * len(gaps)))]

        return {
            "tpot_p50": round(q(0.50), 6),
            "tpot_p95": round(q(0.95), 6),
            "tpot_max": round(gaps[-1], 6) if gaps else 0.0,
            "pieces": n_pieces,
        }

    from modal_examples_tpu.observability import catalog as _C
    from modal_examples_tpu.utils.prometheus import default_registry

    saved = engine.prefill_budget
    try:
        # budgeted arm FIRST: the decode-stall histogram snapshotted right
        # after it covers only budgeted traffic (the measured run + this
        # arm — mixed configs run the measured loop budgeted too), so its
        # quantiles can evidence the "no gap exceeds ~one chunk" contract.
        # Snapshotting after the unbudgeted arm would bake that arm's
        # whole-prompt stalls into the very histogram the budget exists to
        # bound.
        budgeted = run_arm(budget)
        stall_q = default_registry.histogram_quantiles(
            _C.DECODE_STALL_SECONDS
        )
        unbudgeted = run_arm(0)
    finally:
        engine.prefill_budget = saved
    return {
        "budget_tokens": budget,
        "chunk_tokens": engine.prefill_buckets[-1],
        "unbudgeted": unbudgeted,
        "budgeted": budgeted,
        # >1 means the budget cut the interactive stream's tail latency
        "improvement_p95": round(
            unbudgeted["tpot_p95"] / max(budgeted["tpot_p95"], 1e-9), 3
        ),
        **(
            {
                "decode_stall": {
                    k: stall_q[k]
                    for k in ("p50", "p95", "p99", "count")
                    if k in stall_q
                }
            }
            if stall_q
            else {}
        ),
    }


def _measure_spec_adaptive(engine, spec: dict) -> dict:
    """Fused-speculation A/B (docs/speculative.md#gamma-schedule): the same
    warm engine runs an identical MIXED-acceptance population three times
    via the runtime-mutable spec knobs — spec off (depth 0), fixed full γ,
    and the adaptive controller — so both halves of the contract land in
    one json section: speculation pays where acceptance is high
    (``tokens_per_dispatch`` > 1 on the arms that speculate), and the
    controller's retreat means adaptivity can never cost latency (the
    adaptive arm's TPOT p95 vs the spec-off arm's is the benchdiff gate).
    Greedy traffic throughout — only greedy lanes speculate (the fused
    program's exactness contract, docs/speculative.md#exactness)."""
    import threading

    import numpy as _np

    from modal_examples_tpu.serving import SamplingParams

    sp = SamplingParams(max_tokens=spec["max_tokens"], temperature=0.0)
    # two acceptance regimes, measured separately because they gate two
    # DIFFERENT contracts: "accept" (looping text the n-gram proposer
    # nails → speculation must pay: tokens_per_dispatch > 1) and
    # "hostile" (the same bigram followed by a different token every
    # occurrence → proposals fire and miss, so the controller must
    # shrink γ and the adaptive arm must cost no more than spec-off)
    n = spec["slots"] * 2
    populations = {
        "accept": ["one two three " * 6 for _ in range(n)],
        "hostile": [
            "one two three one two four one two five one two six one two"
            for _ in range(n)
        ],
    }
    # bounded concurrency (slots-1 outstanding): a SATURATED batch is the
    # controller's global-pressure regime (it rightly speculates for no
    # one — verify flops scale with γ+1 per lane and a full batch is
    # already amortized), which would make every arm identical; the A/B
    # exists to expose the PER-REQUEST acceptance policy, so the traffic
    # keeps one slot of headroom like latency-bound serving does
    conc = threading.Semaphore(max(1, spec["slots"] - 1))

    def run_arm(depth: int, adaptive: bool, prompts: list) -> dict:
        engine.spec_depth = depth
        engine.spec_adaptive = adaptive
        for _ in engine.stream(engine.submit("spec arm warm " * 3, sp)):
            pass
        # freeze the gauge sweep so it can't drain the γ window mid-arm;
        # the arm computes its own p50 from the full window
        saved_wall = engine._metrics_wall
        engine._metrics_wall = time.monotonic() + 3600.0
        del engine._spec_gamma_window[:]
        r0 = engine._spec_rounds
        k0 = engine._spec_round_tokens
        f0 = engine._spec_fallbacks
        p0 = engine.stats.spec_proposed
        a0 = engine.stats.spec_accepted
        # per-REQUEST TPOT ((t_last - t_first) / (n - 1)), quantiles
        # across requests: spec rounds deliver tokens in bursts, so raw
        # inter-arrival gap quantiles would structurally punish any
        # multi-token dispatch (most gaps ~0, the tail = one whole round)
        tpots: list[float] = []
        t0 = time.time()

        def drain(prompt):
            with conc:
                r = engine.submit(prompt, sp)
                first = last = None
                pieces = 0
                for _ in engine.stream(r):
                    last = time.monotonic()
                    if first is None:
                        first = last
                    pieces += 1
                n = max(r.n_generated, pieces)
                if first is not None and n > 1:
                    tpots.append((last - first) / (n - 1))

        threads = [
            threading.Thread(target=drain, args=(p,)) for p in prompts
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.time() - t0
        rounds = engine._spec_rounds - r0
        tokens = engine._spec_round_tokens - k0
        proposed = engine.stats.spec_proposed - p0
        accepted = engine.stats.spec_accepted - a0
        window = list(engine._spec_gamma_window)
        engine._metrics_wall = saved_wall
        tpots.sort()

        def q(p: float) -> float:
            if not tpots:
                return 0.0
            return tpots[min(len(tpots) - 1, int(p * len(tpots)))]

        return {
            "spec_rounds": int(rounds),
            "fallback_rounds": int(engine._spec_fallbacks - f0),
            "tokens_per_dispatch": (
                round(tokens / rounds, 3) if rounds else None
            ),
            "gamma_p50": (
                float(_np.median(window)) if window else 0.0
            ),
            "proposed": int(proposed),
            "accepted": int(accepted),
            "acceptance_rate": (
                round(accepted / proposed, 4) if proposed else 0.0
            ),
            "tpot_p50": round(q(0.50), 6),
            "tpot_p95": round(q(0.95), 6),
            "elapsed_s": round(elapsed, 3),
        }

    saved_depth, saved_adaptive = engine.spec_depth, engine.spec_adaptive
    section: dict = {}
    try:
        for name, prompts in populations.items():
            section[name] = {
                "off": run_arm(0, False, prompts),
                "fixed": run_arm(engine.spec_gamma, False, prompts),
                "adaptive": run_arm(engine.spec_gamma, True, prompts),
            }
    finally:
        engine.spec_depth = saved_depth
        engine.spec_adaptive = saved_adaptive
    accept, hostile = section["accept"], section["hostile"]
    section.update({
        # the benchdiff-gated scalars (utils/bench_diff.py METRICS): the
        # production mode is adaptive, so its numbers are the headline.
        # tokens_per_dispatch/gamma_p50 come from the regime speculation
        # exists for; fallback_rounds + the TPOT ratio from the regime
        # the controller exists for
        "gamma_p50": accept["adaptive"]["gamma_p50"],
        "tokens_per_dispatch": accept["adaptive"]["tokens_per_dispatch"],
        "fallback_rounds": hostile["adaptive"]["fallback_rounds"],
        # >= ~1 means the controller kept the hostile traffic free:
        # adaptive TPOT tail no worse than never speculating at all
        "adaptive_vs_off_tpot_p95": round(
            hostile["off"]["tpot_p95"]
            / max(hostile["adaptive"]["tpot_p95"], 1e-9),
            3,
        ),
    })
    return section


def _fleet_n_pages(spec: dict) -> int:
    """KV page pool for fleet-config engines: low-slot fleets keep
    multi-slot slack so prefix warmth and queued claims don't fight over
    one slot's pool — ONE formula for the primary and every scale-out
    replica, or their A/B would silently diverge."""
    pages_per_slot = (spec["max_len"] + 15) // 16
    return 1 + max(4, spec["slots"]) * pages_per_slot


def _measure_failover(engine, spec: dict, make_engine) -> dict:
    """In-flight failover A/B (docs/failover.md): greedy reference streams
    first, then the same streams killed mid-decode by an injected
    scheduler crash on their replica and checkpoint-resumed on a second
    one (weights shared — one set in HBM). Emits the `failover` section:
    client-observed takeover latency p50/p95, generated-prefix tokens
    replayed by the reactive re-prefill, and the exactness verdict
    (resumed output == fault-free reference, byte for byte)."""
    import queue as _queue
    import threading as _threading
    import time as _time

    from modal_examples_tpu.faults.inject import FaultPlan, active
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.scheduling import (
        EngineReplica,
        PrefixAffinityRouter,
    )
    from modal_examples_tpu.serving import SamplingParams
    from modal_examples_tpu.utils.prometheus import default_registry

    eng_a = make_engine(params=engine.params)
    eng_b = make_engine(params=engine.params)
    rep_a = EngineReplica(eng_a, "fo-a", role="unified")
    rep_b = EngineReplica(eng_b, "fo-b", role="unified")
    router = PrefixAffinityRouter([rep_a, rep_b], reprobe_s=0.2)
    sp = SamplingParams(max_tokens=2 * spec["max_tokens"], temperature=0.0)
    prompts = [
        f"the quick brown fox jumps over the lazy dog variant {i}"
        for i in range(min(4, spec["slots"]))
    ]
    replayed0 = default_registry.total(C.FAILOVER_TOKENS_REPLAYED_TOTAL)
    failovers0 = default_registry.total(C.FAILOVER_TOTAL)
    try:
        eng_a.start()  # the victim; B boots lazily at takeover
        reference = {p: eng_a.generate(p, sp) for p in prompts}
        reqs, outs, threads = [], {}, []
        for p in prompts:
            req = rep_a.submit(p, sp)
            req._router_replica = rep_a
            reqs.append(req)
            outs[req.request_id] = pieces = []
            t = _threading.Thread(
                target=lambda r=req, buf=pieces: buf.extend(router.stream(r))
            )
            t.start()
            threads.append(t)
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline and not all(
            len(r.generated_tokens) >= 3 for r in reqs
        ):
            _time.sleep(0.002)
        # freeze the victim's scheduler (a blocking control command) so
        # the streams stay mid-decode, arm the crash, then release: the
        # next tick dies with every stream live — the kill is
        # deterministic, not a race against tiny-model decode speed
        freeze = _threading.Event()
        eng_a._ctrl.append((freeze.wait, _queue.Queue()))
        plan = FaultPlan({"engine.scheduler_crash": {"on_hit": 1}})
        with active(plan):
            freeze.set()
            deadline = _time.monotonic() + 60
            while not plan.fired() and _time.monotonic() < deadline:
                _time.sleep(0.002)
        for t in threads:
            t.join(timeout=300)
        identical = all(
            not t.is_alive() for t in threads
        ) and all(
            r.finish_reason in ("stop", "length")
            and "".join(outs[r.request_id]) == reference[r.prompt]
            for r in reqs
        )
        takeover = default_registry.histogram_quantiles(
            C.FAILOVER_TAKEOVER_SECONDS
        ) or {}
        return {
            "streams": len(reqs),
            "failovers": int(
                default_registry.total(C.FAILOVER_TOTAL) - failovers0
            ),
            "takeover_latency": {
                k: round(takeover[k], 6) if isinstance(takeover[k], float)
                else takeover[k]
                for k in ("p50", "p95", "count")
                if k in takeover
            },
            "tokens_replayed": int(
                default_registry.total(C.FAILOVER_TOKENS_REPLAYED_TOTAL)
                - replayed0
            ),
            "resumed_identical": bool(identical),
        }
    finally:
        eng_a.stop()
        eng_b.stop()


def _pct(values: list, q: float) -> float:
    """The repo-wide nearest-rank percentile (utils/stats.py) — one rank
    convention across every BENCH section benchdiff compares."""
    from modal_examples_tpu.utils.stats import percentile_nearest_rank

    return percentile_nearest_rank(values, q)


def _measure_recovery(engine, spec: dict, make_engine) -> dict:
    """Gray-failure recovery A/B (docs/health.md): greedy reference streams
    first, then the same streams with their replica's scheduler SILENTLY
    frozen mid-decode — no crash, no error, ``healthy()`` still true. The
    progress watchdog must classify the wedge from stale watermarks,
    error-stop the replica, and the reactive failover must resume every
    stream token-identically on the standby. Emits the `recovery` section:
    time_to_detect (freeze fired -> watchdog stop ladder action) and
    time_to_mitigate (freeze fired -> every stream resumed on the peer)
    p50/p95 over the episodes, the goodput dip the episode cost, and the
    exactness verdict."""
    import threading as _threading
    import time as _time

    from modal_examples_tpu.faults.inject import FaultPlan, active
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.scheduling import (
        EngineReplica,
        PrefixAffinityRouter,
    )
    from modal_examples_tpu.serving import SamplingParams
    from modal_examples_tpu.serving.health import (
        FleetWatchdog,
        WatchdogPolicy,
    )
    from modal_examples_tpu.utils.prometheus import default_registry

    eng_a = make_engine(params=engine.params)
    eng_b = make_engine(params=engine.params)
    rep_a = EngineReplica(eng_a, "rec-a", role="unified")
    rep_b = EngineReplica(eng_b, "rec-b", role="unified")
    router = PrefixAffinityRouter([rep_a, rep_b], reprobe_s=0.2)
    sp = SamplingParams(max_tokens=2 * spec["max_tokens"], temperature=0.0)
    prompts = [
        f"the quick brown fox jumps over the lazy dog variant {i}"
        for i in range(min(4, spec["slots"]))
    ]
    episodes = int(spec.get("recovery_episodes", 3))
    detect_s: list[float] = []
    mitigate_s: list[float] = []
    episode_walls: list[float] = []
    wedged = 0
    identical = True
    watchdog = None
    try:
        eng_a.start()
        reference = {p: eng_a.generate(p, sp) for p in prompts}

        def _stream_episode(replica) -> float:
            """Run the episode's streams concurrently (the same shape the
            fault episodes use) and return the wall time — the fault-free
            arm of the goodput dip must batch exactly like the faulted
            arm, or the dip compares sequential against concurrent."""
            t0 = _time.monotonic()
            ths = []
            for p in prompts:
                r = replica.submit(p, sp)
                r._router_replica = replica
                th = _threading.Thread(
                    target=lambda rr=r: list(router.stream(rr))
                )
                th.start()
                ths.append(th)
            for th in ths:
                th.join(timeout=300)
            return _time.monotonic() - t0

        wall_ref = _stream_episode(rep_a)
        # warm the STANDBY too: it compiles its own jits (separate engine,
        # separate caches), and its first-ever trace happens at TAKEOVER —
        # under the watchdog, that compile stall reads as a wedge of the
        # engine the failover is recovering onto, and the error-stop
        # poisons it (the watchdog-vs-compile rule, docs/health.md,
        # applied to both replicas)
        eng_b.generate(prompts[0], sp)
        eng_b.stop()
        # the watchdog starts AFTER the warm reference runs: first-compile
        # stalls must never read as a wedge
        watchdog = FleetWatchdog(
            router,
            policy=WatchdogPolicy(
                degraded_after_s=0.75, wedged_after_s=1.5,
                quarantine_after=10_000,  # the bench measures stop/revive
            ),
            poll_s=0.05,
        ).start()
        victim, standby = rep_a, rep_b
        for _ep in range(episodes):
            t_ep = _time.monotonic()
            reqs, outs, threads = [], {}, []
            for p in prompts:
                req = victim.submit(p, sp)
                req._router_replica = victim
                reqs.append(req)
                outs[req.request_id] = pieces = []
                t = _threading.Thread(
                    target=lambda r=req, buf=pieces: buf.extend(
                        router.stream(r)
                    )
                )
                t.start()
                threads.append(t)
            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and not all(
                len(r.generated_tokens) >= 3 for r in reqs
            ):
                _time.sleep(0.002)
            # the standby's loop must be quiet while the freeze arms (the
            # fault plan counts hits process-globally); the resumed
            # streams lazily restart it at takeover
            if standby.engine._running:
                standby.engine.stop()
            stops0 = len([
                e for e in watchdog.events if e["action"] == "stop_revive"
            ])
            failovers0 = default_registry.value(
                C.FAILOVER_TOTAL, labels={"mode": "reactive", "result": "ok"}
            ) or 0.0
            plan = FaultPlan(
                {"engine.scheduler_freeze": {"p": 1.0, "max_fires": 1}}
            )
            t_detect = t_mitigate = None
            with active(plan):
                arm_deadline = _time.monotonic() + 30
                while not plan.fired() and _time.monotonic() < arm_deadline:
                    _time.sleep(0.002)
                if not plan.fired():
                    # the victim's loop never hit the point within the
                    # bound (not running?): fall through WITHOUT waiting
                    # forever — the join + per-request identity check
                    # below stay honest, and the episode contributes no
                    # detect/mitigate sample (zero samples fail the
                    # contract loudly)
                    print(
                        f"recovery episode {_ep}: freeze never fired; "
                        f"victim={victim.name} "
                        f"running={victim.engine._running} "
                        f"poisoned={victim.engine._stopped_on_error} "
                        f"tokens={[len(r.generated_tokens) for r in reqs]}",
                        file=sys.stderr,
                    )
                else:
                    t_fire = _time.monotonic()
                    deadline = t_fire + 60
                    while _time.monotonic() < deadline:
                        if t_detect is None and len([
                            e for e in watchdog.events
                            if e["action"] == "stop_revive"
                        ]) > stops0:
                            t_detect = _time.monotonic() - t_fire
                        resumed = (
                            default_registry.value(
                                C.FAILOVER_TOTAL,
                                labels={"mode": "reactive", "result": "ok"},
                            ) or 0.0
                        ) - failovers0
                        if t_detect is not None and resumed >= len(reqs):
                            t_mitigate = _time.monotonic() - t_fire
                            break
                        _time.sleep(0.002)
            for t in threads:
                t.join(timeout=300)
            wedged += sum(1 for t in threads if t.is_alive())
            for r in reqs:
                got = "".join(outs[r.request_id])
                ok = (
                    r.finish_reason in ("stop", "length")
                    and got == reference[r.prompt]
                )
                identical = identical and ok
                if not ok:
                    # forensics on stderr (stdout stays the ONE json line)
                    print(
                        f"recovery episode {_ep}: {r.request_id} "
                        f"finish={r.finish_reason} "
                        f"out={got[-60:]!r} ref={reference[r.prompt][-60:]!r}",
                        file=sys.stderr,
                    )
            if t_detect is not None:
                detect_s.append(t_detect)
            if t_mitigate is not None:
                mitigate_s.append(t_mitigate)
            episode_walls.append(_time.monotonic() - t_ep)
            # revive the frozen victim for the next episode (the router's
            # probe path, driven directly) and swap roles: the streams now
            # live on the standby
            victim.probe()
            victim, standby = standby, victim
        wall_fault = sum(episode_walls) / max(1, len(episode_walls))
        return {
            "episodes": episodes,
            "streams": len(prompts),
            "time_to_detect": {
                "p50": round(_pct(detect_s, 0.5), 6),
                "p95": round(_pct(detect_s, 0.95), 6),
            },
            "time_to_mitigate": {
                "p50": round(_pct(mitigate_s, 0.5), 6),
                "p95": round(_pct(mitigate_s, 0.95), 6),
            },
            # fraction of fault-free throughput the episode cost: the same
            # streams took wall_fault instead of wall_ref
            "goodput_dip": round(
                max(0.0, 1.0 - wall_ref / wall_fault) if wall_fault else 0.0,
                6,
            ),
            "wedged": int(wedged),
            "resumed_identical": bool(identical),
        }
    finally:
        if watchdog is not None:
            watchdog.stop()
        eng_a.stop()
        eng_b.stop()


def _measure_fleet(engine, spec: dict, make_engine) -> dict:
    """Closed-loop fleet A/B (docs/fleet.md): front the warm engine with a
    router + OpenAI server, calibrate single-replica capacity with an
    overload burst, then run the same saturating open-loop sweep twice —
    pinned to one replica, and with the FleetAutoscaler growing decode
    replicas via snapshot-restored warm boots. The A/B at the pinned arm's
    knee is where closing the loop must pay: higher goodput, lower
    client-observed p99 TPOT, scale events journaled. Ends with an idle
    tail so the scale-back-in path is exercised too."""
    import time as _time

    from modal_examples_tpu.fleet import FleetAutoscaler, SnapshotWarmFactory
    from modal_examples_tpu.fleet.loadgen import (
        LoadGenerator,
        RequestClass,
        ab_index,
        fleet_section,
    )
    from modal_examples_tpu.scheduling import EngineReplica, PrefixAffinityRouter
    from modal_examples_tpu.serving.openai_api import OpenAIServer

    router = PrefixAffinityRouter(
        [EngineReplica(engine, "decode-0", role="unified")]
    )
    server = OpenAIServer(router=router, host="127.0.0.1", port=0).start()
    # the default class mix sized to this config's context budget (byte
    # tokenizer: prompts are CHARACTERS; prompt + max_tokens must fit
    # max_len or the engine clips the completion to nothing)
    classes = (
        RequestClass("interactive", "interactive", 0.5, (1, 2), 16, 2.0, 0.5),
        RequestClass("streaming", "default", 0.3, (1, 3), 32, 4.0, 0.5),
        RequestClass("batch", "batch", 0.2, (2, 4), 24, 30.0, 2.0,
                     stream=False),
    )

    def build(name, role, params=None):
        from modal_examples_tpu.serving import SamplingParams

        eng = make_engine(params=params)
        # compile-cache hits (the primary compiled the same shapes): the
        # replica joins the fleet jitted, not paying first-request
        # compiles (warmup() starts building the chunk programs long prompts
        # hit, too). One short and one chunking prompt before placement run them.
        eng.warmup()
        eng.start()
        for warm_prompt in ("warm " * 8, "boot warm long prompt " * 12):
            eng.generate(warm_prompt, SamplingParams(max_tokens=4))
        return EngineReplica(eng, name, role=role)

    factory = SnapshotWarmFactory(
        build, snapshot_key=f"fleet-bench-{os.getpid()}"
    )
    factory.prime(engine)  # scale-outs restore, never re-init
    lg = LoadGenerator(
        f"http://127.0.0.1:{server.port}", classes=classes, seed=0,
        request_timeout_s=90.0,
    )
    step_s = float(spec.get("fleet_step_s", 3.0))
    autoscaler = FleetAutoscaler(
        router,
        factory,
        max_replicas={"decode": int(spec.get("fleet_max", 3))},
        queue_high=2.0,
        up_ticks=1,
        down_ticks=4,
        cooldown_s=1.0,
        tick_s=0.2,
        slos=(),  # the bench registry carries warmup-phase latencies
    )
    try:
        lg.warm(n_per_class=1)
        # first closed-loop probe is a THROWAWAY: concurrent traffic is
        # what flushes the long tail of (bucket, chunk-offset) jit compiles
        # warm() cannot enumerate; the second probe measures the fleet
        lg.calibrate(duration_s=min(1.5, step_s))
        capacity = lg.calibrate(duration_s=min(2.5, step_s))
        rates = [0.6 * capacity, 1.25 * capacity, 2.5 * capacity]
        pinned = lg.sweep(rates, step_s)
        autoscaler.start()
        autoscaled = lg.sweep(rates, step_s)
        # the ascending ladder only scales out at its saturating step, so
        # re-measure the knee-adjacent rate NOW, fleet still scaled out —
        # the A/B the section headlines (see fleet_section)
        scaled_step = None
        if len(router.replicas) > 1:
            scaled_step = lg.run_step(
                rates[ab_index(pinned)], 1.5 * step_s, label="ab-scaled"
            )
        # idle tail: load is gone — the controller must scale back in
        deadline = _time.monotonic() + 30.0
        while len(router.replicas) > 1 and _time.monotonic() < deadline:
            _time.sleep(0.2)
        scaled_back_to = len(router.replicas)
    finally:
        autoscaler.stop()
        # anything the controller left registered (scale-in not reached
        # inside the tail window) is swept so the child exits clean
        for r in list(router.replicas):
            if r.name != "decode-0":
                try:
                    router.remove_replica(r.name)
                    r.engine.stop()
                except Exception:
                    pass
        factory.store.delete(factory.snapshot_key)  # bench key: no LRU churn
        # NOT server.stop(): that would also stop every replica engine,
        # including the primary the _child epilogue still reads/stops
        server.httpd.shutdown()
        server.httpd.server_close()
    section = fleet_section(
        pinned,
        autoscaled,
        scale_events=autoscaler.events,
        capacity_rps=capacity,
        scaled_step=scaled_step,
    )
    section["scaled_back_to"] = scaled_back_to
    return section


def _measure_shared_prefix(engine, spec: dict, make_engine) -> dict:
    """Shared prefix-store A/B (docs/prefix_store.md): the same
    two-replica fleet serving the same shared-prefix tenant traffic,
    once with per-replica PRIVATE volume tiers (the pre-store world:
    every replica recomputes or respills its own copy) and once with the
    fleet-wide SHARED store. Replicas A and B both serve and spill the
    corpus — between them every chain's rendezvous owner spills (the
    non-owner's puts defer), and the overlap is the dedup measurement:
    shared-arm puts dedup/defer down to ONE fleet-wide copy (ratio >
    1.0) while private-arm replicas each write their own. Then a COLD
    third replica (the scale-out case) serves the corpus — in the
    shared arm it promotes the fleet's spills (all peer hits), in the
    private arm its root is empty and every prefill recomputes. The
    cold replica's shared-arm TTFT p95 is the benchdiff-gated scalar
    (``fleet.shared_prefix_ttft_p95``)."""
    import time as _time

    from modal_examples_tpu.serving import SamplingParams
    from modal_examples_tpu.storage.volume import Volume

    # one multi-page shared prefix (byte tokenizer: characters ARE
    # tokens), fanned into per-tenant requests — the workload the
    # cross-replica store exists for
    prefix = (
        "shared system prompt: you are the fleet's serving benchmark; "
        "answer tersely and deterministically. " * 3
    )
    prompts = [f"{prefix}tenant request {i}" for i in range(4)]

    def _spill(eng) -> None:
        # evict the trie into the host tier, then demote every host
        # block — organic LRU overflow, forced so the A/B is
        # deterministic at bench scale (chaos uses the same lever)
        t = eng.tiered
        eng.prefix_cache.evict(10_000)
        with t._lock:
            items = list(t._host.items())
        for h, data in items:
            t._demote_to_volume(h, data)
            with t._lock:
                t._host.pop(h, None)
                t._host_used -= len(data)

    def _arm(shared: bool) -> dict:
        with Volume.ephemeral() as vol:
            def _mk(name: str):
                tp = {
                    "host_bytes": 1 << 20, "volume": vol,
                    "shared": shared, "replica": name,
                }
                if not shared:
                    # the pre-store world: one private root per replica
                    tp["volume_prefix"] = f"kv-tier-{name}"
                eng = make_engine(params=engine.params, tiered_prefix=tp)
                eng.warmup()
                eng.start()
                # jit the short-prompt path outside the measurement
                eng.generate("warm " * 8, SamplingParams(max_tokens=2))
                return eng

            engines = []
            try:
                eng_a = _mk("rep-a")
                eng_b = _mk("rep-b")
                engines += [eng_a, eng_b]
                for eng in (eng_a, eng_b):
                    for p in prompts:
                        eng.generate(p, SamplingParams(max_tokens=4))
                # both replicas spill: every chain's rendezvous owner is
                # one of the two, so one fleet-wide copy of every block
                # lands (the non-owner's puts defer/dedup against it)
                _spill(eng_a)
                _spill(eng_b)
                # the scale-out case: a COLD replica serves the corpus
                eng_c = _mk("rep-c")
                engines.append(eng_c)
                ttfts = []
                for p in prompts:
                    t0 = _time.perf_counter()
                    eng_c.generate(p, SamplingParams(max_tokens=1))
                    ttfts.append(_time.perf_counter() - t0)
                stats = [e.tiered.store.stats() for e in engines]
                puts = sum(s["puts"] for s in stats)
                writes = sum(s["writes"] for s in stats)
                c_s = stats[-1]
                return {
                    "ttft_p50": _pct(ttfts, 50),
                    "ttft_p95": _pct(ttfts, 95),
                    "cold_volume_hits": eng_c.tiered.tier_hits["volume"],
                    "peer_hits": c_s["hits"].get("peer", 0),
                    "puts": puts,
                    "writes": writes,
                    "dedup_ratio": round(puts / max(1, writes), 4),
                    "store_bytes": max(s["bytes"] for s in stats),
                }
            finally:
                for eng in engines:
                    eng.stop()

    private = _arm(shared=False)
    shared = _arm(shared=True)
    return {
        "private": private,
        "shared": shared,
        "ttft_p95_vs_private": round(
            shared["ttft_p95"] / max(private["ttft_p95"], 1e-9), 4
        ),
    }


def _child(model: str) -> None:
    spec = CONFIGS[model]
    # measured runs keep the distributed request tracer sampled OUT
    # (observability/reqtrace.py): the headline tok/s must not pay
    # per-request span file writes. Override with MTPU_TRACE_SAMPLE=1 to
    # bench-with-tracing deliberately; `tpurun benchdiff` then shows what
    # the instrumentation costs.
    os.environ.setdefault("MTPU_TRACE_SAMPLE", "0")
    # the hot-path profiler is on unless MTPU_PROFILE=0 (resolved once in
    # LLMEngine.__init__ — docs/observability.md): every BENCH json carries
    # an `overhead` section (host fraction, per-phase tick p50/p95, compile
    # totals), and the compile ledger captures every program build. With
    # MTPU_PROFILE=0 the instrumentation cost itself is A/B-able via
    # `tpurun benchdiff`.
    # ... and to the flight recorder (docs/observability.md#metrics-history):
    # the engine starts the tsdb sampler once, so every bench run leaves a
    # replayable metrics history under <state_dir>/tsdb/ and the `overhead`
    # section gains the sampler's own cost — benchdiff's existing
    # overhead.host_fraction / overhead.tick_p95 gates are the proof the
    # recorder costs nothing measurable on the hot path. MTPU_TSDB=0 in the
    # environment still wins (the sampler-off A/B arm).
    os.environ.setdefault("MTPU_TSDB", "1")
    if spec.get("fleet"):
        # production admission shape for the open-loop sweep: bounded
        # queues turn sustained overload into honest 429s (the shed-rate
        # axis of the fleet section) instead of minutes-deep queue waits.
        # Must land before the engine builds its AdmissionController.
        os.environ.setdefault("MTPU_SCHED_MAX_QUEUE", str(4 * spec["slots"]))
    if spec.get("tp", 1) > 1 and os.environ.get("BENCH_CPU"):
        # CPU TP path-proof needs virtual devices BEFORE jax imports
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    jax = _bench_jax()

    import jax.numpy as jnp

    from modal_examples_tpu.models import llama
    from modal_examples_tpu.models.quantize import param_bytes
    from modal_examples_tpu.serving import LLMEngine, SamplingParams
    if model.startswith("llama2-7b"):
        cfg = llama.LlamaConfig.llama2_7b()
    elif model.startswith("llama3.1-8b"):
        cfg = llama.LlamaConfig.llama31_8b()
    elif model == "llama-1b":
        cfg = llama.LlamaConfig(
            vocab_size=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
            ffn_dim=5632, max_seq_len=2048,
        )
    else:
        cfg = llama.LlamaConfig.tiny()

    # tensor parallelism (round 7): a "tensor"-axis mesh shards weights,
    # cache, and — via ops.sharded's shard_map dispatch — the Pallas
    # kernels over the kv-head axis; the SAME engine flags otherwise
    mesh = None
    if spec.get("tp", 1) > 1:
        from modal_examples_tpu.parallel import make_mesh

        mesh = make_mesh(
            {"tensor": spec["tp"]}, devices=jax.devices()[: spec["tp"]]
        )

    # speculative decoding configs (ROADMAP open item #4): "ngram" =
    # prompt-lookup (no draft weights); "draft-1b" = a 1B-shape draft with
    # the target's 32000 vocab, random weights (mechanism-cost floor —
    # the engine random-inits the draft when no draft_params are given)
    speculative = None
    if spec.get("spec"):
        mode, gamma = spec["spec"]
        if mode == "ngram":
            speculative = ("ngram", gamma)
        else:
            draft_cfg = llama.LlamaConfig(
                vocab_size=cfg.vocab_size, dim=2048, n_layers=16,
                n_heads=16, n_kv_heads=8, ffn_dim=5632, max_seq_len=2048,
            )
            speculative = (draft_cfg, gamma)

    t0 = time.time()
    engine = LLMEngine(
        cfg,
        max_slots=spec["slots"],
        max_model_len=spec["max_len"],
        page_size=16,
        # fleet configs may run 1 slot/replica (see tiny-fleet): keep
        # multi-slot page slack so prefix warmth survives next to claims
        n_pages=_fleet_n_pages(spec) if spec.get("fleet") else None,
        prefill_buckets=(64, 128, 256),
        # "int8" = quantized paged KV (half the decode KV HBM traffic and
        # residency, docs/kv_cache.md); default bf16
        kv_dtype=spec.get("kv_dtype", jnp.bfloat16),
        quantization=spec.get("quant"),
        # the v3 ragged kernel + pallas scatter decode structure (round 4);
        # models whose shapes don't fit the kernel fall back to XLA inside
        # decode_step — under mesh= the kernels run per head shard
        paged_impl="pallas",
        mesh=mesh,
        speculative=speculative,
        # stall-free admission (docs/scheduling.md): mixed configs run the
        # measured traffic budgeted; 0 keeps the classic unlimited admit
        max_prefill_tokens_per_tick=spec.get("budget", 0),
        decode_block=spec.get("decode_block", 8),
    )
    build_s = time.time() - t0
    weight_bytes = param_bytes(engine.params)

    # disaggregated two-replica mode (docs/disagg.md): `engine` becomes the
    # DECODE replica; a second engine sharing the same (read-only) weight
    # buffers runs prefill only and ships finished KV pages over the chunked
    # wire. Traffic then flows through the coordinator, so the measured
    # tok/s includes prefill, migration, adoption, and decode.
    coord = None
    if spec.get("disagg"):
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving.disagg import DisaggCoordinator

        prefill_engine = LLMEngine(
            cfg,
            params=engine.params,  # alias, not a copy: one weight set in HBM
            max_slots=min(4, spec["slots"]),  # transient, serialized claims
            max_model_len=spec["max_len"],
            page_size=16,
            prefill_buckets=(64, 128, 256),
            kv_dtype=spec.get("kv_dtype", jnp.bfloat16),
            paged_impl="xla",  # never decodes; skip kernel-probe surface
            tiered_prefix=True,  # host-RAM spill tier under the trie
        )
        coord = DisaggCoordinator(
            [
                EngineReplica(prefill_engine, "prefill-0", role="prefill"),
                EngineReplica(engine, "decode-0", role="decode"),
            ]
        )

    def _submit(prompt_s, sampling):
        if coord is not None:
            return coord.submit(prompt_s, sampling)
        return engine.submit(prompt_s, sampling)

    def _stream(req):
        return coord.stream(req) if coord is not None else engine.stream(req)

    prompt = (
        "The quick brown fox jumps over the lazy dog. "
        * spec.get("prompt_mult", 2)
    )
    max_tokens = spec["max_tokens"]
    if os.environ.get("BENCH_WARM"):
        max_tokens = 16  # warm rerun only measures boot, not throughput
    params = SamplingParams(max_tokens=max_tokens, temperature=1.0)

    # boot-time compiles, then a live warmup round through the scheduler
    t0 = time.time()
    engine.warmup()
    engine.start()
    warm = [_submit(prompt, SamplingParams(max_tokens=8, temperature=1.0))
            for _ in range(2)]
    for r in warm:
        "".join(_stream(r))
    compile_s = time.time() - t0

    # timed: saturate all slots
    n_reqs = spec["slots"] * 2
    base_tokens = engine.stats.generated_tokens
    t0 = time.time()
    reqs = [_submit(prompt, params) for _ in range(n_reqs)]
    for r in reqs:
        for _ in _stream(r):
            pass
    elapsed = time.time() - t0
    generated = engine.stats.generated_tokens - base_tokens

    # per-phase latency distributions (p50/p95/p99) from the engine's
    # observability histograms — phase-attributed perf trajectory in every
    # BENCH_*.json from here on (docs/observability.md). Snapshotted NOW,
    # before the interference A/B below: its unbudgeted arm generates
    # deliberately-degraded traffic that must not pollute the headline
    # token_latency/scheduling sections benchdiff gates on.
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.utils.prometheus import default_registry

    def _q(name, labels=None):
        q = default_registry.histogram_quantiles(name, labels=labels)
        if q is None:
            return None
        return {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in q.items()
        }

    phase_latency = {}
    for phase in (*C.TICK_PHASES, C.TICK_TOTAL_PHASE):
        q = _q(C.TICK_PHASE_SECONDS, {"phase": phase})
        if q:
            phase_latency[phase] = q
    for key, name in (
        ("queue_wait", C.ENGINE_QUEUE_WAIT_SECONDS),
        ("batch_size", C.ENGINE_BATCH_SIZE),
    ):
        q = _q(name)
        if q:
            phase_latency[key] = q
    # token-level serving latency (the vLLM-vs-TGI comparison axes): TTFT =
    # submit -> first token, TPOT = inter-token gap, from the engine's
    # per-request histograms — alongside aggregate tokens/s
    token_latency = {}
    for key, name in (("ttft", C.TTFT_SECONDS), ("tpot", C.TPOT_SECONDS)):
        q = _q(name)
        if q:
            token_latency[key] = {
                k: q[k] for k in ("p50", "p95", "count") if k in q
            }
    # scheduling telemetry (ISSUE-4): per-class admission queue-wait
    # distributions + the shed rate — the control layer's own trajectory
    # rides in every BENCH json alongside the kernel numbers
    sched_wait = {}
    for klass in ("interactive", "default", "batch"):
        q = _q(C.SCHED_QUEUE_WAIT_SECONDS, {"class": klass})
        if q:
            sched_wait[klass] = {
                k: q[k] for k in ("p50", "p95", "count") if k in q
            }
    sheds = default_registry.total(C.SHEDS_TOTAL)
    admitted = default_registry.total(C.REQUESTS_ADMITTED_TOTAL)
    offered = sheds + admitted
    scheduling = {
        "queue_wait": sched_wait,
        "shed_rate": round(sheds / offered, 6) if offered else 0.0,
        "sheds_total": int(sheds),
        "admitted_total": int(admitted),
    }

    # hot-path overhead attribution (docs/observability.md#hot-path-
    # profiling): host-vs-device fraction, per-phase tick p50/p95, detok
    # share, and compile totals from the engine's profiler ring —
    # snapshotted HERE, with the other latency sections and before the
    # interference/fleet/failover A/Bs, so the headline attribution
    # reflects the measured traffic rather than the deliberately-degraded
    # A/B arms. The profiler is on by default, so every config's
    # json carries the section; benchdiff gates overhead.host_fraction and
    # overhead.tick_p95 round over round.
    overhead = None
    if engine.profiler is not None:
        overhead = engine.profiler.overhead_summary()
        # flight-recorder ride-along (docs/observability.md#metrics-history):
        # the tsdb sampler's own telemetry lands NEXT TO the host-overhead
        # numbers it must not move — samples taken, scrape-cost p95, and
        # the series count, read from the same registry it scraped
        from modal_examples_tpu.observability import catalog as _cat
        from modal_examples_tpu.observability import timeseries as _tsm
        from modal_examples_tpu.utils.prometheus import (
            default_registry as _dreg,
        )

        if _tsm.global_sampler() is not None:
            scrape_q = _dreg.histogram_quantiles(
                _cat.TSDB_SCRAPE_SECONDS, quantiles=(0.5, 0.95), aggregate={}
            )
            overhead["tsdb"] = {
                "samples": int(_dreg.value(_cat.TSDB_SAMPLES_TOTAL)),
                "series": int(_dreg.value(_cat.TSDB_SERIES)),
                "scrape_p50": scrape_q["p50"] if scrape_q else None,
                "scrape_p95": scrape_q["p95"] if scrape_q else None,
            }

    # stall-free admission interference A/B (mixed configs): measured on
    # the same warm engine BEFORE it stops — budget on vs off TPOT for an
    # interactive stream under long-prompt arrivals (docs/scheduling.md)
    interference = None
    if spec.get("mixed"):
        interference = _measure_interference(engine, spec)

    # fused adaptive speculation A/B (spec_ab configs,
    # docs/speculative.md#gamma-schedule): spec-off vs fixed-γ vs the
    # acceptance-driven controller on the same warm engine via the
    # runtime-mutable knobs — merged into the `spec` json section below
    spec_ab_info = None
    if spec.get("spec") and spec.get("spec_ab"):
        spec_ab_info = _measure_spec_adaptive(engine, spec)

    # correctness canary (docs/observability.md#correctness-canary): a
    # record-then-compare golden-set round on the same warm engine, BEFORE
    # the fleet/failover/recovery arms stop it — drift_count must be 0 on
    # a healthy build, and an identity-mismatched golden refuses loudly
    canary_info = _measure_canary(engine)

    # closed-loop fleet A/B (fleet configs, docs/fleet.md): saturating
    # open-loop sweep against an OpenAI front, pinned vs autoscaled —
    # scale-out replicas are built by this factory with snapshot-restored
    # params (quantization=None then: the restored tree is already
    # quantized; re-quantizing it would corrupt the weights)
    fleet_info = None
    if spec.get("fleet"):
        def _mk_fleet_engine(params=None, tiered_prefix=None):
            return LLMEngine(
                cfg,
                params=params,
                max_slots=spec["slots"],
                max_model_len=spec["max_len"],
                page_size=16,
                n_pages=_fleet_n_pages(spec),
                prefill_buckets=(64, 128, 256),
                kv_dtype=spec.get("kv_dtype", jnp.bfloat16),
                quantization=spec.get("quant") if params is None else None,
                paged_impl="pallas",
                mesh=mesh,
                max_prefill_tokens_per_tick=spec.get("budget", 0),
                tiered_prefix=tiered_prefix,
            )

        fleet_info = _measure_fleet(engine, spec, _mk_fleet_engine)
        # shared prefix-store A/B (docs/prefix_store.md): private vs
        # fleet-wide volume tiers on a two-replica fleet; the shared
        # arm's cold-replica TTFT is the benchdiff-gated scalar
        sp = _measure_shared_prefix(engine, spec, _mk_fleet_engine)
        fleet_info["shared_prefix"] = sp
        fleet_info["shared_prefix_ttft_p95"] = sp["shared"]["ttft_p95"]

    # in-flight failover A/B (failover configs, docs/failover.md): streams
    # killed mid-decode on one replica, checkpoint-resumed on another —
    # weights aliased (params=engine.params, already quantized) so HBM
    # holds one weight set plus the two caches
    failover_info = None
    if spec.get("failover"):
        # the measured engine's loop must be quiet first: the injected
        # scheduler crash counts hits process-globally, and the victim
        # replica's loop must be the ONLY one running for the kill to
        # land deterministically (the measured traffic is already done)
        engine.stop()

        def _mk_failover_engine(params=None):
            return LLMEngine(
                cfg,
                params=params,
                max_slots=spec["slots"],
                max_model_len=spec["max_len"],
                page_size=16,
                prefill_buckets=(64, 128, 256),
                kv_dtype=spec.get("kv_dtype", jnp.bfloat16),
                quantization=None if params is not None else spec.get("quant"),
                paged_impl="pallas",
                mesh=mesh,
            )

        failover_info = _measure_failover(engine, spec, _mk_failover_engine)

    # gray-failure recovery A/B (recovery configs, docs/health.md): a
    # replica's scheduler silently frozen with streams mid-decode — the
    # watchdog detects from stale watermarks, the failover resumes; same
    # weight-aliasing rules as the failover A/B
    recovery_info = None
    if spec.get("recovery"):
        # quiet loop first, same reason as the failover A/B: the injected
        # freeze counts hits process-globally and must land on the victim
        engine.stop()

        def _mk_recovery_engine(params=None):
            return LLMEngine(
                cfg,
                params=params,
                max_slots=spec["slots"],
                max_model_len=spec["max_len"],
                page_size=16,
                prefill_buckets=(64, 128, 256),
                kv_dtype=spec.get("kv_dtype", jnp.bfloat16),
                quantization=None if params is not None else spec.get("quant"),
                paged_impl="pallas",
                mesh=mesh,
            )

        recovery_info = _measure_recovery(engine, spec, _mk_recovery_engine)

    errors = engine.error_count
    engine.stop()

    tok_s = generated / elapsed
    # decode is weight-streaming-bound: every step reads the full weight set
    # once for up to `slots` tokens. steps/s * weight_bytes over the HBM
    # ceiling says how close the whole serving stack runs to the hardware.
    stream_gbps = (tok_s / spec["slots"]) * weight_bytes / 1e9

    # roofline position (docs/observability.md#roofline-and-usage-
    # accounting): the engine's usage meter joins its analytic work model
    # (FLOPs + dtype-aware bytes) with the device seconds it accounted —
    # MFU/MBU against the target generation's peaks plus the compute-vs-
    # bandwidth bound classification, gated release-to-release by
    # bench_diff. A pure function of token counts and the engine clock.
    utilization = engine.usage.utilization_section(tokens_per_second=tok_s)

    # KV-cache footprint (dtype-aware: int8 counts int8 payload + f32 scale
    # rows): the residency half of the int8-KV win. max_slots_at_hbm = how
    # many slots of THIS config's context length fit in v5e HBM after the
    # weights — ~2x at kv_dtype="int8", measurable the moment the bytes
    # halve, no chip required.
    cache_occ = engine.cache.occupancy()
    bytes_per_page = cache_occ["bytes_total"] // engine.cache.n_pages
    bytes_per_slot = engine.pages_per_slot * bytes_per_page
    kv_cache_info = {
        "dtype": engine.cache.kv_dtype,
        "bytes": int(cache_occ["bytes_total"]),
        "bytes_per_slot": int(bytes_per_slot),
        "max_slots_at_hbm": int(
            max(0.0, V5E_HBM_BYTES - weight_bytes) // max(bytes_per_slot, 1)
        ),
    }

    # speculative decoding (ROADMAP open item #4): the acceptance-rate ->
    # tok/s story needs both numbers in the same json line
    spec_info = None
    if engine.spec_gamma:
        spec_info = {
            "mode": engine.spec_mode,
            "gamma": engine.spec_gamma,
            "adaptive": bool(engine.spec_adaptive),
            "proposed": int(engine.stats.spec_proposed),
            "accepted": int(engine.stats.spec_accepted),
            "acceptance_rate": round(engine.stats.acceptance_rate(), 4),
            # spec_ab configs: the off/fixed/adaptive A/B arms + the
            # benchdiff-gated scalars (gamma_p50, tokens_per_dispatch,
            # fallback_rounds, adaptive_vs_off_tpot_p95)
            **(spec_ab_info or {}),
        }
    # disaggregated serving (docs/disagg.md): migration volume + latency and
    # the tiered prefix cache's per-tier hit mix, only for disagg configs
    disagg_info = None
    if coord is not None:
        mig = coord.stats()["migrations"]
        mq = _q(C.DISAGG_MIGRATION_SECONDS)
        tier_hits = {
            lbls.get("tier", "?"): int(v)
            for lbls, v in default_registry.series(C.PREFIX_TIER_HITS_TOTAL)
        }
        total_hits = sum(tier_hits.values())
        disagg_info = {
            "pages_migrated": int(mig["pages"]),
            "migration_bytes": int(mig["bytes"]),
            "migrations": {
                k: int(mig[k]) for k in ("ok", "fallback", "aborted")
            },
            "migration_latency": (
                {k: mq[k] for k in ("p50", "p95", "count") if k in mq}
                if mq
                else None
            ),
            "tier_hits": tier_hits,
            "tier_hit_rates": {
                k: round(v / total_hits, 6) for k, v in tier_hits.items()
            }
            if total_hits
            else {},
        }
    # chaos path-proof (docs/faults.md): for chaos configs the seeded
    # episode schedule runs a fresh tiny fleet through every cataloged
    # fault point AFTER the measured traffic (the measured number stays
    # fault-free); the report rides in the json so a failure-handling
    # regression breaks the bench contract, not just the test suite
    faults_info = None
    if spec.get("chaos"):
        from modal_examples_tpu.faults.chaos import run_chaos

        chaos_report = run_chaos(seed=0, strict=False)
        faults_info = {
            "injected": int(chaos_report["injected_total"]),
            "per_point": chaos_report["injected"],
            "recovered": int(chaos_report["recovered"]),
            "wedged": int(chaos_report["wedged"]),
            "points_missed": chaos_report["points_missed"],
            "episodes": len(chaos_report["episodes"]),
            "invariants": (
                "ok" if chaos_report["invariants"] == "ok" else "violated"
            ),
        }
    print(
        json.dumps(
            {
                "metric": f"{model} serving decode throughput (1 chip)",
                "value": round(tok_s, 2),
                "unit": "tok/s",
                "vs_baseline": round(tok_s / A100_LLAMA2_7B_TOK_S, 4),
                "model": model,
                "params": cfg.param_count,
                "weight_gb": round(weight_bytes / 1e9, 2),
                "backend": jax.default_backend(),
                "slots": spec["slots"],
                "generated_tokens": generated,
                "elapsed_s": round(elapsed, 2),
                "engine_build_s": round(build_s, 1),
                "compile_s": round(compile_s, 1),
                "pct_hbm_ceiling": round(stream_gbps / V5E_HBM_GBPS, 4),
                "engine_errors": errors,
                # the RESOLVED decode plan (paged_impl_plan(mesh=...)):
                # benches must report the per-shard variant actually run,
                # incl. the tensor-parallel degree, not the requested impl
                "tp": engine.impl_plan.get("tp", 1),
                "impl_plan": {
                    k: v
                    for k, v in engine.impl_plan.items()
                    if k != "downgraded"
                },
                "phase_latency": phase_latency,
                "token_latency": token_latency,
                "scheduling": scheduling,
                "kv_cache": kv_cache_info,
                "utilization": utilization,
                **({"overhead": overhead} if overhead else {}),
                "tokens_per_second": round(tok_s, 2),
                **({"spec": spec_info} if spec_info else {}),
                **({"disagg": disagg_info} if disagg_info else {}),
                **({"faults": faults_info} if faults_info else {}),
                **({"interference": interference} if interference else {}),
                **({"canary": canary_info} if canary_info else {}),
                **({"fleet": fleet_info} if fleet_info else {}),
                **({"failover": failover_info} if failover_info else {}),
                **({"recovery": recovery_info} if recovery_info else {}),
            }
        )
    )


def _extract_json(stdout: str) -> dict | None:
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def _slope_time(run, iters: int) -> float:
    """Per-iteration seconds via the two-point slope (cancels fixed
    dispatch cost), falling back to plain elapsed when tiny/fast runs make
    the slope non-positive on noise. ``run(n)`` executes n iterations and
    host-syncs; shared by every secondary bench child."""
    n1, n2 = max(1, iters // 2), iters
    t1, t2 = run(n1), run(n2)
    if t2 > t1 and n2 > n1:
        return (t2 - t1) / (n2 - n1)
    return t2 / n2


def _image_child() -> None:
    """Secondary metric (BASELINE.json: "SDXL images/sec"): full txt2img
    pipeline — SD3-Medium-shape MMDiT (24 blocks, width 1536, ~2B params,
    bf16) rectified-flow sampling at 4 steps (the reference's Turbo loop,
    stable_diffusion/text_to_image.py) + SD3 VAE decode to 512px — as ONE
    jitted program. Random weights (zero-egress: no checkpoints), which is
    perf-equivalent: the FLOPs/bytes don't depend on the values."""
    import dataclasses as _dc

    jax = _bench_jax()
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import diffusion, vae

    tiny = bool(os.environ.get("BENCH_IMAGE_TINY"))
    if tiny:
        mcfg = diffusion.MMDiTConfig.tiny()
        vcfg = _dc.replace(
            vae.VAEConfig.tiny(), latent_channels=mcfg.channels
        )
        steps, B, iters, S_text = 2, 1, 2, 16
    else:
        mcfg = diffusion.MMDiTConfig.sd3_shape()
        vcfg = _dc.replace(vae.VAEConfig.sd3_shape(), dtype="bfloat16")
        steps, B, iters, S_text = 4, 1, 4, 154  # CLIP-L+G 77+77 joint tokens

    t0 = time.time()
    params = diffusion.mmdit_init(jax.random.PRNGKey(0), mcfg)
    vparams = vae.init_params(jax.random.PRNGKey(1), vcfg)
    jax.block_until_ready((params, vparams))
    build_s = time.time() - t0
    from modal_examples_tpu.models.quantize import param_bytes

    dt = mcfg.jnp_dtype
    text = jax.random.normal(jax.random.PRNGKey(2), (B, S_text, mcfg.text_dim), dt)
    pooled = jax.random.normal(jax.random.PRNGKey(3), (B, mcfg.pooled_dim), dt)
    null_t = jnp.zeros_like(text)
    null_p = jnp.zeros_like(pooled)

    def pipe(params, vparams, key, text, pooled, null_t, null_p):
        lat = diffusion.mmdit_sample(
            params, key, text, pooled, null_t, null_p, mcfg,
            steps=steps, guidance=4.0,
        )
        return vae.decode(vparams, lat.astype(vcfg.jnp_dtype), vcfg)

    fn = jax.jit(pipe)
    t0 = time.time()
    img = fn(params, vparams, jax.random.PRNGKey(4), text, pooled, null_t, null_p)
    jax.block_until_ready(img)
    compile_s = time.time() - t0

    def run(n):
        t0 = time.time()
        img = None
        for i in range(n):
            img = fn(params, vparams, jax.random.PRNGKey(5 + i), text,
                     pooled, null_t, null_p)
        np.asarray(img[0, 0, 0])
        return time.time() - t0

    sec_per_img = _slope_time(run, iters) / B
    img_s = 1.0 / sec_per_img
    out_px = mcfg.img_size * vcfg.downscale
    print(
        json.dumps(
            {
                "metric": (
                    "tiny txt2img path-proof (NOT the SD metric)"
                    if tiny else "sd3-medium-shape txt2img (1 chip)"
                ),
                "value": round(img_s, 3),
                "unit": "img/s",
                # text_to_image.py:11-13: "an image in 1 to 2 seconds" on
                # H100 (SD3.5-Large-Turbo, 1024px) -> ~0.67 img/s midpoint.
                # The tiny path-proof config may never claim the baseline.
                "vs_baseline": 0.0 if tiny else round(img_s / (1 / 1.5), 4),
                "steps": steps,
                "resolution": f"{out_px}x{out_px}",
                "param_gb": round(
                    param_bytes(params) / 1e9 + param_bytes(vparams) / 1e9, 2
                ),
                "sec_per_image": round(sec_per_img, 3),
                "build_s": round(build_s, 1),
                "compile_s": round(compile_s, 1),
                "backend": jax.default_backend(),
            }
        ),
        flush=True,
    )


def _embed_child() -> None:
    """Secondary metric: sentence-embedding throughput (BASELINE config
    "bge-small-en sentence embeddings"; the reference's TEI tier —
    text_embeddings_inference.py, wikipedia/main.py's 575k tok/s fleet
    claim). bge-small geometry = models.bert defaults (384 dim, 12
    layers); random weights are perf-equivalent."""
    jax = _bench_jax()
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import bert

    tiny = bool(os.environ.get("BENCH_TINY"))
    cfg = bert.BertConfig.tiny() if tiny else bert.BertConfig()  # bge-small shape
    B, S, iters = (8, 64, 2) if tiny else (256, 512, 8)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    mask = jnp.ones((B, S), jnp.int32)
    fn = jax.jit(lambda p, t, m: bert.embed(p, t, m, cfg))
    t0 = time.time()
    np.asarray(fn(params, toks, mask))
    compile_s = time.time() - t0

    def run(n):
        out = None
        t0 = time.time()
        for _ in range(n):
            out = fn(params, toks, mask)
        np.asarray(out[0, 0])
        return time.time() - t0

    tok_s = B * S / _slope_time(run, iters)
    print(json.dumps({
        "metric": ("tiny embed path-proof" if tiny
                   else "bge-small-shape embedding throughput (1 chip)"),
        "value": round(tok_s, 0), "unit": "tok/s",
        "vs_baseline": 0.0,  # the reference's 575k tok/s is a fleet number
        "batch": B, "seq": S, "compile_s": round(compile_s, 1),
        "backend": jax.default_backend(),
    }), flush=True)


def _asr_child() -> None:
    """Secondary metric: Whisper transcription speed as x-realtime
    (BASELINE config "Whisper-base audio transcription";
    openai_whisper/batched_whisper.py). whisper-base geometry, 30 s
    chunks, greedy decode of 64 tokens per chunk."""
    jax = _bench_jax()
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import whisper

    tiny = bool(os.environ.get("BENCH_TINY"))
    if tiny:
        cfg = whisper.WhisperConfig.test_tiny()
        B, frames, max_toks, iters = 2, 200, 8, 2
    else:
        cfg = whisper.WhisperConfig.base()
        B, frames, max_toks, iters = 8, 3000, 64, 4  # 8 x 30 s chunks
    params = whisper.init_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    mel = jax.random.normal(jax.random.PRNGKey(1), (B, frames, cfg.n_mels))
    fn = jax.jit(
        lambda p, m: whisper.greedy_transcribe(
            p, m, cfg, bos_id=0, eos_id=1, max_tokens=max_toks
        )
    )
    t0 = time.time()
    np.asarray(fn(params, mel))
    compile_s = time.time() - t0

    def run(n):
        out = None
        t0 = time.time()
        for _ in range(n):
            out = fn(params, mel)
        np.asarray(out[0, 0])
        return time.time() - t0

    audio_s = B * frames * 0.01  # 10 ms mel hop
    xrt = audio_s / _slope_time(run, iters)
    print(json.dumps({
        "metric": ("tiny asr path-proof" if tiny
                   else "whisper-base-shape transcription speed (1 chip)"),
        "value": round(xrt, 1), "unit": "x-realtime",
        "vs_baseline": 0.0,  # no hard reference number in BASELINE.md
        "batch": B, "chunk_s": frames * 0.01, "tokens_per_chunk": max_toks,
        "compile_s": round(compile_s, 1),
        "backend": jax.default_backend(),
    }), flush=True)


def _finetune_child() -> None:
    """Secondary metric: LoRA fine-tune step throughput (BASELINE config
    "Llama-2-7B LoRA fine-tune"; unsloth_finetune.py). Adapters train
    on-the-fly against a frozen int8 base (the memory trick that fits 7B
    on one 16 GB chip); tokens/sec = B*S / step."""
    jax = _bench_jax()
    import jax.numpy as jnp
    import numpy as np
    import optax

    from modal_examples_tpu.models import llama, lora
    from modal_examples_tpu.models.quantize import init_quantized_llama
    from modal_examples_tpu.training import cross_entropy_loss

    tiny = bool(os.environ.get("BENCH_TINY"))
    if tiny:
        # tiny path keeps the SAME quantized-base shape as the real run so
        # CI exercises it (a float-only tiny path masked an int8-adapter
        # crash here once)
        cfg = llama.LlamaConfig.tiny()
        B, S, iters = 2, 32, 2
    else:
        cfg = llama.LlamaConfig.llama2_7b()
        B, S, iters = 2, 512, 4
    base = init_quantized_llama(jax.random.PRNGKey(0), cfg, bits=8)
    jax.block_until_ready(base)
    lcfg = lora.LoRAConfig(rank=16)
    adapters = lora.init_lora(jax.random.PRNGKey(1), base, lcfg)
    opt = optax.adam(1e-4)
    opt_state = opt.init(adapters)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    mask = jnp.ones((B, S), jnp.float32)

    @jax.jit
    def step(adapters, opt_state, toks, mask):
        def loss_fn(ad):
            logits = llama.forward(
                base, toks, cfg, attn_impl="xla", lora=ad,
                lora_scale=lcfg.scale,
            )
            return cross_entropy_loss(logits[:, :-1], toks[:, 1:], mask[:, 1:])

        loss, g = jax.value_and_grad(loss_fn)(adapters)
        upd, opt_state = opt.update(g, opt_state)
        return optax.apply_updates(adapters, upd), opt_state, loss

    t0 = time.time()
    adapters, opt_state, loss = step(adapters, opt_state, toks, mask)
    np.asarray(loss)
    compile_s = time.time() - t0

    def run(n):
        nonlocal adapters, opt_state
        loss = None
        t0 = time.time()
        for _ in range(n):
            adapters, opt_state, loss = step(adapters, opt_state, toks, mask)
        np.asarray(loss)
        return time.time() - t0

    step_s = _slope_time(run, iters)
    print(json.dumps({
        "metric": ("tiny finetune path-proof" if tiny
                   else "llama2-7b-int8-base LoRA finetune (1 chip)"),
        "value": round(B * S / step_s, 1), "unit": "train tok/s",
        "vs_baseline": 0.0,  # reference publishes no single-GPU number
        "batch": B, "seq": S, "step_s": round(step_s, 3),
        "adapter_params": lora.param_count(adapters),
        "compile_s": round(compile_s, 1),
        "backend": jax.default_backend(),
    }), flush=True)


SECONDARY_CHILDREN = {
    "--child-image": _image_child,
    "--child-embed": _embed_child,
    "--child-asr": _asr_child,
    "--child-finetune": _finetune_child,
}


def _run_config(model: str, env: dict, timeout: float) -> tuple[dict | None, str]:
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", model],
            capture_output=True,
            text=True,
            timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"{model}: timeout"
    if proc.returncode == NO_TPU_EXIT:
        raise SystemExit(
            f"bench.py: no TPU, no result ({proc.stderr.strip()[-300:]})"
        )
    result = _extract_json(proc.stdout)
    if result is None:
        return None, f"{model}: exit={proc.returncode} stderr={proc.stderr[-400:]}"
    if proc.stderr:
        # forward the child's diagnostics (the stdout one-json-line
        # contract holds; stderr is where section forensics like the
        # recovery mismatch reports land — don't swallow them)
        sys.stderr.write(proc.stderr[-4000:])
    return result, ""


def main() -> int:
    from modal_examples_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()  # before any JAX import; children inherit it
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(sys.argv[2])
        return 0
    if len(sys.argv) > 1 and sys.argv[1] in SECONDARY_CHILDREN:
        SECONDARY_CHILDREN[sys.argv[1]]()
        return 0

    # Hard wall-clock budget for the WHOLE bench (driver runs us with its own
    # timeout; round 1 summed per-config timeouts to 72 min and got rc=124).
    deadline = time.time() + float(os.environ.get("BENCH_BUDGET_S", "1100"))

    env = dict(os.environ)
    if env.get("BENCH_MODEL"):
        order = [env["BENCH_MODEL"]]
    elif env.get("BENCH_CPU"):
        order = ["tiny"]
    else:
        # canary-first: the tiny config proves the full engine path end to
        # end in ~1 min and becomes the guaranteed fallback line; then every
        # real target, best-expected first so budget exhaustion still leaves
        # the strongest measured number on the table.
        order = [
            "tiny",
            "llama2-7b-int8-kv8-s36",
            "llama2-7b-int4-s36",
            "llama2-7b-int8-s36",
            "llama2-7b-int8-kv8-ctx1024",
            "llama2-7b-tp2-int8-ctx1024",
            "llama2-7b-int8-spec-ngram",
            "llama2-7b-mixed-ctx1024",
            "llama2-7b-fleet-sweep",
            "llama2-7b-disagg-2rep",
            "llama2-7b-int8-spec-draft1b",
            "llama2-7b-int8-s32",
            "llama2-7b-int8-s16",
            "llama3.1-8b-int8-s32",
            "llama2-7b",
            "llama-1b",
        ]

    results: dict[str, dict] = {}
    last_err = ""
    # the LLM decode headline must not starve the other four BASELINE
    # configs (image/embeddings/ASR/finetune secondary children): a flat
    # 500s reserve is carved out of the deadline for the whole LLM-config
    # loop — both the break check and each config's timeout are computed
    # against (deadline - reserve), so the config in flight when budget
    # runs low cannot eat the breadth metrics' time either
    secondary_reserve = (
        0 if os.environ.get("BENCH_NO_SECONDARY") else 500
    )
    for i, model in enumerate(order):
        spec = CONFIGS.get(model)
        if spec is None:
            last_err = f"unknown config {model!r}"
            continue
        is_canary = len(order) > 1 and i == 0
        # the reserve binds BOTH the break check and each config's timeout —
        # otherwise the config in flight when budget ran low could run to
        # the wall and consume the breadth metrics' time anyway
        remaining = (deadline - secondary_reserve) - time.time() - 15
        if remaining < 60:
            last_err = last_err or "budget exhausted"
            break
        # a canary keeps >=60s reserved per pending config so it can't starve
        # them; real configs run with whatever remains (best-first order)
        reserve = 60 * (len(order) - i - 1) if is_canary else 0
        timeout = max(60, min(spec["timeout"], remaining - reserve))
        result, err = _run_config(model, env, timeout)
        if result is None:
            last_err = err
            continue
        results[model] = result
        if env.get("BENCH_FIRST_WIN") and not is_canary:
            break

    # the HEADLINE is pinned to the north-star family: vs_baseline compares
    # against the A100 Llama-2-7B number, so only llama2-7b* configs may
    # claim it (round-3 VERDICT: a 1B model must never be scored against
    # the 7B baseline). Other models still appear in all_configs.
    real = {k: v for k, v in results.items() if k.startswith("llama2-7b")}
    real = real or {k: v for k, v in results.items() if k != "tiny"} or results
    if not real:
        print(
            json.dumps(
                {
                    "metric": "serving decode throughput",
                    "value": 0.0,
                    "unit": "tok/s",
                    "vs_baseline": 0.0,
                    "error": last_err,
                }
            )
        )
        return 1

    best_name = max(real, key=lambda k: real[k]["value"])
    best = real[best_name]
    if not best_name.startswith("llama2-7b"):
        # fallback headline (7B configs all failed): vs_baseline against the
        # 7B A100 number would be dishonest for another model — null it out
        best["vs_baseline"] = 0.0
        best["baseline_note"] = (
            "no llama2-7b config completed; value is NOT comparable to the "
            "A100 llama2-7b baseline"
        )
    best["all_configs"] = {k: v["value"] for k, v in results.items()}

    # secondary metrics: one child per remaining BASELINE config —
    # images/sec (SDXL analog, text_to_image.py:11-13), embedding tok/s
    # (bge-small / TEI), ASR x-realtime (whisper-base), LoRA train tok/s
    # (llama2-7b fine-tune). Under BENCH_CPU=1 each child runs a tiny
    # path-proof instead so the METRIC PATHS stay proven end to end.
    secondary = {
        "image_gen": "--child-image",
        "embeddings": "--child-embed",
        "asr": "--child-asr",
        "finetune": "--child-finetune",
    }
    if not os.environ.get("BENCH_NO_SECONDARY"):
        for key, flag in secondary.items():
            if key == "image_gen" and os.environ.get("BENCH_NO_IMAGE"):
                continue  # BENCH_NO_IMAGE skips only the slow SD3 child
            if deadline - time.time() < 240:
                break
            child_env = dict(env)
            if env.get("BENCH_CPU"):
                child_env["BENCH_IMAGE_TINY"] = "1"  # image child's switch
                child_env["BENCH_TINY"] = "1"
            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), flag],
                    capture_output=True, text=True,
                    # keep ~180s in reserve so a slow compile can't starve
                    # the warm-boot proof that follows
                    timeout=max(120, min(600, deadline - time.time() - 180)),
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                    env=child_env,
                )
                result = _extract_json(proc.stdout)
                if result is not None:
                    best[key] = result
            except subprocess.TimeoutExpired:
                best[key] = {"error": "timeout"}

    # warm-boot proof for the compile cache: rerun the winner (tiny token
    # budget) — its compiles are now disk hits, so build+compile collapses.
    if deadline - time.time() > 150 and not env.get("BENCH_CPU"):
        warm_env = dict(env)
        warm_env["BENCH_WARM"] = "1"
        warm, _ = _run_config(
            best_name, warm_env, max(60, deadline - time.time() - 15)
        )
        if warm is not None:
            best["warm_build_s"] = warm["engine_build_s"]
            best["warm_compile_s"] = warm["compile_s"]

    print(json.dumps(best))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
