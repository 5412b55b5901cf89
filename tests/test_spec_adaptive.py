"""ISSUE 20 acceptance: fused adaptive speculative decoding.

Two layers, matching the design's split (docs/speculative.md):

- :class:`TestGammaController` — the pure γ-schedule policy
  (serving/spec_runtime/controller.py), exercised with hand-fed
  (proposed, accepted) rounds: acceptance collapse drives γ→0 within K
  rounds, probe/recovery hysteresis can't flap, batch pressure overrides
  without touching per-request state, requests are independent. No jax,
  no clocks — this is the fast tier.
- engine-level classes (slow tier) — the fused round wired into the
  scheduler: a hostile low-acceptance draft makes the controller retreat
  to whole-round classic fallbacks (the "spec can never cost latency"
  escape hatch; the wall-clock A/B lives in bench.py's
  ``tiny-spec-adaptive`` where timing is controlled), and the PR-12
  exactness contract — checkpoint/resume and live migration mid-stream on
  a SPECULATING engine stay token-identical, greedy, bf16 + int8.
"""

import threading

import pytest

import numpy as np


def _mk_ctrl(**kw):
    from modal_examples_tpu.serving.spec_runtime import (
        AdaptiveGammaController,
    )

    kw.setdefault("gamma_max", 4)
    return AdaptiveGammaController(**kw)


class TestGammaController:
    def test_optimistic_start_uses_full_depth(self):
        c = _mk_ctrl()
        assert c.gamma_for("r1") == 4

    def test_acceptance_collapse_drives_gamma_to_zero_within_k_rounds(self):
        """A request whose draft stops predicting it (acceptance 0) must
        stop speculating within a handful of rounds — with the default
        EWMA (α=0.4 from init 1.0) the third zero round crosses the 0.3
        collapse line: 0.6³ = 0.216."""
        c = _mk_ctrl()
        gammas = []
        for _ in range(6):
            g = c.gamma_for("r1")
            gammas.append(g)
            c.observe("r1", proposed=max(g, 1), accepted=0)
        assert gammas[0] == 4
        assert gammas[3] == 0, gammas  # collapsed after round 3's observe
        assert all(g == 0 for g in gammas[3:]), gammas
        assert c.snapshot()["r1"]["collapsed"] is True

    def test_gamma_tracks_ewma_between_full_and_collapse(self):
        """Partial acceptance scales γ smoothly: the budget is
        round(ewma * cap), never 0 while healthy (γ≥1 keeps evidence
        flowing) and never above the cap."""
        c = _mk_ctrl()
        for _ in range(8):
            g = c.gamma_for("r1")
            assert 1 <= g <= 4
            c.observe("r1", proposed=g, accepted=g // 2)
        assert not c.snapshot()["r1"]["collapsed"]

    def test_probe_cadence_and_recovery_hysteresis(self):
        """Collapsed requests emit a single probe every ``probe_every``
        rounds; recovery needs the EWMA back above ``recover_above``
        (0.6 > the 0.3 collapse line — the hysteresis band), so one good
        probe (EWMA 0.216→0.53, inside the band) must NOT re-enable
        speculation, while a second (→0.72) must."""
        c = _mk_ctrl(probe_every=4)
        for _ in range(3):
            c.observe("r1", proposed=4, accepted=0)  # collapse: ewma 0.216
        assert c.snapshot()["r1"]["collapsed"] is True

        # 3 silent rounds, then the probe
        assert [c.gamma_for("r1") for _ in range(4)] == [0, 0, 0, 1]
        c.observe("r1", proposed=1, accepted=1)  # ewma -> 0.5296: in-band
        assert c.snapshot()["r1"]["collapsed"] is True, "must not flap"

        assert [c.gamma_for("r1") for _ in range(4)] == [0, 0, 0, 1]
        c.observe("r1", proposed=1, accepted=1)  # ewma -> 0.7178: recovered
        assert c.snapshot()["r1"]["collapsed"] is False
        assert c.gamma_for("r1") >= 1

    def test_batch_pressure_zeroes_gamma_without_touching_state(self):
        """A full batch speculates for no one — but pressure is not
        evidence of bad acceptance: the EWMA and the probe counter must
        be untouched, so the next uncontended round resumes exactly where
        the request left off."""
        c = _mk_ctrl(probe_every=4)
        assert c.gamma_for("r1", batch_fill=1.0) == 0
        assert "r1" not in c.snapshot()  # no state even created
        for _ in range(3):
            c.observe("r1", proposed=4, accepted=0)  # collapse
        # pressure rounds must not advance the probe countdown
        for _ in range(10):
            assert c.gamma_for("r1", batch_fill=0.99) == 0
        assert [c.gamma_for("r1") for _ in range(4)] == [0, 0, 0, 1]

    def test_prefill_pressure_caps_gamma_at_one(self):
        c = _mk_ctrl()
        assert c.gamma_for("r1", prefill_pressure=True) == 1
        c.observe("r1", proposed=1, accepted=1)
        assert c.gamma_for("r1", prefill_pressure=False) == 4

    def test_gamma_cap_clamps_below_gamma_max(self):
        c = _mk_ctrl()
        assert c.gamma_for("r1", gamma_cap=2) == 2
        assert c.gamma_for("r1", gamma_cap=0) == 0

    def test_requests_are_independent(self):
        """One request's collapse must not leak into its batchmates —
        per-request EWMA is the whole point versus a global knob."""
        c = _mk_ctrl()
        for _ in range(5):
            c.observe("bad", proposed=4, accepted=0)
            c.observe("good", proposed=4, accepted=4)
        assert c.gamma_for("bad") == 0
        assert c.gamma_for("good") == 4

    def test_forget_drops_state(self):
        c = _mk_ctrl()
        for _ in range(5):
            c.observe("r1", proposed=4, accepted=0)
        assert c.gamma_for("r1") == 0
        c.forget("r1")
        assert "r1" not in c.snapshot()
        assert c.gamma_for("r1") == 4  # fresh optimistic start

    def test_zero_proposed_rounds_carry_no_evidence(self):
        """Classic-lane rounds (γ=0 dispatched) and empty n-gram lookups
        report proposed=0 — they must not drag the EWMA toward zero."""
        c = _mk_ctrl()
        for _ in range(50):
            c.observe("r1", proposed=0, accepted=0)
        assert c.gamma_for("r1") == 4

    def test_hysteresis_band_validated(self):
        with pytest.raises(ValueError, match="hysteresis"):
            _mk_ctrl(collapse_below=0.7, recover_above=0.3)

    def test_resolve_spec_adaptive_knob_rule(self, monkeypatch):
        """Explicit arg beats MTPU_SPEC_ADAPTIVE beats off (the
        MTPU_KV_DTYPE knob rule, resolved once at engine build)."""
        from modal_examples_tpu.serving.spec_runtime import (
            SPEC_ADAPTIVE_ENV,
            resolve_spec_adaptive,
        )

        monkeypatch.delenv(SPEC_ADAPTIVE_ENV, raising=False)
        assert resolve_spec_adaptive(None) is False
        assert resolve_spec_adaptive(True) is True
        monkeypatch.setenv(SPEC_ADAPTIVE_ENV, "1")
        assert resolve_spec_adaptive(None) is True
        assert resolve_spec_adaptive(False) is False


# ---------------------------------------------------------------------------
# engine level: slow tier (compiles tiny models)
# ---------------------------------------------------------------------------

PROMPT = "the quick brown fox jumps over the lazy dog and naps in the sun"


def _mk_engine(jax, speculative=None, params=None, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (16, 32))
    return LLMEngine(
        llama.LlamaConfig.tiny(), params=params, seed=0,
        speculative=speculative, **kw,
    )


@pytest.mark.slow
class TestEngineAdaptive:
    def test_hostile_draft_retreats_to_classic_fallbacks(self, jax_cpu):
        """The A/B the controller exists for, structurally: a random
        (unrelated) draft model yields near-chance acceptance, so with
        the controller ON the engine must (a) collapse the request's
        EWMA, (b) dispatch whole-round classic fallbacks instead of
        burning draft+verify flops, and (c) still be token-identical to
        the plain engine. The wall-clock half of the A/B (adaptive TPOT
        ≤ spec-off TPOT under this workload) runs where timing is
        controlled: bench.py ``tiny-spec-adaptive``, gated in benchdiff."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import SamplingParams

        plain = _mk_engine(jax_cpu)
        eng = _mk_engine(
            jax_cpu, params=plain.params,
            speculative=(llama.LlamaConfig.tiny(), 4), spec_adaptive=True,
        )
        try:
            assert eng.spec_adaptive is True
            sp = SamplingParams(max_tokens=40, temperature=0.0)
            want = plain.generate(PROMPT, sp)
            got = eng.generate(PROMPT, sp)
            assert got == want
            # near-chance acceptance over a 512-vocab: the controller
            # must have stopped paying for speculation
            assert eng.stats.acceptance_rate() < 0.6
            assert eng._spec_fallbacks > 0, (
                "collapse never produced a whole-round classic fallback"
            )
            assert eng.error_count == 0, eng.error_log
        finally:
            plain.stop()
            eng.stop()

    def test_adaptive_keeps_depth_when_draft_is_perfect(self, jax_cpu):
        """Self-draft (draft == target): acceptance ~1.0, so the
        controller must keep γ at full depth — adaptivity may only ever
        remove unprofitable speculation, never profitable."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig.tiny()
        params0 = llama.init_params(jax_cpu.random.PRNGKey(0), cfg)
        eng = LLMEngine(
            cfg, params0, max_slots=2, max_model_len=128, page_size=8,
            prefill_buckets=(16, 32), seed=0,
            speculative=(cfg, 4), draft_params=params0, spec_adaptive=True,
        )
        try:
            eng.generate(
                PROMPT, SamplingParams(max_tokens=24, temperature=0.0)
            )
            assert eng.stats.acceptance_rate() > 0.95
            assert eng._spec_fallbacks == 0
            assert eng._spec_rounds > 0
            # tokens-per-dispatch is the win: γ=4 fully accepted → 5
            assert (
                eng._spec_round_tokens / eng._spec_rounds > 2.0
            ), (eng._spec_round_tokens, eng._spec_rounds)
        finally:
            eng.stop()

    def test_spec_depth_runtime_mutable_for_bench_ab(self, jax_cpu):
        """bench.py A/Bs fixed-vs-adaptive on ONE live engine by mutating
        ``spec_depth``/``spec_adaptive`` — γ=0 must behave classic (and
        stay token-identical) without a rebuild."""
        from modal_examples_tpu.serving import SamplingParams

        eng = _mk_engine(jax_cpu, speculative=("ngram", 4))
        try:
            sp = SamplingParams(max_tokens=16, temperature=0.0)
            want = eng.generate("one two one two one two", sp)
            rounds_before = eng._spec_rounds
            assert rounds_before > 0
            eng.spec_depth = 0  # spec OFF: every round is a fallback
            got = eng.generate("one two one two one two", sp)
            assert got == want
            assert eng._spec_rounds == rounds_before
            eng.spec_depth = eng.spec_gamma  # back ON
            got2 = eng.generate("one two one two one two", sp)
            assert got2 == want
            assert eng._spec_rounds > rounds_before
        finally:
            eng.stop()


@pytest.mark.slow
class TestSpecExactnessUnderFailover:
    """PR-12 × PR-20: the failover exactness contract holds on a
    SPECULATING engine — a checkpoint can only be cut at a harvest
    boundary (the PR-19 rule), so a resumed/migrated stream re-enters
    mid-speculation token-identically."""

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_resume_mid_stream_token_identical(self, jax_cpu, kv_dtype):
        from modal_examples_tpu.serving import SamplingParams

        sp = SamplingParams(max_tokens=12, temperature=0.0)
        eng = _mk_engine(
            jax_cpu, speculative=("ngram", 4), kv_dtype=kv_dtype,
        )
        try:
            ref = eng.submit("one two one two one two", sp)
            ref_text = "".join(eng.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            n = ref.n_generated
            assert eng._spec_rounds > 0  # the ref run really speculated
            for k in (1, n // 2, n - 1):
                req = eng.make_request("one two one two one two", sp)
                req.auto_seed = ref.auto_seed
                eng.submit_resumed(
                    req,
                    prompt_tokens=ref.prompt_tokens,
                    generated=ref_tokens[:k],
                    emitted_len=0,
                )
                out = "".join(eng.stream(req))
                assert req.generated_tokens == ref_tokens, (kv_dtype, k)
                assert out == ref_text, (kv_dtype, k)
            from modal_examples_tpu.faults.chaos import check_drained

            assert check_drained({"eng": eng}) == []
        finally:
            eng.stop()

    def test_migrate_mid_stream_token_identical(self, jax_cpu):
        import time

        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        sp = SamplingParams(max_tokens=32, temperature=0.0)
        eng_a = _mk_engine(jax_cpu, speculative=("ngram", 4))
        eng_b = _mk_engine(
            jax_cpu, speculative=("ngram", 4), params=eng_a.params
        )
        rep_a = EngineReplica(eng_a, "spec-a", role="unified")
        rep_b = EngineReplica(eng_b, "spec-b", role="unified")
        try:
            ref = eng_b.submit("red blue red blue red blue", sp)
            ref_text = "".join(eng_b.stream(ref))
            ref_tokens = list(ref.generated_tokens)

            req = rep_a.submit("red blue red blue red blue", sp)
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng_a.stream(req))
            )
            t.start()
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if len(req.generated_tokens) >= 5:
                    break
                time.sleep(0.005)
            assert len(req.generated_tokens) >= 5
            result = fo.migrate_request(rep_a, rep_b, req, chunk_bytes=512)
            assert result == "ok"
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.generated_tokens == ref_tokens
            assert "".join(pieces) == ref_text
            # the adopted stream kept speculating on B (ngram index was
            # rebuilt from prompt+generated history at adoption)
            assert eng_b.stats.spec_proposed > 0
        finally:
            eng_a.stop()
            eng_b.stop()


@pytest.mark.slow
class TestSpecObservability:
    def test_gauges_and_trace_events_emitted(self, jax_cpu):
        """Declared⇔emitted, live: a speculating engine's gauge sweep
        must land the mtpu_spec_* series in the registry with real
        values (the static closure test only proves call sites exist)."""
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.utils.prometheus import parse_exposition
        from modal_examples_tpu.serving import SamplingParams

        eng = _mk_engine(jax_cpu, speculative=("ngram", 4))
        try:
            eng.generate(
                "one two one two one two",
                SamplingParams(max_tokens=16, temperature=0.0),
            )
            eng._metrics_wall = 0.0  # defeat the sweep throttle
            eng._refresh_gauges()
            from modal_examples_tpu.utils.prometheus import (
                default_registry,
            )

            exp = parse_exposition(default_registry.expose())
            assert exp.peak(C.SPEC_TOKENS_PER_DISPATCH) >= 1.0
            assert exp.peak(C.SPEC_ACCEPTANCE_RATE) > 0.0
        finally:
            eng.stop()
