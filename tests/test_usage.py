"""Hardware-utilization accounting (ISSUE 17,
docs/observability.md#roofline-and-usage-accounting): the analytic work
model hand-checked against the formulas (bf16 AND int8 KV), fake-clock
MFU/MBU determinism, per-tenant conservation under concurrent streams and
sheds (Σ tenants == the engine's own counters, Σ journal == the same), and
the read surfaces — `tpurun usage`, the gateway `/usage` snapshot, the
OpenAI `cached_tokens` usage field, and benchdiff's hardware-identity
refusal."""

import json
import threading
import urllib.request

import pytest

from modal_examples_tpu.observability import catalog as C
from modal_examples_tpu.observability import usage as us
from modal_examples_tpu.utils.prometheus import Registry


class _Req:
    """The slice of ``serving.engine.Request`` the accountant touches."""

    def __init__(self, rid="req-1", tenant="acme", priority="default"):
        self.request_id = rid
        self.tenant = tenant
        self.priority = priority
        self.n_generated = 0
        self.cached_prompt_tokens = 0


# ---------------------------------------------------------------------------
# the analytic work model
# ---------------------------------------------------------------------------


class TestWorkModel:
    def test_formulas_hand_checked(self):
        m = us.WorkModel(
            n_params=1000, n_layers=2, dim=8,
            weight_bytes=2000, kv_bytes_per_token=64.0,
        )
        # prefill: 2·N·T + 2·L·D·ΣT²
        assert m.prefill_flops(10, sq_tokens=100) == (
            2 * 1000 * 10 + 2 * 2 * 8 * 100
        )
        # decode: 2·N per token + 4·L·D·ctx
        assert m.decode_flops(5, ctx_sum=50) == (
            2 * 1000 * 5 + 4 * 2 * 8 * 50
        )
        # prefill bytes: one weight stream per dispatched program + KV write
        assert m.prefill_bytes(10, n_calls=2) == 2 * 2000 + 64 * 10
        # decode bytes: weight stream per token + KV history read
        assert m.decode_bytes(5, ctx_sum=50) == 5 * 2000 + 64 * 50
        # the attention terms need ΣT², not (ΣT)²: two 10-token prompts
        # cost less than one 20-token prompt
        assert m.prefill_flops(20, sq_tokens=2 * 10 * 10) < m.prefill_flops(
            20, sq_tokens=20 * 20
        )

    def test_from_engine_bf16_tiny(self, jax_cpu):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.models.quantize import param_bytes
        from modal_examples_tpu.serving.kv_cache import PagedKVCache

        cfg = llama.LlamaConfig.tiny()  # dim 128, L2, H4, Hkv2 -> hd 32
        cache = PagedKVCache.create(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.dim // cfg.n_heads, n_pages=8, page_size=16,
        )
        params = llama.init_params(jax_cpu.random.PRNGKey(0), cfg)
        m = us.WorkModel.from_engine(
            cfg, cache=cache, weight_bytes=param_bytes(params)
        )
        assert m.n_params == cfg.param_count
        assert m.weight_bytes == 2 * cfg.param_count  # bf16: 2 B/param
        # bf16 KV/token: k+v · L · Hkv · hd · 2 B = 2·2·2·32·2 = 512
        assert m.kv_bytes_per_token == 512.0
        assert m.kv_bytes_per_token == cache.bytes() / (
            cache.n_pages * cache.page_size
        )

    def test_from_engine_int8_halves_kv_bytes(self, jax_cpu):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving.kv_cache import PagedKVCache

        cfg = llama.LlamaConfig.tiny()
        cache = PagedKVCache.create(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.dim // cfg.n_heads, n_pages=8, page_size=16,
            kv_dtype="int8",
        )
        m = us.WorkModel.from_engine(cfg, cache=cache, weight_bytes=1)
        # int8 KV/token: payload k+v·L·Hkv·hd·1 B = 256, plus the f32
        # scale rows k+v·L·Hkv·4 B = 32 -> 288; the model prices the cache
        # the engine actually allocated, so int8 halves modeled traffic
        assert m.kv_bytes_per_token == 288.0
        assert m.kv_bytes_per_token == cache.bytes() / (
            cache.n_pages * cache.page_size
        )
        assert m.kv_bytes_per_token < 512.0


class TestResolvePeaks:
    def test_explicit_beats_env_beats_default(self, monkeypatch):
        monkeypatch.setenv(us.GENERATION_ENV, "v4")
        assert us.resolve_peaks("v5p")["generation"] == "v5p"
        assert us.resolve_peaks()["generation"] == "v4"
        monkeypatch.delenv(us.GENERATION_ENV)
        assert us.resolve_peaks()["generation"] == us.DEFAULT_GENERATION

    def test_unknown_generation_raises_and_chips_scale(self, monkeypatch):
        with pytest.raises(ValueError, match="tpu9000"):
            us.resolve_peaks("tpu9000")
        monkeypatch.setenv(us.GENERATION_ENV, "tpu9000")
        with pytest.raises(ValueError, match="tpu9000"):
            us.resolve_peaks()
        p = us.resolve_peaks("v5e", chips=4)
        assert p["chips"] == 4
        # published per-chip peaks (Google Cloud "TPU v5e" documentation)
        assert p["tflops_per_chip"] == 197.0
        assert p["hbm_gbps_per_chip"] == 819.0

    def test_on_a_tpu_the_device_kind_decides(self, monkeypatch):
        import jax

        class _Dev:
            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv(us.GENERATION_ENV, "v4")  # ignored on a TPU
        monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v6 lite")])
        assert us.resolve_peaks()["generation"] == "v6e"
        monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 mega")])
        with pytest.raises(ValueError, match="TPU v9 mega"):
            us.resolve_peaks()


# ---------------------------------------------------------------------------
# the meter: fake-clock determinism, conservation, delta flush
# ---------------------------------------------------------------------------


def _meter(registry=None, journal_path=None, chips=1):
    model = us.WorkModel(
        n_params=1000, n_layers=2, dim=8,
        weight_bytes=2000, kv_bytes_per_token=64.0,
    )
    return us.EngineUsage(
        model, name="eng-0", generation="v5e", chips=chips,
        registry=registry, journal_path=journal_path,
    )


class TestEngineUsageMeter:
    def test_roofline_is_deterministic_and_hand_checkable(self):
        # 7B-class numbers so the achieved fractions survive summary()'s
        # 6-decimal rounding and land in the regime the meter exists for
        N, L, D = 7_000_000_000, 32, 4096
        WB, KVB = 7_000_000_000, 262_144  # int8 weights, bf16 KV/token

        def drive():
            u = us.EngineUsage(
                us.WorkModel(
                    n_params=N, n_layers=L, dim=D,
                    weight_bytes=WB, kv_bytes_per_token=float(KVB),
                ),
                name="eng-0", generation="v5e",
            )
            req = _Req()
            u.note_prompt(req, 512)
            u.note_phase_seconds("prefill", 0.5)
            for ctx in (512, 513, 514):
                u.note_token(req, ctx)
            u.note_phase_seconds("decode", 2.0)
            return u.summary()

        a, b = drive(), drive()
        assert a == b  # seconds come from the injected brackets: exact
        peaks = us.resolve_peaks("v5e")
        pre = a["phases"]["prefill"]
        pre_flops = 2 * N * 512 + 2 * L * D * 512 * 512
        assert pre["flops"] == pre_flops
        assert pre["bytes"] == WB + KVB * 512  # one dispatched program
        assert pre["mfu"] == pytest.approx(
            pre_flops / (0.5 * peaks["tflops_per_chip"] * 1e12), abs=1e-6
        )
        dec = a["phases"]["decode"]
        ctx_sum = 512 + 513 + 514
        dec_bytes = 3 * WB + KVB * ctx_sum
        assert dec["flops"] == 2 * N * 3 + 4 * L * D * ctx_sum
        assert dec["bytes"] == dec_bytes
        assert dec["mbu"] == pytest.approx(
            dec_bytes / (2.0 * peaks["hbm_gbps_per_chip"] * 1e9), abs=1e-6
        )
        tot = a["phases"]["total"]
        assert tot["flops"] == pre["flops"] + dec["flops"]
        assert tot["device_seconds"] == pytest.approx(2.5)
        # decode streams bytes, not flops: bandwidth-bound by a wide margin
        assert dec["bound"] == "bandwidth"

    def test_zero_seconds_yields_null_bound(self):
        u = _meter()
        u.note_prompt(_Req(), 10)
        s = u.summary()
        assert s["phases"]["prefill"]["mfu"] == 0.0
        assert s["phases"]["prefill"]["bound"] is None
        # ...and the BENCH section defaults the classification to the
        # decode-dominated truth instead of exporting null
        sec = u.utilization_section()
        assert sec["bound"] == "bandwidth"
        assert sec["tokens_per_second_per_chip"] is None

    def test_utilization_section_shape_and_chip_normalization(self):
        u = _meter(chips=2)
        u.note_prompt(_Req(), 10)
        u.note_phase_seconds("prefill", 1.0)
        sec = u.utilization_section(tokens_per_second=100.0)
        assert sec["chips"] == 2
        assert sec["tokens_per_second_per_chip"] == 50.0
        assert set(sec["per_phase"]) == {"prefill", "decode"}
        assert sec["work_model"] == {
            "n_params": 1000, "weight_bytes": 2000,
            "kv_bytes_per_token": 64.0,
        }

    def test_tenant_buckets_conserve_and_sort(self):
        u = _meter()
        a, b = _Req("r1", tenant="a"), _Req("r2", tenant="b", priority="batch")
        u.note_prompt(a, 10)
        u.note_prompt(b, 20)
        u.note_token(a, 10)
        u.note_token(a, 11)
        u.note_token(b, 20)
        u.note_slot_release(a, pages=4, held_s=2.0)
        t = u.tenants()
        assert [r["tenant"] for r in t["tenants"]] == ["a", "b"]
        assert t["totals"]["prompt_tokens"] == 30
        assert t["totals"]["generated_tokens"] == 3
        assert t["totals"]["device_seconds"] == pytest.approx(2.0)
        assert t["totals"]["kv_page_seconds"] == pytest.approx(8.0)
        assert t["totals"]["requests"] == 2

    def test_flush_emits_deltas_not_totals(self):
        reg = Registry()
        u = _meter(registry=reg)
        req = _Req(tenant="a")
        labels = {"tenant": "a", "class": "default"}
        u.note_prompt(req, 10)
        u.note_token(req, 10)
        u.flush()
        assert reg.value(C.USAGE_PROMPT_TOKENS_TOTAL, labels) == 10.0
        assert reg.value(C.USAGE_GENERATED_TOKENS_TOTAL, labels) == 1.0
        u.flush()  # no new work: counters must NOT double
        assert reg.value(C.USAGE_PROMPT_TOKENS_TOTAL, labels) == 10.0
        u.note_token(req, 11)
        u.flush()
        assert reg.value(C.USAGE_GENERATED_TOKENS_TOTAL, labels) == 2.0
        # roofline gauges refresh on every flush, all phases present
        for phase in C.ROOFLINE_PHASES:
            assert reg.value(C.MFU, {"phase": phase}) is not None
            assert reg.value(C.HBM_BW_UTIL, {"phase": phase}) is not None

    def test_finish_journals_once_with_accounted_tokens(self, tmp_path):
        path = tmp_path / "usage.jsonl"
        u = _meter(journal_path=path)
        req = _Req("req-9", tenant="acme", priority="interactive")
        u.note_prompt(req, 12)
        req.n_generated = 3
        req.cached_prompt_tokens = 16
        u.note_finish(req, "stop")
        u.note_finish(req, "stop")  # double-finish: journals exactly once
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(recs) == 1
        assert recs[0]["request_id"] == "req-9"
        assert recs[0]["tenant"] == "acme"
        assert recs[0]["class"] == "interactive"
        assert recs[0]["prompt_tokens"] == 12  # the ACCOUNTED figure
        assert recs[0]["generated_tokens"] == 3
        assert recs[0]["cached_prompt_tokens"] == 16
        assert recs[0]["finish_reason"] == "stop"
        totals = us.journal_tenant_totals(recs)
        assert totals == {"acme": {
            "prompt_tokens": 12, "generated_tokens": 3, "requests": 1,
        }}

    def test_shed_never_prefilled_journals_zero_prompt(self, tmp_path):
        # conservation depends on the journal recording what was ACCOUNTED:
        # a request shed before prefill contributes 0, not its prompt length
        path = tmp_path / "usage.jsonl"
        u = _meter(journal_path=path)
        req = _Req("req-shed")
        u.note_finish(req, "shed")
        rec = json.loads(path.read_text())
        assert rec["prompt_tokens"] == 0
        assert rec["generated_tokens"] == 0

    def test_admission_shed_charges_the_turned_away_tenant(self):
        from modal_examples_tpu.scheduling.admission import (
            AdmissionConfig, AdmissionController, ShedError,
        )
        from modal_examples_tpu.scheduling.policy import ScheduledRequest

        reg = Registry()
        u = _meter(registry=reg)
        ctl = AdmissionController(AdmissionConfig(max_queue={"default": 0}))
        ctl.usage = u  # the engine wires this at build
        entry = ScheduledRequest(payload=None, tenant="noisy", cost=1)
        with pytest.raises(ShedError):
            ctl.admit(entry, depths={"default": 0}, pages_used=0,
                      pages_total=8)
        assert u.tenants()["totals"]["sheds"] == 1
        # sheds emit immediately (rare events skip the delta flush)
        assert reg.value(
            C.USAGE_SHEDS_TOTAL, {"tenant": "noisy", "class": "default"}
        ) == 1.0


# ---------------------------------------------------------------------------
# live-engine conservation: Σ tenants == engine counters, Σ journal == same
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(jax_cpu):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    cfg = llama.LlamaConfig.tiny()
    eng = LLMEngine(
        cfg, max_slots=4, max_model_len=128, page_size=16,
        prefill_buckets=(32, 64), seed=0,
    )
    yield eng
    eng.stop()


class TestEngineConservation:
    def test_concurrent_streams_conserve_exactly(self, engine):
        from modal_examples_tpu.serving.sampling import SamplingParams

        reqs, errs = [], []

        def run(tenant, klass, prompt):
            try:
                req = engine.submit(
                    prompt, SamplingParams(max_tokens=6, temperature=0.0),
                    tenant=tenant, priority=klass,
                )
                reqs.append(req)
                for _ in engine.stream(req):
                    pass
            except Exception as e:  # surface thread failures in the assert
                errs.append(e)

        threads = [
            threading.Thread(target=run, args=args)
            for args in (
                ("acme", "interactive", "the quick brown fox jumps"),
                ("acme", "default", "pack my box with five dozen jugs"),
                ("globex", "default", "sphinx of black quartz judge my vow"),
                ("globex", "batch", "how vexingly quick daft zebras jump"),
                ("initech", "default", "the five boxing wizards jump"),
            )
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs, errs
        assert len(reqs) == 5

        # Σ per-tenant buckets == the engine's own ledger, EXACTLY — the
        # hooks sit at the same sites that bump EngineStats, so this holds
        # under concurrency without reconciliation
        totals = engine.usage.tenants()["totals"]
        assert totals["prompt_tokens"] == engine.stats.prompt_tokens
        assert totals["generated_tokens"] == engine.stats.generated_tokens
        assert totals["requests"] == 5
        assert totals["device_seconds"] > 0
        assert totals["kv_page_seconds"] > 0

        # Σ journal == the same counters (the offline half): the session
        # state dir is shared, so filter to THIS engine's request ids
        ids = {r.request_id for r in reqs}
        recs = [
            r for r in us.read_usage_journal(n=10_000)
            if r["request_id"] in ids
        ]
        assert len(recs) == 5
        jt = us.journal_tenant_totals(recs)
        assert sum(b["prompt_tokens"] for b in jt.values()) == (
            engine.stats.prompt_tokens
        )
        assert sum(b["generated_tokens"] for b in jt.values()) == (
            engine.stats.generated_tokens
        )
        # per-tenant split matches the buckets, not just the grand total
        by_tenant = {}
        for row in engine.usage.tenants()["tenants"]:
            b = by_tenant.setdefault(row["tenant"], 0)
            by_tenant[row["tenant"]] = b + row["prompt_tokens"]
        assert {t: b["prompt_tokens"] for t, b in jt.items()} == by_tenant

        # device time was attributed to both phases by the clock brackets
        phases = engine.usage.summary()["phases"]
        assert phases["prefill"]["device_seconds"] > 0
        assert phases["decode"]["device_seconds"] > 0
        assert phases["total"]["bound"] in ("compute", "bandwidth")

    def test_prefix_cache_hit_reports_cached_tokens(self, engine):
        from modal_examples_tpu.serving.sampling import SamplingParams

        prompt = "a shared system prompt long enough to fill pages " * 2
        p = SamplingParams(max_tokens=2, temperature=0.0)
        first = engine.submit(prompt, p, tenant="cachet")
        for _ in engine.stream(first):
            pass
        second = engine.submit(prompt, p, tenant="cachet")
        for _ in engine.stream(second):
            pass
        # the repeat prompt serves its full pages from the prefix cache
        assert second.cached_prompt_tokens >= engine.cache.page_size
        assert second.cached_prompt_tokens <= engine.stats.prompt_tokens
        rec = [
            r for r in us.read_usage_journal(n=10_000)
            if r["request_id"] == second.request_id
        ]
        assert rec and rec[0]["cached_prompt_tokens"] == (
            second.cached_prompt_tokens
        )

    def test_openai_usage_carries_cached_tokens_field(self, engine):
        from modal_examples_tpu.serving import OpenAIServer

        srv = OpenAIServer(
            engine, model_name="tiny-usage", host="127.0.0.1", port=0
        )
        srv.start()
        try:
            body = json.dumps({
                "messages": [{"role": "user", "content": "count me"}],
                "max_tokens": 3,
                "temperature": 0.0,
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/chat/completions",
                data=body, headers={"content-type": "application/json"},
            )
            with urllib.request.urlopen(req) as r:
                out = json.load(r)
        finally:
            srv.httpd.shutdown()
        usage = out["usage"]
        details = usage.get("prompt_tokens_details")
        assert details is not None, usage
        assert isinstance(details["cached_tokens"], int)
        assert 0 <= details["cached_tokens"] <= usage["prompt_tokens"]

    def test_gateway_usage_snapshot_sees_live_engine(self, engine):
        from modal_examples_tpu.web.gateway import _usage_snapshot

        snap = _usage_snapshot(last=5)
        eng = snap["engines"].get(engine.usage.replica)
        assert eng is not None, list(snap["engines"])
        assert "phases" in eng["roofline"]
        assert eng["totals"]["prompt_tokens"] == engine.stats.prompt_tokens
        assert isinstance(snap["records"], list)
        assert isinstance(snap["journal_totals"], dict)


# ---------------------------------------------------------------------------
# CLI surface (jax-free)
# ---------------------------------------------------------------------------


class TestCliUsage:
    def test_cmd_usage_json_reads_journal_and_metrics(
        self, tmp_path, capsys
    ):
        from modal_examples_tpu.core.cli import cmd_usage
        from modal_examples_tpu.observability.journal import named_journal

        j = named_journal("usage", path=tmp_path / "usage.jsonl")
        j.record({
            "request_id": "req-1", "tenant": "acme", "class": "default",
            "prompt_tokens": 40, "generated_tokens": 8,
            "cached_prompt_tokens": 0, "finish_reason": "stop",
        })
        # a pushed exposition carrying the per-tenant counters
        reg = Registry()
        reg.counter_inc(
            C.USAGE_PROMPT_TOKENS_TOTAL,
            40.0, {"tenant": "acme", "class": "default"},
        )
        reg.gauge_set(C.MFU, 0.25, {"phase": "total"})
        mdir = tmp_path / "metrics"
        mdir.mkdir()
        (mdir / "job1.prom").write_text(reg.expose())

        rc = cmd_usage(["--json", "--dir", str(tmp_path)])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["journal_totals"]["acme"]["prompt_tokens"] == 40
        assert out["records"][0]["request_id"] == "req-1"
        row = [t for t in out["tenants"] if t["tenant"] == "acme"]
        assert row and row[0]["prompt_tokens"] == 40.0
        assert out["roofline"]["total"]["mfu"] == 0.25

    def test_cmd_usage_text_renders_table(self, tmp_path, capsys):
        from modal_examples_tpu.core.cli import cmd_usage
        from modal_examples_tpu.observability.journal import named_journal

        named_journal("usage", path=tmp_path / "usage.jsonl").record({
            "request_id": "req-2", "tenant": "acme", "class": "batch",
            "prompt_tokens": 5, "generated_tokens": 1,
        })
        assert cmd_usage(["--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "acme" in out


# ---------------------------------------------------------------------------
# benchdiff: utilization gates + hardware-identity refusal
# ---------------------------------------------------------------------------


def _bench_json(tmp_path, name, **extra):
    doc = {"metric": "m", "value": 100.0, "unit": "tok/s", **extra}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


class TestBenchDiffIdentity:
    def test_mismatch_needs_both_sides_present(self):
        from modal_examples_tpu.utils import bench_diff as bd

        assert bd.identity_mismatches(
            {"backend": "cpu"}, {"backend": "tpu"}
        ) == ["backend: 'cpu' != 'tpu'"]
        # absent keys never disqualify: older files predate them
        assert bd.identity_mismatches({}, {"backend": "tpu"}) == []
        assert bd.identity_mismatches(
            {"backend": "tpu"}, {"backend": "tpu"}
        ) == []

    def test_run_diff_refuses_cross_hardware_compare(self, tmp_path, capsys):
        from modal_examples_tpu.utils.bench_diff import run_diff

        old = _bench_json(tmp_path, "old.json", backend="tpu")
        new = _bench_json(tmp_path, "new.json", backend="cpu")
        assert run_diff([old, new]) == 2
        out = capsys.readouterr().out
        assert "HARDWARE MISMATCH" in out
        assert "refusing" in out

    def test_allow_backend_mismatch_overrides_loudly(self, tmp_path, capsys):
        from modal_examples_tpu.utils.bench_diff import run_diff

        old = _bench_json(tmp_path, "old.json", backend="tpu")
        new = _bench_json(tmp_path, "new.json", backend="cpu")
        rc = run_diff([old, new, "--allow-backend-mismatch"])
        assert rc in (0, 1)  # the diff itself proceeds
        out = capsys.readouterr().out
        assert "HARDWARE MISMATCH" in out
        assert "--allow-backend-mismatch set" in out

    def test_same_hardware_diffs_quietly(self, tmp_path, capsys):
        from modal_examples_tpu.utils.bench_diff import run_diff

        old = _bench_json(tmp_path, "old.json", backend="cpu")
        new = _bench_json(tmp_path, "new.json", backend="cpu")
        assert run_diff([old, new]) == 0
        assert "MISMATCH" not in capsys.readouterr().out

    def test_utilization_metrics_are_gated(self, tmp_path):
        from modal_examples_tpu.utils.bench_diff import compare

        old = {"value": 100.0, "utilization": {
            "mfu": 0.40, "mbu": 0.70, "tokens_per_second_per_chip": 100.0,
        }}
        new = {"value": 100.0, "utilization": {
            "mfu": 0.10, "mbu": 0.70, "tokens_per_second_per_chip": 100.0,
        }}
        rows = {r["metric"]: r for r in compare(old, new)}
        # abs comparison, the shed-rate rule: 0.40 -> 0.10 is a regression
        assert rows["utilization.mfu"]["regressed"] is True
        assert rows["utilization.mbu"]["regressed"] is False
        assert "utilization.tokens_per_second_per_chip" in rows


# ---------------------------------------------------------------------------
# the mbu_collapse alert: guarded threshold
# ---------------------------------------------------------------------------


class TestMbuCollapseAlert:
    def _rule(self):
        from modal_examples_tpu.observability import alerts as al

        rules = [r for r in al.DEFAULT_RULES if r.name == "mbu_collapse"]
        assert len(rules) == 1
        return rules[0]

    def _evaluator(self, tmp_path):
        from modal_examples_tpu.observability import alerts as al

        class Src:
            records: list = []

            def recent(self, window_s=None):
                return list(self.records)

        src = Src()
        src.records = []
        ev = al.AlertEvaluator(
            (self._rule(),), source=src, registry=Registry(),
            journal_path=tmp_path / "alerts.jsonl",
        )
        return ev, src

    @staticmethod
    def _rec(at, mbu, slots):
        return {"at": at, "series": [
            [C.HBM_BW_UTIL, {"phase": "decode"}, "gauge", mbu, 0.0],
            [C.ACTIVE_SLOTS, {}, "gauge", slots, 0.0],
        ]}

    def test_idle_engine_never_fires(self, tmp_path):
        # zero MBU with zero slots is just an idle engine
        ev, src = self._evaluator(tmp_path)
        for at in (10.0, 40.0, 80.0):
            src.records.append(self._rec(at, 0.0, 0))
            assert ev.evaluate_once(now=at) == []

    def test_collapse_under_load_fires_after_for_s(self, tmp_path):
        ev, src = self._evaluator(tmp_path)
        src.records.append(self._rec(10.0, 0.0, 3))
        assert ev.evaluate_once(now=10.0) == []  # held 0s < for_s=20
        src.records.append(self._rec(31.0, 0.0, 3))
        out = ev.evaluate_once(now=31.0)
        assert [t["event"] for t in out] == ["fire"]
        # bandwidth flows again: hysteretic clear
        src.records.append(self._rec(32.0, 0.4, 3))
        assert ev.evaluate_once(now=32.0) == []
        src.records.append(self._rec(43.0, 0.4, 3))
        assert [t["event"] for t in ev.evaluate_once(now=43.0)] == ["clear"]

    def test_healthy_decode_never_fires(self, tmp_path):
        ev, src = self._evaluator(tmp_path)
        for at in (10.0, 35.0, 60.0):
            src.records.append(self._rec(at, 0.55, 3))
            assert ev.evaluate_once(now=at) == []
