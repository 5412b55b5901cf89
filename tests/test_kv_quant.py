"""int8 quantized paged-KV cache (docs/kv_cache.md).

The accuracy contract is tolerance-based, never token-exact (KV
quantization legitimately changes logits — vLLM's fp8 KV does too):

- quantize/dequant round trip is bounded by amax/254 per element;
- interpreter-mode int8 paged decode — BOTH ragged variants and the XLA
  gather fallback — matches the f32-cache reference within the declared
  logit-drift tolerance, and matches the XLA fallback over the SAME
  quantized cache much tighter (identical dequantized values);
- the default (bf16/f32) path constructs no QuantizedKV anywhere: 2-leaf
  cache, pass-through helpers — bit-identical to the pre-int8 code.
"""

import chunk_tail
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modal_examples_tpu import ops
from modal_examples_tpu.models import llama
from modal_examples_tpu.ops import reference
from modal_examples_tpu.ops.kv_quant import (
    QuantizedKV,
    dequantize_kv,
    is_quantized,
    kv_dtype_name,
    kv_empty,
    kv_gather,
    kv_scatter,
    quantize_kv,
    resolve_kv_dtype,
)
from modal_examples_tpu.serving.kv_cache import PagedKVCache

#: declared logit-drift tolerance for int8 KV vs the f32 cache on the tiny
#: random-weight models (logit scale ~3; per-token-head int8 => ~2% drift)
LOGIT_TOL = 0.25


# -- quantize/dequant primitives --------------------------------------------


class TestQuantizeKV:
    def test_roundtrip_error_bound(self):
        x = jax.random.normal(
            jax.random.PRNGKey(0), (2, 5, 16, 4, 64), jnp.float32
        )
        q = quantize_kv(x)
        assert q.data.dtype == jnp.int8
        assert q.scale.shape == x.shape[:-1]
        deq = dequantize_kv(q, jnp.float32)
        # per (token, head) row: |x - deq| <= scale/2 (+ rounding slack)
        bound = q.scale[..., None] * 0.51
        assert bool(jnp.all(jnp.abs(deq - x) <= bound))

    def test_zero_rows_exact(self):
        x = jnp.zeros((2, 3, 8), jnp.float32)
        q = quantize_kv(x)
        assert bool(jnp.all(q.scale == 1.0))  # no div-by-zero scales
        assert bool(jnp.all(dequantize_kv(q, jnp.float32) == 0.0))

    def test_deterministic(self):
        # the prefix cache relies on same-values => same quantized bytes
        # when concurrent prefills rewrite a shared page
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 4, 32))
        a, b = quantize_kv(x), quantize_kv(x)
        assert bool(jnp.all(a.data == b.data))
        assert bool(jnp.all(a.scale == b.scale))

    def test_pytree_two_leaves_and_scan_slicing(self):
        q = quantize_kv(jnp.ones((4, 2, 8, 3, 16)))
        assert len(jax.tree.leaves(q)) == 2
        # lax.scan over the layer axis must slice data AND scale together
        def body(c, layer_q):
            assert isinstance(layer_q, QuantizedKV)
            return c, layer_q.scale.sum()

        _, sums = jax.lax.scan(body, 0, q)
        assert sums.shape == (4,)

    def test_resolve_kv_dtype(self):
        assert resolve_kv_dtype("int8") == "int8"
        assert resolve_kv_dtype(jnp.int8) == "int8"
        assert resolve_kv_dtype("bf16") == jnp.bfloat16
        assert resolve_kv_dtype("bfloat16") == jnp.bfloat16
        assert resolve_kv_dtype("f32") == jnp.float32
        assert resolve_kv_dtype(jnp.float32) == jnp.float32

    def test_kv_empty_and_dtype_name(self):
        shape = (2, 3, 16, 4, 32)
        plain = kv_empty(shape, jnp.bfloat16)
        assert not is_quantized(plain) and plain.shape == shape
        q = kv_empty(shape, "int8")
        assert is_quantized(q)
        assert q.shape == shape and q.scale.shape == shape[:-1]
        assert bool(jnp.all(dequantize_kv(q, jnp.float32) == 0.0))
        assert kv_dtype_name(q) == "int8"
        assert kv_dtype_name(plain) == "bfloat16"

    def test_gather_scatter_semantics(self):
        pages = quantize_kv(
            jax.random.normal(jax.random.PRNGKey(2), (2, 6, 4, 2, 8))
        )
        tables = jnp.array([[1, 3], [5, 0]], jnp.int32)
        g = kv_gather(pages, tables, layer=1, dtype=jnp.float32)
        want = dequantize_kv(pages, jnp.float32)[1][tables]
        assert np.allclose(np.asarray(g), np.asarray(want))
        # plain arrays: bit-identical pass-through to direct indexing
        plain = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 4, 2, 8))
        assert bool(jnp.all(kv_gather(plain, tables, layer=0) == plain[0][tables]))

        upd = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 2, 8))
        page_idx = jnp.array([1, 4, 2], jnp.int32)
        slot = jnp.array([0, 3, 1], jnp.int32)
        out = kv_scatter(pages, upd, page_idx, slot)
        qu = quantize_kv(upd)
        assert bool(jnp.all(out.data[:, page_idx, slot] == qu.data))
        assert bool(jnp.all(out.scale[:, page_idx, slot] == qu.scale))
        out_p = kv_scatter(plain, upd, page_idx, slot)
        assert bool(
            jnp.all(out_p == plain.at[:, page_idx, slot].set(upd))
        )


# -- kernels vs references ---------------------------------------------------


def _ragged_setup(Hq=16, Hkv=16, dtype=jnp.float32):
    L, B, D, ps, pp = 2, 2, 128, 16, 4
    n_pages = B * pp + 1
    kp = jax.random.normal(
        jax.random.PRNGKey(0), (L, n_pages, ps, Hkv, D), dtype
    )
    vp = jax.random.normal(jax.random.PRNGKey(1), kp.shape, dtype)
    pt = (1 + jnp.arange(B * pp, dtype=jnp.int32)).reshape(B, pp)
    prefix = jnp.array([19, 44], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, Hq, D), dtype)
    k_new = jax.random.normal(jax.random.PRNGKey(3), (B, Hkv, D), dtype)
    v_new = jax.random.normal(jax.random.PRNGKey(4), (B, Hkv, D), dtype)
    return kp, vp, pt, prefix, q, k_new, v_new


class TestInt8RaggedKernels:
    @pytest.mark.parametrize("variant,Hkv", [("flat", 16), ("grouped", 8)])
    def test_int8_matches_f32_reference_within_tolerance(self, variant, Hkv):
        """Interpreter-mode int8 ragged decode vs the f32-cache XLA
        reference: within the declared drift tolerance (attention outputs
        are O(1) at these shapes; observed ~0.01)."""
        Hq = 16 if variant == "flat" else 32
        kp, vp, pt, prefix, q, k_new, v_new = _ragged_setup(Hq, Hkv)
        qkp, qvp = quantize_kv(kp), quantize_kv(vp)
        o = ops.paged_decode_attention_ragged(
            q, qkp, qvp, jnp.int32(1), pt, prefix, k_new, v_new,
            variant=variant,
        )
        ref = ops.paged_decode_attention_inflight(
            q, kp[1][pt], vp[1][pt], prefix, k_new, v_new
        )
        assert float(jnp.max(jnp.abs(o - ref))) < 0.05

    @pytest.mark.parametrize("variant,Hkv", [("flat", 16), ("grouped", 8)])
    def test_int8_kernel_matches_xla_fallback_tight(self, variant, Hkv):
        """Kernel vs the XLA gather fallback over the SAME quantized cache:
        both read identical dequantized values, so only accumulation order
        differs — the bound is the bf16-probe class, not the quant drift."""
        Hq = 16 if variant == "flat" else 32
        kp, vp, pt, prefix, q, k_new, v_new = _ragged_setup(Hq, Hkv)
        qkp, qvp = quantize_kv(kp), quantize_kv(vp)
        o = ops.paged_decode_attention_ragged(
            q, qkp, qvp, jnp.int32(1), pt, prefix, k_new, v_new,
            variant=variant,
        )
        dk = kv_gather(qkp, pt, layer=1, dtype=q.dtype)
        dv = kv_gather(qvp, pt, layer=1, dtype=q.dtype)
        ref = ops.paged_decode_attention_inflight(
            q, dk, dv, prefix, k_new, v_new
        )
        assert float(jnp.max(jnp.abs(o - ref))) < 5e-3

    def test_plain_cache_path_unchanged(self):
        """bf16/f32 caches keep the exact pre-int8 kernel path (no dequant
        multiply, no scale operands): the default stays bit-identical."""
        kp, vp, pt, prefix, q, k_new, v_new = _ragged_setup()
        o = ops.paged_decode_attention_ragged(
            q, kp, vp, jnp.int32(1), pt, prefix, k_new, v_new,
            variant="flat",
        )
        ref = ops.paged_decode_attention_inflight(
            q, kp[1][pt], vp[1][pt], prefix, k_new, v_new
        )
        assert float(jnp.max(jnp.abs(o - ref))) < 1e-5

    def test_reference_paged_ops_accept_quantized(self):
        kp, vp, pt, prefix, q, k_new, v_new = _ragged_setup()
        qkp, qvp = quantize_kv(kp), quantize_kv(vp)
        lens = prefix + 1
        o = reference.paged_decode_attention(q, qkp[1], qvp[1], pt, lens)
        ref = reference.paged_decode_attention(q, kp[1], vp[1], pt, lens)
        assert float(jnp.max(jnp.abs(o - ref))) < 0.05

    def test_variant_auto_selection_respects_kv_dtype(self):
        from modal_examples_tpu.ops.paged_attention import ragged_variant_for

        # since PR 35 the flat form reads a page as (ps * Hkv, D) rows through
        # a reshape of the cache in HBM, a bitcast at Hkv % 8 for bf16 and
        # int8 pages alike (the flatten in VMEM wanted 16 / 32)
        assert [ragged_variant_for(n) for n in (32, 16, 8)] == ["flat"] * 3
        assert [ragged_variant_for(n) for n in (4, 2, 1)] == ["grouped"] * 3


class TestInt8Scatter:
    def test_scatter_kv_pages_quantized_exact(self):
        L, P, ps, Hkv, D, B = 2, 6, 16, 4, 32, 3
        kp = quantize_kv(
            jax.random.normal(jax.random.PRNGKey(0), (L, P, ps, Hkv, D))
        )
        vp = quantize_kv(
            jax.random.normal(jax.random.PRNGKey(1), (L, P, ps, Hkv, D))
        )
        k_all = jax.random.normal(jax.random.PRNGKey(2), (L, B, Hkv, D))
        v_all = jax.random.normal(jax.random.PRNGKey(3), k_all.shape)
        page_idx = jnp.array([1, 3, 5], jnp.int32)
        slot = jnp.array([0, 7, 15], jnp.int32)
        ok, ov = ops.scatter_kv_pages(kp, vp, k_all, v_all, page_idx, slot)
        qk, qv = quantize_kv(k_all), quantize_kv(v_all)
        assert bool(jnp.all(ok.data[:, page_idx, slot] == qk.data))
        assert bool(jnp.all(ok.scale[:, page_idx, slot] == qk.scale))
        assert bool(jnp.all(ov.data[:, page_idx, slot] == qv.data))
        # non-target pages untouched, data and scale both
        assert bool(jnp.all(ok.data[:, 0] == kp.data[:, 0]))
        assert bool(jnp.all(ok.scale[:, 0] == kp.scale[:, 0]))


# -- model-level: prefill / decode / verify ----------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mk_cache(cfg, kv_dtype, slots=2, pp=4, ps=16):
    return PagedKVCache.create(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_pages=1 + slots * pp, page_size=ps,
        kv_dtype=kv_dtype, prefer_native=False,
    )


class TestModelPaths:
    def _prefilled(self, cfg, params, kv_dtype):
        slots, pp = 2, 4
        cache = _mk_cache(cfg, kv_dtype, slots, pp)
        tables = jnp.asarray(
            1 + np.arange(slots * pp).reshape(slots, pp), jnp.int32
        )
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (slots, 32), 0, cfg.vocab_size
        )
        seq_lens = jnp.array([20, 31], jnp.int32)
        logits, kp, vp = llama.prefill(
            params, toks, cache.k_pages, cache.v_pages, tables, seq_lens,
            cfg, attn_impl="xla",
        )
        return logits, kp, vp, tables, seq_lens

    def test_prefill_quantizes_pages_within_bound(self, tiny_model):
        cfg, params = tiny_model
        _, kp32, _, tables, _ = self._prefilled(cfg, params, jnp.float32)
        _, kp8, _, _, _ = self._prefilled(cfg, params, "int8")
        assert is_quantized(kp8)
        deq = dequantize_kv(kp8, jnp.float32)
        bound = kp8.scale[..., None] * 0.51 + 1e-6
        assert bool(jnp.all(jnp.abs(deq - kp32) <= bound))

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_decode_step_int8_logit_drift(self, tiny_model, impl):
        cfg, params = tiny_model
        lo32, k32, v32, tables, seq_lens = self._prefilled(
            cfg, params, jnp.float32
        )
        _, k8, v8, _, _ = self._prefilled(cfg, params, "int8")
        tok = jnp.argmax(lo32, -1).astype(jnp.int32)
        active = jnp.ones((2,), bool)
        l32, _, _ = llama.decode_step(
            params, tok, seq_lens, k32, v32, tables, active, cfg, impl=impl
        )
        l8, k8n, v8n = llama.decode_step(
            params, tok, seq_lens, k8, v8, tables, active, cfg, impl=impl
        )
        assert is_quantized(k8n) and is_quantized(v8n)  # stays quantized
        assert float(jnp.max(jnp.abs(l8 - l32))) < LOGIT_TOL

    def test_verify_step_int8_logit_drift(self, tiny_model):
        cfg, params = tiny_model
        _, k32, v32, tables, seq_lens = self._prefilled(
            cfg, params, jnp.float32
        )
        _, k8, v8, _, _ = self._prefilled(cfg, params, "int8")
        chain = jax.random.randint(
            jax.random.PRNGKey(2), (2, 3), 0, cfg.vocab_size
        )
        active = jnp.ones((2,), bool)
        l32, _, _ = llama.verify_step(
            params, chain, seq_lens, k32, v32, tables, active, cfg
        )
        l8, k8n, _ = llama.verify_step(
            params, chain, seq_lens, k8, v8, tables, active, cfg
        )
        assert is_quantized(k8n)
        assert float(jnp.max(jnp.abs(l8 - l32))) < LOGIT_TOL

    def test_prefill_chunk_int8(self, tiny_model):
        """Chunked prefill's prefix gather dequantizes: a second chunk over
        an int8 cache lands near the f32-cache logits."""
        cfg, params = tiny_model
        slots, pp, ps, C = 1, 4, 16, 32
        tables = jnp.asarray(
            1 + np.arange(slots * pp).reshape(slots, pp), jnp.int32
        )
        toks = jax.random.randint(
            jax.random.PRNGKey(3), (1, 2 * C), 0, cfg.vocab_size
        )
        outs = {}
        for name, kvd in (("f32", jnp.float32), ("int8", "int8")):
            cache = _mk_cache(cfg, kvd, slots, pp)
            kp, vp = cache.k_pages, cache.v_pages
            lo, kp, vp = llama.prefill_chunk(
                params, toks[:, :C], kp, vp, tables,
                jnp.array([C], jnp.int32), cfg, q_offset=0, attn_impl="xla",
            )
            lo, kp, vp = llama.prefill_chunk(
                params, toks[:, C:], kp, vp, tables,
                jnp.array([C], jnp.int32), cfg, q_offset=C, attn_impl="xla",
            )
            outs[name] = lo
        drift = float(jnp.max(jnp.abs(outs["int8"] - outs["f32"])))
        assert drift < LOGIT_TOL


# -- PagedKVCache container ---------------------------------------------------


class TestPagedKVCacheInt8:
    def test_four_leaf_pytree_and_halved_bytes(self):
        cfg = llama.LlamaConfig.tiny()
        bf16 = _mk_cache(cfg, jnp.bfloat16)
        q8 = _mk_cache(cfg, "int8")
        assert len(jax.tree.leaves(bf16)) == 2
        assert len(jax.tree.leaves(q8)) == 4
        assert bf16.kv_dtype == "bfloat16" and not bf16.quantized
        assert q8.kv_dtype == "int8" and q8.quantized
        # int8 = half the payload + ~3%-scale overhead (D=64 here -> ~6%)
        assert q8.bytes() < 0.6 * bf16.bytes()
        occ = q8.occupancy()
        assert occ["bytes_total"] == q8.bytes()

    def test_create_kv_dtype_and_legacy_dtype_aliases(self):
        cfg = llama.LlamaConfig.tiny()
        a = _mk_cache(cfg, "int8")
        assert is_quantized(a.k_pages)
        b = PagedKVCache.create(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, n_pages=9, page_size=16,
            dtype=jnp.float32, prefer_native=False,  # legacy spelling
        )
        assert b.k_pages.dtype == jnp.float32
        with pytest.raises(ValueError):
            PagedKVCache.create(
                n_layers=1, n_kv_heads=1, head_dim=8, n_pages=2,
                kv_dtype="int8", dtype=jnp.float32, prefer_native=False,
            )


# -- engine e2e ---------------------------------------------------------------


class TestEngineInt8KV:
    def _mk(self, **kw):
        from modal_examples_tpu.serving import LLMEngine

        cfg = llama.LlamaConfig.tiny()
        return LLMEngine(
            cfg, max_slots=2, page_size=16, max_model_len=128,
            prefill_buckets=(32,), seed=0, **kw,
        )

    def test_generates_and_reports_int8(self):
        from modal_examples_tpu.serving.sampling import SamplingParams

        eng = self._mk(kv_dtype="int8")
        try:
            assert eng.kv_dtype == "int8"
            assert eng.impl_plan["kv_dtype"] == "int8"
            assert len(jax.tree.leaves(eng.cache)) == 4
            out = eng.generate(
                "hello world", SamplingParams(max_tokens=6, temperature=0.0)
            )
            assert isinstance(out, str)
            assert eng.error_count == 0
            # dtype-aware footprint gauge reflects the halved cache
            from modal_examples_tpu.utils.prometheus import default_registry

            eng._metrics_wall = 0.0
            eng._refresh_gauges()
            val = default_registry.value(
                "mtpu_kv_cache_bytes", labels={"dtype": "int8"}
            )
            assert val == eng.cache.bytes()
        finally:
            eng.stop()

    def test_default_stays_two_leaf_bf16(self):
        eng = self._mk()
        try:
            assert eng.kv_dtype == "bfloat16"
            assert len(jax.tree.leaves(eng.cache)) == 2
            assert not is_quantized(eng.cache.k_pages)
        finally:
            eng.stop()

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv("MTPU_KV_DTYPE", "int8")
        eng = self._mk()
        try:
            assert eng.kv_dtype == "int8"
        finally:
            eng.stop()
        # explicit arg beats the env
        eng2 = self._mk(kv_dtype=jnp.float32)
        try:
            assert eng2.kv_dtype == "float32"
        finally:
            eng2.stop()

    def test_int8_vs_f32_same_greedy_start(self):
        """Greedy decode over int8 KV tracks the f32-cache engine for a
        short horizon on the tiny model — a sanity check that the drift is
        quantization noise, not a broken read/write path. (Tolerance-based
        contract: long generations MAY diverge; first tokens of this fixed
        tiny model have comfortable argmax margins.)"""
        from modal_examples_tpu.serving.sampling import SamplingParams

        outs = {}
        for name, kvd in (("f32", jnp.float32), ("int8", "int8")):
            eng = self._mk(kv_dtype=kvd)
            try:
                outs[name] = eng.generate(
                    "the quick brown fox",
                    SamplingParams(max_tokens=4, temperature=0.0),
                )
                assert eng.error_count == 0
            finally:
                eng.stop()
        assert outs["int8"] == outs["f32"]


# -- incremental n-gram index (satellite) ------------------------------------


class TestNgramIndex:
    @staticmethod
    def _brute(hist, n, gamma, lookback):
        """The pre-index per-tick rescan (the replaced implementation),
        kept here as the semantics oracle."""
        h = hist[-lookback:]
        if len(h) <= n:
            return []
        tail = h[-n:]
        for j in range(len(h) - n - 1, -1, -1):
            if h[j : j + n] == tail:
                return h[j + n : j + n + gamma]
        return []

    @pytest.mark.parametrize("lookback", [8, 32, 1024])
    @pytest.mark.parametrize("n", [1, 2, 3])
    def test_matches_bruteforce_rescan(self, n, lookback):
        from modal_examples_tpu.serving.engine import _NgramIndex

        rng = np.random.RandomState(n * 1000 + lookback)
        for trial in range(20):
            seq = rng.randint(0, 4, size=rng.randint(1, 60)).tolist()
            cut = rng.randint(0, len(seq) + 1)
            idx = _NgramIndex(n, seq[:cut], lookback)
            for tok in seq[cut:]:
                idx.push(tok)
            for gamma in (1, 3, 5):
                assert idx.propose(gamma) == self._brute(
                    seq, n, gamma, lookback
                ), (seq, n, gamma, lookback)

    def test_incremental_equals_bulk(self):
        from modal_examples_tpu.serving.engine import _NgramIndex

        seq = [1, 2, 3, 1, 2, 3, 1, 2]
        bulk = _NgramIndex(2, seq, 1024)
        inc = _NgramIndex(2, seq[:3], 1024)
        for t in seq[3:]:
            inc.push(t)
        assert bulk.propose(4) == inc.propose(4) == [3, 1, 2]


@pytest.fixture(scope="module")
def tail_engine():
    from modal_examples_tpu.serving import LLMEngine

    eng = LLMEngine(
        llama.LlamaConfig.tiny(), max_slots=2, page_size=16, seed=0, kv_dtype="int8",
        max_model_len=chunk_tail.MAX_MODEL_LEN, prefill_buckets=chunk_tail.BUCKETS,
    )
    yield chunk_tail.warmed(eng)
    eng.stop()


@pytest.mark.parametrize("case", list(chunk_tail.CASES))
def test_the_tail_chunk_over_int8_pages_is_as_wide_as_what_is_left(tail_engine, case, monkeypatch):
    """The prefix the last chunk gathers is dequantised whatever its width
    (tests/chunk_tail.py)."""
    chunk_tail.check(tail_engine, case, monkeypatch)
