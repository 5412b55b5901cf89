"""Sharded Pallas fast paths under tensor parallelism (ROADMAP open item
#2, round 7): the ragged decode kernels, the KV scatter, and flash prefill
run inside shard_map over the kv-head mesh axis — on a CPU mesh
(xla_force_host_platform_device_count, interpreter-mode kernels), so the
multi-chip serving path is exercised by the fast tier without TPUs.

Contracts proven here:
- op level: each sharded wrapper is BIT-exact vs the single-device kernel
  (attention is head-local, scatter is head-local, int8 scales are per
  token-head — sharding the head axis changes no math);
- plan level: ``paged_impl_plan(mesh=...)`` resolves legality against the
  PER-SHARD head counts and reports the variant each device actually runs;
- engine level: ``LLMEngine(mesh=..., paged_impl="pallas",
  scatter_impl="pallas")`` constructs and serves (the old mesh×pallas
  ValueError is gone), token-identical to the sharded XLA path for plain
  caches and within the documented tolerance for int8 — and composes with
  speculative decoding.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def mesh2(jax):
    from modal_examples_tpu.parallel import make_mesh

    return make_mesh({"tensor": 2}, devices=jax.devices()[:2])


def _mk_cache(jax, L, n_pages, ps, Hkv, D, kv_dtype, seed=0):
    import jax.numpy as jnp

    from modal_examples_tpu.ops import quantize_kv

    k = jax.random.normal(
        jax.random.PRNGKey(seed), (L, n_pages, ps, Hkv, D), jnp.float32
    )
    v = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (L, n_pages, ps, Hkv, D), jnp.float32
    )
    if kv_dtype == "int8":
        return quantize_kv(k), quantize_kv(v)
    return k.astype(kv_dtype), v.astype(kv_dtype)


class TestShardedKernelOps:
    """Direct wrapper-vs-kernel exactness on the 2-device CPU mesh."""

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    @pytest.mark.parametrize("variant", ["flat", "grouped"])
    def test_sharded_ragged_matches_single_device(
        self, jax, mesh2, kv_dtype, variant
    ):
        import jax.numpy as jnp

        from modal_examples_tpu.ops import (
            paged_decode_attention_ragged,
            sharded_ragged_decode,
        )

        L, Pn, ps, Hkv, D, B, Hq = 2, 9, 16, 2, 8, 2, 4
        kp, vp = _mk_cache(jax, L, Pn, ps, Hkv, D, kv_dtype)
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Hq, D), jnp.float32)
        k_new = jax.random.normal(
            jax.random.PRNGKey(3), (B, Hkv, D), jnp.float32
        )
        v_new = jax.random.normal(
            jax.random.PRNGKey(4), (B, Hkv, D), jnp.float32
        )
        tables = jnp.asarray(
            1 + np.arange(B * 4).reshape(B, 4), jnp.int32
        )
        prefix = jnp.asarray([17, 33], jnp.int32)
        layer = jnp.int32(1)

        ref = paged_decode_attention_ragged(
            q, kp, vp, layer, tables, prefix, k_new, v_new, variant=variant
        )
        out = jax.jit(
            lambda *a: sharded_ragged_decode(mesh2, *a, variant=variant)
        )(q, kp, vp, layer, tables, prefix, k_new, v_new)
        # the dispatcher adds nothing to the kernel: BIT-exact against the
        # same kernel run on each shard's own heads — int8 too (the scales
        # are per token-head)
        tp, g = 2, Hq // Hkv
        per_shard = []
        for s in range(tp):
            kv = slice(s * Hkv // tp, (s + 1) * Hkv // tp)
            own = lambda pg: jax.tree.map(lambda a: a[:, :, :, kv], pg)
            per_shard.append(paged_decode_attention_ragged(
                q[:, kv.start * g : kv.stop * g], own(kp), own(vp), layer,
                tables, prefix, k_new[:, kv], v_new[:, kv], variant=variant,
            ))
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.concatenate(per_shard, axis=1))
        )
        # against all heads in one call: ulp-exact. flat's block-diagonal
        # matmul contracts over W = ps*Hkv columns, so halving Hkv changes
        # its f32 summation tree; grouped's per-kv-head contractions do not
        # change, but in interpret mode its softmax runs on XLA's CPU
        # backend, whose f32 row sums over an (Hq, W) array depend on Hq in
        # the last bit (one head alone differs from itself among two, on
        # one device, by 1.5e-8)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-6, rtol=0
        )

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_sharded_scatter_matches_xla(self, jax, mesh2, kv_dtype):
        import jax.numpy as jnp

        from modal_examples_tpu.ops import (
            is_quantized,
            kv_scatter,
            sharded_scatter_kv_pages,
        )

        L, Pn, ps, Hkv, D, B = 2, 7, 16, 2, 8, 3
        kp, vp = _mk_cache(jax, L, Pn, ps, Hkv, D, kv_dtype, seed=5)
        k_all = jax.random.normal(
            jax.random.PRNGKey(7), (L, B, Hkv, D), jnp.float32
        )
        v_all = jax.random.normal(
            jax.random.PRNGKey(8), (L, B, Hkv, D), jnp.float32
        )
        page_idx = jnp.asarray([1, 3, 5], jnp.int32)
        slot = jnp.asarray([0, 7, 15], jnp.int32)

        ref_k = kv_scatter(kp, k_all, page_idx, slot)
        ref_v = kv_scatter(vp, v_all, page_idx, slot)
        ok, ov = jax.jit(
            lambda *a: sharded_scatter_kv_pages(mesh2, *a)
        )(kp, vp, k_all, v_all, page_idx, slot)
        for got, want in ((ok, ref_k), (ov, ref_v)):
            if is_quantized(want):
                np.testing.assert_array_equal(
                    np.asarray(got.data), np.asarray(want.data)
                )
                np.testing.assert_array_equal(
                    np.asarray(got.scale), np.asarray(want.scale)
                )
            else:
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want)
                )

    def test_sharded_flash_matches_single_device(self, jax, mesh2):
        import jax.numpy as jnp

        from modal_examples_tpu.ops import (
            flash_attention,
            flash_attention_chunked,
            sharded_flash_attention,
            sharded_flash_attention_chunked,
        )

        B, Hq, Hkv, S, D = 2, 4, 2, 32, 8
        q = jax.random.normal(
            jax.random.PRNGKey(0), (B, Hq, S, D), jnp.float32
        )
        k = jax.random.normal(
            jax.random.PRNGKey(1), (B, Hkv, S, D), jnp.float32
        )
        v = jax.random.normal(
            jax.random.PRNGKey(2), (B, Hkv, S, D), jnp.float32
        )
        ref = flash_attention(q, k, v, True)
        out = jax.jit(lambda q, k, v: sharded_flash_attention(mesh2, q, k, v))(
            q, k, v
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

        # chunked (rectangular) prefill: q chunk at q_offset vs full prefix
        qc = q[:, :, :16, :]
        ref_c = flash_attention_chunked(qc, k, v, q_offset=16)
        out_c = jax.jit(
            lambda q, k, v: sharded_flash_attention_chunked(
                mesh2, q, k, v, q_offset=16
            )
        )(qc, k, v)
        np.testing.assert_array_equal(np.asarray(out_c), np.asarray(ref_c))

    def test_no_mesh_falls_through(self, jax):
        """mesh=None (or a 1-wide tensor axis) must be the plain kernel —
        the single-chip path stays byte-for-byte what it was."""
        import jax.numpy as jnp

        from modal_examples_tpu.ops import (
            paged_decode_attention_ragged,
            sharded_ragged_decode,
        )

        L, Pn, ps, Hkv, D, B, Hq = 1, 5, 16, 2, 8, 1, 4
        kp, vp = _mk_cache(jax, L, Pn, ps, Hkv, D, "float32")
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Hq, D), jnp.float32)
        k_new = jax.random.normal(
            jax.random.PRNGKey(3), (B, Hkv, D), jnp.float32
        )
        v_new = jax.random.normal(
            jax.random.PRNGKey(4), (B, Hkv, D), jnp.float32
        )
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        prefix = jnp.asarray([21], jnp.int32)
        out = sharded_ragged_decode(
            None, q, kp, vp, jnp.int32(0), tables, prefix, k_new, v_new
        )
        ref = paged_decode_attention_ragged(
            q, kp, vp, jnp.int32(0), tables, prefix, k_new, v_new
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_indivisible_heads_raise(self, jax):
        """Hkv % tp != 0 is the one genuinely illegal sharding — loud
        ValueError, not a wrong-answer shard_map."""
        import jax.numpy as jnp

        from modal_examples_tpu.ops import sharded_ragged_decode
        from modal_examples_tpu.parallel import make_mesh

        mesh4 = make_mesh({"tensor": 4}, devices=jax.devices()[:4])
        kp, vp = _mk_cache(jax, 1, 5, 16, 2, 8, "float32")
        q = jnp.zeros((1, 4, 8), jnp.float32)
        kv = jnp.zeros((1, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="divisible"):
            sharded_ragged_decode(
                mesh4, q, kp, vp, jnp.int32(0),
                jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
                kv, kv,
            )


class TestPerShardLegality:
    """``paged_impl_plan(mesh=...)`` resolves the variant against the
    SHARD-local head counts — the legality table the kernels implicitly
    apply inside shard_map, mirrored in the reporting layer."""

    @pytest.mark.parametrize(
        "n_kv_heads,n_heads,tp,kv_dtype,want_attn,want_variant",
        [
            # flat needs Hkv%8 per SHARD (a page read as (ps * Hkv, D) rows
            # in place, bf16 and int8 alike: PR 35): 32 and 16 heads stay
            # flat at tp=2 (16 and 8 per shard)
            (32, 32, 1, "bfloat16", "ragged", "flat"),
            (32, 32, 2, "bfloat16", "ragged", "flat"),
            (16, 32, 2, "bfloat16", "ragged", "flat"),
            (32, 32, 1, "int8", "ragged", "flat"),
            (32, 32, 2, "int8", "ragged", "flat"),
            # GQA (llama-3 geometry) is flat on one chip and grouped the
            # moment the shard halves its 8 heads
            (8, 32, 1, "bfloat16", "ragged", "flat"),
            (8, 32, 2, "bfloat16", "ragged", "grouped"),
            (2, 4, 2, "float32", "ragged", "grouped"),
            # heads not divisible by tp: loud downgrade to the XLA gather
            (2, 4, 4, "bfloat16", "xla-gather", None),
        ],
    )
    def test_plan_table(
        self, jax, n_kv_heads, n_heads, tp, kv_dtype, want_attn, want_variant
    ):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=n_heads * 128, n_layers=1,
            n_heads=n_heads, n_kv_heads=n_kv_heads, ffn_dim=128,
        )
        mesh = (
            make_mesh({"tensor": tp}, devices=jax.devices()[:tp])
            if tp > 1
            else None
        )
        plan = llama.paged_impl_plan(
            cfg, 16, "pallas", "pallas", kv_dtype=kv_dtype, mesh=mesh,
            warn=False,
        )
        assert plan["tp"] == tp
        assert plan["attention"] == want_attn
        assert plan["ragged_variant"] == want_variant
        if want_attn == "xla-gather":
            assert plan["scatter"] == "xla"
            assert any("tp=" in m for m in plan["downgraded"])
        else:
            assert plan["scatter"] == "pallas"
            assert plan["downgraded"] == []


class TestEngineShardedPallas:
    """The acceptance contract: mesh= + pallas impls construct and serve,
    token-identical to the sharded XLA path (plain caches) / within the
    documented tolerance (int8)."""

    def _cfg_params(self, jax):
        from modal_examples_tpu.models import llama

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)

    def test_tp2_pallas_matches_tp2_xla_bitexact(self, jax, mesh2):
        import jax.numpy as jnp

        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg, params = self._cfg_params(jax)
        kw = dict(
            max_slots=2, max_model_len=64, page_size=16,
            prefill_buckets=(32,), seed=0, kv_dtype=jnp.bfloat16,
        )
        sp = SamplingParams(max_tokens=16, temperature=0.0)
        prompts = ["sharded pallas decode", "fast path under tp"]
        xla_tp = LLMEngine(cfg, params, mesh=mesh2, **kw)
        pal_tp = LLMEngine(cfg, params, mesh=mesh2, paged_impl="pallas", **kw)
        # the acceptance-criterion spelling: both impls as engine kwargs
        pal_sc = LLMEngine(
            cfg, params, mesh=mesh2, paged_impl="pallas",
            scatter_impl="pallas", **kw,
        )
        try:
            want = [xla_tp.generate(p, sp) for p in prompts]
            got = [pal_tp.generate(p, sp) for p in prompts]
            got_sc = [pal_sc.generate(p, sp) for p in prompts]
            assert want == got == got_sc
            assert pal_tp.error_count == 0 and pal_sc.error_count == 0
            assert pal_tp.impl_plan["attention"] == "ragged"
            assert pal_tp.impl_plan["tp"] == 2
            assert pal_sc.impl_plan["scatter"] == "pallas"
            assert len(pal_tp.cache.k_pages.sharding.device_set) == 2
        finally:
            xla_tp.stop()
            pal_tp.stop()
            pal_sc.stop()

    def test_tp2_pallas_int8_tolerance(self, jax, mesh2):
        """int8 × TP × pallas: all four cache leaves shard, the plan
        reports the per-shard variant, and decode logits stay within the
        documented int8 tolerance of the sharded-XLA int8 path (the in-VMEM
        dequant and the gather dequant compute the same math)."""
        import functools

        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.ops.kv_quant import shard_kv
        from modal_examples_tpu.serving import LLMEngine, SamplingParams
        from modal_examples_tpu.serving.engine import _shard_params
        from modal_examples_tpu.serving.kv_cache import PagedKVCache
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        cfg, params = self._cfg_params(jax)
        eng = LLMEngine(
            cfg, params, mesh=mesh2, paged_impl="pallas", max_slots=2,
            max_model_len=64, page_size=16, prefill_buckets=(32,), seed=0,
            kv_dtype="int8",
        )
        try:
            out = eng.generate(
                "quantized sharded kernels",
                SamplingParams(max_tokens=12, temperature=0.0),
            )
            assert isinstance(out, str) and eng.error_count == 0
            assert eng.impl_plan["kv_dtype"] == "int8"
            # Hkv//tp = 1: under the 8 heads flat reads in place -> grouped per shard
            assert eng.impl_plan["ragged_variant"] == "grouped"
            kp = eng.cache.k_pages
            assert len(kp.data.sharding.device_set) == 2
            assert len(kp.scale.sharding.device_set) == 2
        finally:
            eng.stop()

        # direct decode_step: sharded pallas vs sharded xla, same int8 cache
        sharded_params = _shard_params(params, cfg, mesh2)
        toks = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, 128)
        tables = jnp.asarray(
            1 + np.arange(2 * 4).reshape(2, 4), jnp.int32
        )
        seq_lens = jnp.asarray([12, 16], jnp.int32)
        active = jnp.ones((2,), bool)

        def run(impl):
            cache = PagedKVCache.create(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, n_pages=9, page_size=16,
                kv_dtype="int8", prefer_native=False,
            )
            dsh = NamedSharding(mesh2, P(None, None, None, "tensor", None))
            ssh = NamedSharding(mesh2, P(None, None, None, "tensor"))
            kp = shard_kv(cache.k_pages, dsh, ssh)
            vp = shard_kv(cache.v_pages, dsh, ssh)
            lo, kp, vp = jax.jit(
                functools.partial(
                    llama.prefill, cfg=cfg, attn_impl="flash", mesh=mesh2
                )
            )(sharded_params, toks, kp, vp, tables, seq_lens)
            nxt = jnp.argmax(lo, -1).astype(jnp.int32)
            l2, _, _ = jax.jit(
                functools.partial(
                    llama.decode_step, cfg=cfg, impl=impl, mesh=mesh2
                )
            )(sharded_params, nxt, seq_lens, kp, vp, tables, active)
            return np.asarray(l2)

        l_pallas, l_xla = run("pallas"), run("xla")
        assert float(np.max(np.abs(l_pallas - l_xla))) < 1e-4

    def test_spec_tp_int8_pallas_compose(self, jax, mesh2):
        """The full stack composes: speculative decoding × tensor
        parallelism × int8 KV × the sharded pallas kernels — draft chain,
        target verify, and both caches' scatters all run under the same
        sharded jit without error (token exactness deliberately NOT
        asserted: int8 + psum reordering, docs/kv_cache.md)."""
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg, params = self._cfg_params(jax)
        eng = LLMEngine(
            cfg, params, mesh=mesh2, paged_impl="pallas",
            speculative=(cfg, 2), draft_params=params, max_slots=2,
            max_model_len=64, page_size=16, prefill_buckets=(32,), seed=0,
            kv_dtype="int8",
        )
        try:
            out = eng.generate(
                "compose spec tp int8 pallas",
                SamplingParams(max_tokens=12, temperature=0.0),
            )
            assert isinstance(out, str) and out
            assert eng.error_count == 0, eng.error_log
            # identical draft == target: proposals must mostly be accepted
            assert eng.stats.acceptance_rate() > 0.5
        finally:
            eng.stop()
