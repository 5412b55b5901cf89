"""Tensor-parallel serving tests: the paged engine under ``mesh=`` serves,
shards its params and cache, and stays within the documented logit drift of
the single-device run."""

import pytest

pytestmark = pytest.mark.slow  # heavyweight: excluded from the fast tier

import numpy as np


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


class TestEngineTP:
    """Tensor parallelism as ONE engine flag (vllm_inference.py:180): the
    paged continuous-batching engine runs under a sharded jit — same
    scheduler, same OpenAI surface.

    Accuracy contract (docs/tensor_parallel.md, round 7): TP output is NOT
    token-exact vs single-device — row-parallel projections psum partial
    f32 sums in a different reduction order, and the ~1e-6 logit drift can
    flip a greedy argmax on these tiny random models (with the flash
    prefill kernel now running per head shard under shard_map, the drift
    surface is fixed by construction, not by partitioner luck). Single-vs-
    TP is therefore held to LOGIT tolerance; same-mesh pallas-vs-XLA
    token-exactness lives in tests/test_sharded_pallas.py."""

    def test_paged_engine_tp2_serves_and_shards(self, jax):
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])

        kw = dict(
            max_slots=2, max_model_len=64, page_size=16,
            prefill_buckets=(32,), seed=0, kv_dtype=jnp.float32,
        )
        tp = LLMEngine(cfg, params, mesh=mesh, **kw)
        try:
            prompts = ["sharded decode test", "one flag not a fork"]
            sp = SamplingParams(max_tokens=16, temperature=0.0)
            got = [tp.generate(p, sp) for p in prompts]
            assert all(got), got
            # deterministic: the same sharded program replays exactly
            assert got[0] == tp.generate(prompts[0], sp)
            assert tp.error_count == 0, tp.error_log
            # params and cache actually sharded over the tensor axis
            wq = tp.params["layers"]["wq"]
            assert len(wq.sharding.device_set) == 2
            assert len(tp.cache.k_pages.sharding.device_set) == 2
        finally:
            tp.stop()

    def test_paged_tp2_logit_drift_vs_single(self, jax):
        """The tolerance half of the TP contract for the plain f32 cache:
        prefill (sharded flash) + decode logits stay within the documented
        psum-reordering drift of the single-device run."""
        import functools

        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving.engine import _shard_params
        from modal_examples_tpu.serving.kv_cache import PagedKVCache

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 128)
        tables = jnp.asarray(
            1 + np.arange(2 * 4).reshape(2, 4), jnp.int32
        )
        seq_lens = jnp.array([12, 16], jnp.int32)
        active = jnp.ones((2,), bool)

        def run(p, mesh_arg):
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            cache = PagedKVCache.create(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, n_pages=9, page_size=16,
                kv_dtype=jnp.float32, prefer_native=False,
            )
            kp, vp = cache.k_pages, cache.v_pages
            if mesh_arg is not None:
                sh = NamedSharding(
                    mesh_arg, P(None, None, None, "tensor", None)
                )
                kp = jax.device_put(kp, sh)
                vp = jax.device_put(vp, sh)
            lo, kp, vp = jax.jit(
                functools.partial(
                    llama.prefill, cfg=cfg, attn_impl="flash", mesh=mesh_arg
                )
            )(p, toks, kp, vp, tables, seq_lens)
            nxt = jnp.argmax(lo, -1).astype(jnp.int32)
            l2, _, _ = jax.jit(
                functools.partial(
                    llama.decode_step, cfg=cfg, impl="xla", mesh=mesh_arg
                )
            )(p, nxt, seq_lens, kp, vp, tables, active)
            return np.asarray(lo), np.asarray(l2)

        lo_s, l2_s = run(params, None)
        lo_t, l2_t = run(_shard_params(params, cfg, mesh), mesh)
        assert float(np.max(np.abs(lo_t - lo_s))) < 1e-3
        assert float(np.max(np.abs(l2_t - l2_s))) < 1e-3

    @pytest.mark.parametrize("tp, kv", [(2, "float32"), (4, "int8")])
    def test_chunked_decode_loop_under_mesh_matches_single(self, jax, tp, kv):
        """The default decode step's chunk loop (a ``while`` of gathers and
        einsums whose trip count is read from the positions) stays
        auto-partitionable over the KV-head axis: contexts that end in the
        first, second and third chunk of the table, the page cache sharded
        by head, the logits and the cache writes of one device."""
        import functools

        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from modal_examples_tpu import ops
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.ops.kv_quant import shard_kv
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving.engine import _shard_params

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
            ffn_dim=128, max_seq_len=2048, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"tensor": tp}, devices=jax.devices()[:tp])
        B, ps, pp = 4, 16, 96
        span = ops.decode_chunk_pages(ps, pp) * ps
        assert pp * ps >= 3 * span
        positions = jnp.asarray([7, span + 3, 2 * span + 40, 0], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        n_pages = 1 + B * pp
        shape = (cfg.n_layers, n_pages, ps, cfg.n_kv_heads, cfg.head_dim)
        kp = 0.3 * jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        vp = 0.3 * jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
        if kv == "int8":
            kp, vp = ops.quantize_kv(kp), ops.quantize_kv(vp)
        tables = jnp.asarray(1 + np.arange(B * pp).reshape(B, pp), jnp.int32)
        toks = jnp.asarray([5, 9, 11, 0], jnp.int32)

        def run(p, mesh_arg, kp, vp):
            if mesh_arg is not None:
                kp, vp = (
                    shard_kv(
                        pages,
                        NamedSharding(mesh_arg, P(None, None, None, "tensor", None)),
                        NamedSharding(mesh_arg, P(None, None, None, "tensor")),
                    )
                    for pages in (kp, vp)
                )
            lg, k2, _ = jax.jit(
                functools.partial(
                    llama.decode_step, cfg=cfg, impl="xla", mesh=mesh_arg
                )
            )(p, toks, positions, kp, vp, tables, active)
            return np.asarray(lg), np.asarray(ops.dequantize_kv(k2, jnp.float32))

        lg_s, k_s = run(params, None, kp, vp)
        lg_t, k_t = run(_shard_params(params, cfg, mesh), mesh, kp, vp)
        assert float(np.max(np.abs(lg_t[:3] - lg_s[:3]))) < 1e-3
        assert float(np.max(np.abs(k_t - k_s))) < 1e-3

    def test_int8_kv_engine_tp2(self, jax):
        """int8 KV composes with tensor parallelism: the 4-leaf cache's
        scale arrays shard on the same kv-head axis as their int8 data
        (engine._shard_cache), so dequant never crosses chips. NOT a
        token-exact assertion like the bf16/f32 TP tests: a psum's
        ulp-level reduction reordering can flip an int8 rounding at a code
        boundary, so the contract is tolerance-based (docs/kv_cache.md) —
        checked on logits below; here the engine must boot, shard all four
        leaves, and generate cleanly."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(7), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])

        tp = LLMEngine(
            cfg, params, mesh=mesh, max_slots=2, max_model_len=64,
            page_size=16, prefill_buckets=(32,), seed=0, kv_dtype="int8",
        )
        try:
            sp = SamplingParams(max_tokens=12, temperature=0.0)
            out = tp.generate("quantized cache sharded", sp)
            assert isinstance(out, str) and tp.error_count == 0
            # int8 payload AND f32 scale rows actually sharded
            kp = tp.cache.k_pages
            assert len(kp.data.sharding.device_set) == 2
            assert len(kp.scale.sharding.device_set) == 2
        finally:
            tp.stop()

    def test_int8_kv_tp2_logit_drift_vs_single(self, jax):
        """The tolerance half of the int8-KV TP contract: prefill + decode
        logits over the sharded quantized cache stay within the declared
        drift of the single-device quantized run (differences come only
        from psum reduction order at int8 code boundaries)."""
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving.engine import _shard_params
        from modal_examples_tpu.serving.kv_cache import PagedKVCache

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(8), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
        toks = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, 128)
        tables = jnp.asarray(
            1 + np.arange(2 * 4).reshape(2, 4), jnp.int32
        )
        seq_lens = jnp.array([12, 16], jnp.int32)
        active = jnp.ones((2,), bool)

        def run(p, shard):
            cache = PagedKVCache.create(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, n_pages=9, page_size=16,
                kv_dtype="int8", prefer_native=False,
            )
            if shard:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                from modal_examples_tpu.ops import QuantizedKV

                d = NamedSharding(mesh, P(None, None, None, "tensor", None))
                s = NamedSharding(mesh, P(None, None, None, "tensor"))
                for name in ("k_pages", "v_pages"):
                    pg = getattr(cache, name)
                    setattr(cache, name, QuantizedKV(
                        data=jax.device_put(pg.data, d),
                        scale=jax.device_put(pg.scale, s),
                    ))
            lo, kp, vp = llama.prefill(
                p, toks, cache.k_pages, cache.v_pages, tables, seq_lens,
                cfg, attn_impl="xla",
            )
            nxt = jnp.argmax(lo, -1).astype(jnp.int32)
            l2, _, _ = llama.decode_step(
                p, nxt, seq_lens, kp, vp, tables, active, cfg, impl="xla"
            )
            return np.asarray(lo), np.asarray(l2)

        lo_s, l2_s = run(params, shard=False)
        lo_t, l2_t = run(_shard_params(params, cfg, mesh), shard=True)
        assert float(np.max(np.abs(lo_t - lo_s))) < 0.25
        assert float(np.max(np.abs(l2_t - l2_s))) < 0.25

    def test_quantized_engine_tp2(self, jax):
        """int8 weight-only quantization composes with tensor parallelism
        (vLLM serves quantized TP): the TP engine serves cleanly and the
        QuantizedWeight payload AND its per-channel scales actually shard.
        Token equality vs single-device is deliberately not asserted (the
        psum-reordering contract in the class docstring)."""
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.models.quantize import QuantizedWeight
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(4), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])

        tp = LLMEngine(
            cfg, params, mesh=mesh, max_slots=2, max_model_len=64,
            page_size=16, prefill_buckets=(32,), seed=0,
            kv_dtype=jnp.float32, quantization="int8",
        )
        try:
            sp = SamplingParams(max_tokens=12, temperature=0.0)
            for p in ["quantized sharded decode", "int8 over two chips"]:
                assert tp.generate(p, sp), p
            assert tp.error_count == 0, tp.error_log
            wq = tp.params["layers"]["wq"]
            assert isinstance(wq, QuantizedWeight)
            assert len(wq.q.sharding.device_set) == 2
            assert len(wq.scale.sharding.device_set) == 2
        finally:
            tp.stop()

    def test_spec_decode_under_tp(self, jax):
        """Speculative decoding composes with tensor parallelism: the spec
        program (draft chain + verify + accept/reject) runs under the same
        sharded jit. With draft == target, greedy proposals must almost
        always match the target's argmax — the acceptance rate IS the
        spec-under-TP correctness signal (token equality vs a single-device
        engine is the psum lottery; class docstring)."""
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig(
            vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype="float32",
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
        spec_tp = LLMEngine(
            cfg, params, mesh=mesh, speculative=(cfg, 2),
            draft_params=params, max_slots=2, max_model_len=64,
            page_size=16, prefill_buckets=(32,), seed=0,
            kv_dtype=jnp.float32,
        )
        try:
            sp = SamplingParams(max_tokens=12, temperature=0.0)
            got = spec_tp.generate("compose tp and spec", sp)
            assert got
            assert spec_tp.error_count == 0, spec_tp.error_log
            assert spec_tp.stats.acceptance_rate() > 0.9
        finally:
            spec_tp.stop()


class TestMoETensorParallel:
    def test_moe_engine_tp2_exact_match(self, jax):
        """MoE serving composes with TP (the reference's MoE targets run
        under --tp-size: sglang_low_latency.py's Qwen MoE,
        very_large_models.py's DeepSeek): the expert ffn dim shards over
        the tensor axis (llama.partition_specs) and the engine output must
        equal single-device token-for-token."""
        import jax.numpy as jnp

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.parallel import make_mesh
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig.tiny_moe()
        assert cfg.n_experts > 0
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
        kw = dict(
            max_slots=2, max_model_len=64, page_size=16,
            prefill_buckets=(32,), seed=0, kv_dtype=jnp.float32,
        )
        single = LLMEngine(cfg, params, **kw)
        tp = LLMEngine(cfg, params, mesh=mesh, **kw)
        try:
            sp = SamplingParams(max_tokens=12, temperature=0.0)
            for p in ["moe sharded decode", "expert routing test"]:
                assert single.generate(p, sp) == tp.generate(p, sp), p
            # expert weights really sharded over the tensor axis
            up = tp.params["layers"]["moe_up"]
            assert len(up.sharding.device_set) == 2
        finally:
            single.stop()
            tp.stop()
