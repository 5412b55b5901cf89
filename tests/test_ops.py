"""Kernel correctness tests: every Pallas kernel against its XLA reference
(interpret mode on the CPU backend; the same kernels compile via Mosaic on
TPU — exercised by bench.py and __graft_entry__.py)."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def jnp(jax):
    import jax.numpy as jnp

    return jnp


class TestFlashAttention:
    @pytest.mark.parametrize(
        "B,Hq,Hkv,S,D,causal",
        [
            (2, 4, 4, 256, 64, True),
            (1, 8, 2, 128, 64, False),  # GQA
            (2, 4, 2, 256, 128, True),
            (1, 2, 2, 384, 64, True),  # 3 blocks of 128
        ],
    )
    def test_matches_reference(self, jax, jnp, B, Hq, Hkv, S, D, causal):
        from modal_examples_tpu.ops import flash_attention, reference

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
        out = flash_attention(q, k, v, causal)
        want = reference.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_gradients_match_reference(self, jax, jnp):
        from modal_examples_tpu.ops import flash_attention, reference

        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64))
        k = jax.random.normal(ks[1], (1, 2, 128, 64))
        v = jax.random.normal(ks[2], (1, 2, 128, 64))
        g1 = jax.grad(
            lambda q, k, v: flash_attention(q, k, v, True).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: reference.attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_bwd_kernel_gqa_multiblock(self, jax, jnp, causal):
        """Pallas backward kernels (dq/dkv) vs reference grads: GQA group
        reduction + multiple q/k blocks + causal block skipping."""
        from modal_examples_tpu.ops import flash_attention, reference

        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(ks[0], (2, 4, 256, 64))
        k = jax.random.normal(ks[1], (2, 2, 256, 64))
        v = jax.random.normal(ks[2], (2, 2, 256, 64))
        gq, gk, gv = jax.grad(
            lambda q, k, v: (flash_attention(q, k, v, causal) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        rq, rk, rv = jax.grad(
            lambda q, k, v: (
                reference.attention(q, k, v, causal=causal) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip((gq, gk, gv), (rq, rk, rv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
            )

    def test_lse_is_logsumexp(self, jax, jnp):
        from modal_examples_tpu.ops import flash_attention_with_lse

        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 1, 128, 64))
        k = jax.random.normal(ks[1], (1, 1, 128, 64))
        v = jax.random.normal(ks[2], (1, 1, 128, 64))
        scale = 64**-0.5
        _, lse = flash_attention_with_lse(q, k, v, causal=False)
        s = (q[0, 0] @ k[0, 0].T) * scale
        want = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse[0, 0]), np.asarray(want), atol=1e-4)

    @pytest.mark.parametrize("q_offset", [0, 128, 256])
    def test_chunked_prefill_matches_full_rows(self, jax, jnp, q_offset):
        """A query chunk at offset o against the full K/V must equal rows
        [o, o+chunk) of dense causal attention over the whole sequence."""
        from modal_examples_tpu.ops import flash_attention_chunked, reference

        B, H, Skv, D, chunk = 1, 2, 384, 64, 128
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, H, Skv, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, Skv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, Skv, D), jnp.float32)
        full = reference.attention(q, k, v, causal=True)
        out = flash_attention_chunked(
            q[:, :, q_offset : q_offset + chunk], k, v, q_offset=q_offset
        )
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(full[:, :, q_offset : q_offset + chunk]),
            atol=2e-5,
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("tail", [128, 256])
    @pytest.mark.parametrize("Hq,Hkv,D,Dv", [(8, 2, 128, 128), (4, 4, 192, 128)])
    def test_a_short_tail_over_a_long_prefix_runs_on_padded_keys(
        self, jax, jnp, Hq, Hkv, D, Dv, tail, dtype
    ):
        """A last chunk of 128 or 256 rows over 1024 cached keys: 1152 / 1280
        keys divide into no block longer than the tail, so the call pads them
        with zero rows to 2048 and takes one long key block a query tile; the
        padded keys lie after every query and weigh nothing."""
        from modal_examples_tpu.ops import flash_attention_chunked, reference
        from modal_examples_tpu.ops.flash_attention import choose_blocks, padded_kv_len

        dt = jnp.dtype(dtype)
        q_offset, Skv = 1024, 1024 + tail
        assert choose_blocks(tail, Skv, D, Dv, dt.itemsize)[1] <= tail
        assert padded_kv_len(Skv) == 2048
        assert choose_blocks(tail, 2048, D, Dv, dt.itemsize)[1] >= 1024
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (1, Hq, tail, D), dt)
        k = jax.random.normal(ks[1], (1, Hkv, Skv, D), dt)
        v = jax.random.normal(ks[2], (1, Hkv, Skv, Dv), dt)
        out = flash_attention_chunked(q, k, v, q_offset=q_offset)
        want = np.asarray(
            reference.attention_chunked(q, k, v, q_offset=q_offset).astype(jnp.float32)
        )
        assert out.shape == want.shape and out.dtype == dt
        atol = 2e-5 if dtype == "float32" else float(
            jnp.finfo(jnp.bfloat16).eps
        ) * float(np.abs(want).max())
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want, atol=atol)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("chunk", [384, 640])
    @pytest.mark.parametrize("offset_chunks", [0, 1])
    @pytest.mark.parametrize(
        "Hq,Hkv,D,Dv", [(8, 2, 128, 128), (4, 4, 192, 128)]
    )
    def test_chunked_tiles_match_reference(
        self, jax, jnp, Hq, Hkv, D, Dv, offset_chunks, chunk, dtype
    ):
        """The chosen tiles against ``reference.attention_chunked`` at the
        cells' head geometries (GQA 4:1 at width 128; 192-wide q/k over
        128-wide values) and at lengths the larger tiles do not divide: 384
        and 640 query rows fall to tiles of 128 over key blocks of 128 or
        256, so at an offset of one chunk the first query tile walks whole
        key blocks, the block the diagonal crosses, and masked ones."""
        from modal_examples_tpu.ops import flash_attention_chunked, reference
        from modal_examples_tpu.ops.flash_attention import choose_blocks, padded_kv_len

        dt = jnp.dtype(dtype)
        q_offset = offset_chunks * chunk
        Skv = q_offset + chunk
        padded = padded_kv_len(Skv)  # 1280 keys run as 2048: one long key block
        bq, bk = choose_blocks(chunk, padded, D, Dv, dt.itemsize)
        assert chunk // bq > 1  # several query tiles
        if q_offset and padded == Skv:
            assert Skv // bk > 1
            first_tile_last = (q_offset + bq - 1) // bk
            assert 0 < first_tile_last < Skv // bk - 1  # whole, crossed, masked
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (1, Hq, chunk, D), dt)
        k = jax.random.normal(ks[1], (1, Hkv, Skv, D), dt)
        v = jax.random.normal(ks[2], (1, Hkv, Skv, Dv), dt)
        out = flash_attention_chunked(q, k, v, q_offset=q_offset)
        want = reference.attention_chunked(q, k, v, q_offset=q_offset)
        assert out.shape == want.shape and out.dtype == dt
        want = np.asarray(want.astype(jnp.float32))
        # float32: today's bound. bf16: one ulp at the output's scale (the
        # kernel rounds exp(s - m) to bf16 before the second dot and divides
        # by the row sum after it; the reference rounds the normalised p)
        atol = 2e-5 if dtype == "float32" else float(
            jnp.finfo(jnp.bfloat16).eps
        ) * float(np.abs(want).max())
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)), want, atol=atol
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_lse_is_logsumexp_across_tiles(self, jax, jnp, causal):
        """384 rows are three query tiles over three key blocks: the row
        statistics carried from block to block give the whole row's lse."""
        from modal_examples_tpu.ops import flash_attention_with_lse, reference
        from modal_examples_tpu.ops.flash_attention import choose_blocks

        assert choose_blocks(384, 384, 64, 64, 4) == (128, 128)
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (1, 4, 384, 64))
        k = jax.random.normal(ks[1], (1, 2, 384, 64))
        v = jax.random.normal(ks[2], (1, 2, 384, 64))
        o, lse = flash_attention_with_lse(q, k, v, causal=causal)
        want_o, want_lse = reference.attention_with_lse(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=1e-4)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)

    @pytest.mark.parametrize(
        "Sq,Skv,D,Dv",
        [
            (2048, 4096, 128, 128),  # Mistral / Mixtral chunk at offset 2048
            (2048, 2048, 128, 128),  # ... at offset 0
            (2048, 4096, 192, 128),  # DeepSeek-V2 chunk at offset 2048
            (2048, 2048, 192, 128),  # ... at offset 0
            (256, 256, 128, 128),  # the reason cells' bucket call
        ],
    )
    def test_chosen_tiles_divide_and_fit(self, Sq, Skv, D, Dv):
        """Pure Python: at the cells' five shapes (bf16) the chooser returns
        divisors of the lengths whose footprint is under the budget it
        states; a bucket call keeps a tile of its own length."""
        from modal_examples_tpu.ops.flash_attention import (
            _VMEM_BUDGET,
            _VMEM_LIMIT,
            block_footprint,
            choose_blocks,
        )

        bq, bk = choose_blocks(Sq, Skv, D, Dv, 2)
        assert Sq % bq == 0 and Skv % bk == 0
        assert block_footprint(bq, bk, D, Dv, 2) <= _VMEM_BUDGET
        assert _VMEM_BUDGET < _VMEM_LIMIT <= 64 * 2**20
        if Sq >= 2048:  # far fewer steps than 128 x 128 took
            assert bq * bk >= 16 * 128 * 128
        else:
            assert (bq, bk) == (Sq, Skv)
        # a tighter budget falls to smaller divisors, never to a non-divisor
        sq, sk = choose_blocks(Sq, Skv, D, Dv, 2, budget=2**20)
        assert Sq % sq == 0 and Skv % sk == 0 and sq * sk <= bq * bk

    @pytest.mark.parametrize(
        "S,want", [(384, 128), (640, 128), (768, 256), (200, 200), (48, 48), (12, 12)]
    )
    def test_chosen_tile_of_odd_lengths(self, S, want):
        """Lengths no power-of-two tile divides: the 128-multiples fall to
        the largest 128 * 2^i that divides them, short or ragged ones (tests
        and tiny models only) keep one block."""
        from modal_examples_tpu.ops.flash_attention import choose_blocks

        assert choose_blocks(S, S, 64, 64, 4) == (want, want)

    def test_rejects_ragged_seq(self, jax, jnp):
        from modal_examples_tpu.ops import flash_attention

        q = jnp.ones((1, 1, 200, 64))
        with pytest.raises(ValueError, match="multiples? of block"):
            flash_attention(q, q, q, True)


class TestPagedAttention:
    def test_ragged_kernel_matches_inflight(self, jax, jnp):
        """v3 kernel (full [L,P,...] cache + layer scalar + in-flight token)
        must exactly match the XLA inflight formulation the default decode
        path uses — they are interchangeable inside decode_step."""
        from modal_examples_tpu.ops import (
            paged_decode_attention_inflight,
            paged_decode_attention_ragged,
        )

        L, B, Hq, Hkv, D = 3, 4, 8, 2, 64
        page_size, n_pages, pages_per_seq = 16, 40, 4
        ks = jax.random.split(jax.random.PRNGKey(7), 6)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        kp = jax.random.normal(
            ks[1], (L, n_pages, page_size, Hkv, D), jnp.float32
        )
        vp = jax.random.normal(
            ks[2], (L, n_pages, page_size, Hkv, D), jnp.float32
        )
        pt = (
            jax.random.permutation(ks[3], n_pages)[: B * pages_per_seq]
            .reshape(B, pages_per_seq)
            .astype(jnp.int32)
        )
        k_new = jax.random.normal(ks[4], (B, Hkv, D), jnp.float32)
        v_new = jax.random.normal(ks[5], (B, Hkv, D), jnp.float32)
        # ragged, page-unaligned prefixes incl. 0 (fresh slot) and full
        prefix = jnp.array([0, 5, 33, 64], jnp.int32)
        for li in (0, 2):
            want = paged_decode_attention_inflight(
                q, kp[li][pt], vp[li][pt], prefix, k_new, v_new
            )
            got = paged_decode_attention_ragged(
                q, kp, vp, jnp.int32(li), pt, prefix, k_new, v_new
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5,
                err_msg=f"layer {li}",
            )

    def test_ragged_variants_agree(self, jax, jnp):
        """flat (v3 all-heads matmul) and grouped (v4 per-kv-head, the GQA
        path — round 5) are interchangeable formulations of the same math:
        both must match the XLA inflight reference at MHA and GQA shapes."""
        from modal_examples_tpu.ops import (
            paged_decode_attention_inflight,
            paged_decode_attention_ragged,
        )

        page_size, pages_per_seq = 16, 3
        for Hq, Hkv in [(4, 4), (8, 2)]:  # MHA and GQA (G=4)
            L, B, D = 2, 3, 64
            n_pages = 1 + B * pages_per_seq
            ks = jax.random.split(jax.random.PRNGKey(11), 6)
            q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
            kp = jax.random.normal(
                ks[1], (L, n_pages, page_size, Hkv, D), jnp.float32
            )
            vp = jax.random.normal(ks[2], kp.shape, jnp.float32)
            pt = (1 + jnp.arange(B * pages_per_seq, dtype=jnp.int32)).reshape(
                B, pages_per_seq
            )
            k_new = jax.random.normal(ks[3], (B, Hkv, D), jnp.float32)
            v_new = jax.random.normal(ks[4], (B, Hkv, D), jnp.float32)
            prefix = jnp.array([0, 17, 48], jnp.int32)
            want = paged_decode_attention_inflight(
                q, kp[1][pt], vp[1][pt], prefix, k_new, v_new
            )
            for variant in ("flat", "grouped"):
                got = paged_decode_attention_ragged(
                    q, kp, vp, jnp.int32(1), pt, prefix, k_new, v_new,
                    variant=variant,
                )
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), atol=2e-5,
                    err_msg=f"Hq={Hq} Hkv={Hkv} variant={variant}",
                )

    @pytest.mark.parametrize(
        "shape, positions",
        [
            ("mha-tiny", (9, 21)),  # LlamaConfig.tiny: Hq == n_kv_heads path
            ("gqa-g4", (0, 17, 40)),  # llama-3.1 shape class, incl. fresh slot
        ],
    )
    def test_decode_step_pallas_structure_matches_xla(
        self, jax, jnp, shape, positions
    ):
        """decode_step(impl='pallas') (ragged-kernel read-only structure)
        must produce the same logits and cache writes as the default path —
        at MHA-style shapes AND GQA (G=4), where paged_impl_plan
        auto-selects the round-5 grouped variant."""
        from modal_examples_tpu.models import llama

        if shape == "mha-tiny":
            cfg = llama.LlamaConfig.tiny()
        else:
            cfg = llama.LlamaConfig(
                vocab_size=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=2,
                ffn_dim=128, max_seq_len=128, dtype="float32",
            )
            plan = llama.paged_impl_plan(cfg, 16, "pallas", "xla")
            assert plan["ragged_variant"] == "grouped", plan
        params = llama.init_params(jax.random.PRNGKey(4), cfg)
        B, ps, pp = len(positions), 16, 4
        n_pages = 1 + B * pp
        kp = jax.random.normal(
            jax.random.PRNGKey(5),
            (cfg.n_layers, n_pages, ps, cfg.n_kv_heads, cfg.head_dim),
            jnp.float32,
        ) * 0.1
        vp = jax.random.normal(jax.random.PRNGKey(6), kp.shape, jnp.float32) * 0.1
        tables = jnp.asarray(1 + np.arange(B * pp).reshape(B, pp), jnp.int32)
        toks = jnp.asarray(np.arange(3, 3 + B), jnp.int32)
        pos = jnp.asarray(positions, jnp.int32)
        active = jnp.ones((B,), bool)
        outs = {}
        for impl in ("xla", "pallas"):
            lg, k2, v2 = llama.decode_step(
                params, toks, pos, kp, vp, tables, active, cfg, impl=impl
            )
            outs[impl] = (np.asarray(lg), np.asarray(k2), np.asarray(v2))
        for a, b in zip(outs["xla"], outs["pallas"]):
            np.testing.assert_allclose(a, b, atol=3e-5)

    #: chunk 4 pages (64 positions) a half of the ring, update 2 pages
    RING = dict(chunk_pages=4, update_pages=2)

    @pytest.mark.parametrize(
        "case, prefix, kw",
        [
            ("ragged-lengths", (0, 1, 65, 191, 100, 16), RING),
            ("dead-slots-between", (0, 0, 33, 0, 192, 0), RING),
            ("dead-first-and-last", (0, 130, 0, 0, 64, 0), RING),
            ("ends-on-a-chunk-edge", (64, 128, 192, 0, 64, 128), RING),
            ("one-past-a-chunk-edge", (65, 129, 0, 193, 65, 1), RING),
            ("nothing-live", (0, 0, 0, 0, 0, 0), RING),
            # 13 table columns under a 16-page half: every chunk is partial
            ("table-shorter-than-a-chunk", (192, 7, 0, 100, 191, 16),
             dict(chunk_pages=16, update_pages=8)),
            ("sizes-the-kernel-picks", (192, 7, 0, 100, 191, 16), {}),
        ],
    )
    @pytest.mark.parametrize("pages", ["bf16", "int8"])
    # the last: the published SmallThinker's 28 heads over 4 (32 rows in the flat form)
    @pytest.mark.parametrize(
        "heads", [(8, 2), (4, 4), (28, 4)], ids=["gqa-g4", "group-of-one", "gqa-g7-of-4"]
    )
    @pytest.mark.parametrize("variant", ["flat", "grouped"])
    def test_ragged_ring_against_the_reference(
        self, jax, jnp, variant, heads, pages, case, prefix, kw
    ):
        """The kernel's ring (a chunk in flight behind the one computed, the
        next sequence's first chunk started by the last of the one before,
        partial chunks fetched and awaited page by page, dead slots skipped)
        against ``reference.paged_decode_attention`` over the same pages with
        the in-flight token written behind each prefix."""
        from modal_examples_tpu.ops import (
            paged_decode_attention_ragged, quantize_kv, reference,
        )
        from modal_examples_tpu.ops.kv_quant import QuantizedKV

        (Hq, Hkv), D, L, ps, pp, P = heads, 128, 2, 16, 13, 80
        B = len(prefix)
        ks = jax.random.split(jax.random.PRNGKey(35), 6)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (L, P, ps, Hkv, D), jnp.bfloat16)
        vp = jax.random.normal(ks[2], kp.shape, jnp.bfloat16)
        k_new = jax.random.normal(ks[3], (B, Hkv, D), jnp.bfloat16)
        v_new = jax.random.normal(ks[4], (B, Hkv, D), jnp.bfloat16)
        pt = 1 + jax.random.permutation(ks[5], P - 1)[: B * pp].reshape(B, pp).astype(jnp.int32)
        lens = jnp.asarray(prefix, jnp.int32)
        if pages == "int8":
            kp, vp = quantize_kv(kp), quantize_kv(vp)
        got = paged_decode_attention_ragged(
            q, kp, vp, jnp.int32(1), pt, lens, k_new, v_new, variant=variant, **kw
        )
        # the reference reads the in-flight token back from the pages, and
        # computes in float32 on the same bf16 / int8 values
        page = pt[jnp.arange(B), lens // ps]
        f32 = jnp.float32

        def written(x, new):
            if pages == "bf16":
                return x[1].astype(f32).at[page, lens % ps].set(new.astype(f32))
            new = quantize_kv(new)
            return QuantizedKV(
                data=x.data[1].at[page, lens % ps].set(new.data),
                scale=x.scale[1].at[page, lens % ps].set(new.scale),
            )

        want = reference.paged_decode_attention(
            q.astype(f32), written(kp, k_new), written(vp, v_new), pt, lens + 1
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), atol=3e-2, err_msg=case,
        )

    #: positions of a batch's tokens under a test window of 64 (a ring of 5
    #: pages of 16), the published 28 query heads over 4 K/V heads of 128
    WINDOW_CASES = [
        # contexts window .. window + page: the ring's first wrap, position by position
        ("first-turn-position-by-position", tuple(range(64, 81))),
        ("a-window-starting-mid-page", (69, 87, 101, 135, 150, 77)),
        ("dead-slots", (0, 70, 0, 0, 133, 0)),
        ("shorter-than-the-window", (1, 15, 16, 17, 40, 63)),
        ("several-turns-on", (80, 81, 160, 161, 400, 415)),
        ("nothing-live", (0, 0, 0)),
    ]

    @pytest.mark.parametrize("case, positions", WINDOW_CASES)
    @pytest.mark.parametrize(
        "sizes", [{}, dict(chunk_pages=2, update_pages=1)],
        ids=["sizes-the-kernel-picks", "chunks-of-2-pages"],
    )
    def test_flat_at_4_kv_heads_over_a_ring_against_the_reference_and_the_loop(
        self, jax, jnp, sizes, case, positions
    ):
        """The all-heads form at 4 K/V heads with a group of 7 (28 query
        heads run as 32 rows; two tokens' heads to a tile of the flattened
        page), a sliding-window layer's ring read in place from the first
        page the window reaches with a wrap, that page's head masked by
        ``starts``: against ``reference.paged_decode_attention`` over the
        window's positions copied out of the ring into a table of their own
        with the in-flight token behind them, and against the chunked loop
        over the rolled table."""
        from modal_examples_tpu.ops import (
            paged_window_decode_attention_chunked, paged_window_decode_attention_ragged,
            reference, window_ring_pages,
        )

        Hq, Hkv, D, ps, window = 28, 4, 128, 16, 64
        ring, B = window_ring_pages(window, ps), len(positions)
        ks = jax.random.split(jax.random.PRNGKey(42), 6)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (2, 1 + B * ring, ps, Hkv, D), jnp.bfloat16)
        vp = jax.random.normal(ks[2], kp.shape, jnp.bfloat16)
        k_new = jax.random.normal(ks[3], (B, Hkv, D), jnp.bfloat16)
        v_new = jax.random.normal(ks[4], (B, Hkv, D), jnp.bfloat16)
        tables = 1 + jax.random.permutation(ks[5], B * ring).reshape(B, ring).astype(jnp.int32)
        args = (q, kp, vp, jnp.int32(1), tables, jnp.asarray(positions, jnp.int32), k_new, v_new)
        got = paged_window_decode_attention_ragged(*args, window=window, variant="flat", **sizes)
        loop = paged_window_decode_attention_chunked(*args, window=window)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(loop, np.float32), atol=2e-2, err_msg=case
        )
        # the reference: each slot's window, oldest first, then its own token
        f32, pp = jnp.float32, window // ps + 1
        lin = [np.zeros((B * pp, ps, Hkv, D), np.float32) for _ in range(2)]
        lens = []
        for b, t in enumerate(positions):
            seen = np.arange(max(t - window + 1, 0), t)
            at = (np.asarray(tables)[b, (seen // ps) % ring], seen % ps)
            for dst, pages, new in zip(lin, (kp, vp), (k_new, v_new)):
                rows = np.concatenate([np.asarray(pages[1].astype(f32))[at], np.asarray(new[b].astype(f32))[None]])
                dst.reshape(B, pp * ps, Hkv, D)[b, : len(rows)] = rows
            lens.append(len(seen) + 1)
        want = reference.paged_decode_attention(
            q.astype(f32), jnp.asarray(lin[0]), jnp.asarray(lin[1]),
            jnp.arange(B * pp, dtype=jnp.int32).reshape(B, pp), jnp.asarray(lens, jnp.int32),
        )
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=3e-2, err_msg=case)

    def test_a_ring_takes_both_inputs_and_no_int8_pages(self, jax, jnp):
        from modal_examples_tpu.ops import paged_decode_attention_ragged, quantize_kv

        q = jnp.zeros((2, 8, 128), jnp.bfloat16)
        kp = jnp.zeros((1, 9, 16, 4, 128), jnp.bfloat16)
        new = jnp.zeros((2, 4, 128), jnp.bfloat16)
        pt, lens = jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="together"):
            paged_decode_attention_ragged(q, kp, kp, jnp.int32(0), pt, lens, new, new, starts=lens)
        with pytest.raises(NotImplementedError, match="int8"):
            paged_decode_attention_ragged(
                q, quantize_kv(kp), quantize_kv(kp), jnp.int32(0), pt, lens, new, new,
                first_pages=lens, starts=lens,
            )

    def test_mha_group_of_one(self, jax, jnp):
        """Hq == Hkv (a group of one) through both decode attentions
        ``decode_step`` chooses between, against the plain reference over
        the same pages with the in-flight token written behind each
        prefix."""
        from modal_examples_tpu.ops import (
            paged_decode_attention_chunked,
            paged_decode_attention_ragged,
            reference,
        )

        L, B, H, D = 2, 2, 4, 64
        page_size, n_pages, pages_per_seq = 16, 16, 2
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kp = jax.random.normal(ks[1], (L, n_pages, page_size, H, D), jnp.float32)
        vp = jax.random.normal(ks[2], kp.shape, jnp.float32)
        k_new = jax.random.normal(ks[3], (B, H, D), jnp.float32)
        v_new = jax.random.normal(ks[4], (B, H, D), jnp.float32)
        pt = jnp.arange(B * pages_per_seq, dtype=jnp.int32).reshape(B, -1)
        prefix = jnp.array([16, 31], jnp.int32)  # contexts 17 and 32
        page = pt[jnp.arange(B), prefix // page_size]
        for li in range(L):
            want = reference.paged_decode_attention(
                q,
                kp[li].at[page, prefix % page_size].set(k_new),
                vp[li].at[page, prefix % page_size].set(v_new),
                pt, prefix + 1,
            )
            for op in (paged_decode_attention_chunked, paged_decode_attention_ragged):
                out = op(q, kp, vp, jnp.int32(li), pt, prefix, k_new, v_new)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(want), atol=2e-5,
                    err_msg=f"{op.__name__} layer {li}",
                )


class TestPagedImplOption:
    """``paged_impl`` chooses the attention inside the ONE decode structure
    (``llama.decode_step``): it has two values, the plan reports what will
    run for each, and neither the model nor its kernels read the
    environment (the engine resolves ``MTPU_PAGED_IMPL`` once)."""

    @staticmethod
    def _refusal(monkeypatch, how, value):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine

        kw = {}
        if how == "arg":
            kw["paged_impl"] = value
        else:
            monkeypatch.setenv("MTPU_PAGED_IMPL", value)
        with pytest.raises(ValueError, match="unknown paged_impl") as e:
            LLMEngine(llama.LlamaConfig.tiny(), **kw)
        return str(e.value)

    @staticmethod
    def _known(msg):
        """The values the engine accepts, as its own refusal names them."""
        import re

        return re.findall(r"'([\w-]+)'", msg.split("known:")[1])

    @pytest.mark.parametrize(
        "how, value",
        [("arg", "xla-writeback"), ("env", "pallas-writeback")],
    )
    def test_engine_refuses_the_retired_values(self, monkeypatch, how, value):
        msg = self._refusal(monkeypatch, how, value)
        assert repr(value) in msg
        assert self._known(msg) == ["xla", "pallas"], msg

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize("shape", ["tiny", "mistral_7b", "mixtral_8x7b"])
    def test_plan_names_what_runs_for_every_accepted_value(
        self, monkeypatch, jax, shape, backend
    ):
        """Whatever the engine accepts resolves to one of the two attentions
        ``decode_step`` has, and a request the shapes cannot honour (on the
        chip: ``tiny``'s head_dim) is named in ``downgraded``. Left unset
        the plan decides: the kernel on the chip where the shapes allow it,
        the loop elsewhere, and nothing was asked for, so nothing is
        downgraded. The plan is shape arithmetic: ``backend`` only steers
        its legality branch."""
        from modal_examples_tpu.models import llama

        cfg = getattr(llama.LlamaConfig, shape)()
        accepted = self._known(self._refusal(monkeypatch, "arg", "no-such-impl"))
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        kernel_serves = backend == "tpu" and shape != "tiny"
        for impl in [None] + accepted:
            for scatter in ("xla", "pallas"):
                for kvd in ("bfloat16", "int8"):
                    plan = llama.paged_impl_plan(
                        cfg, 16, impl, scatter, kv_dtype=kvd, warn=False
                    )
                    assert plan["attention"] in {"ragged", "xla-gather"}, plan
                    down = " ".join(plan["downgraded"])
                    if impl is None:
                        assert plan["attention"] == (
                            "ragged" if kernel_serves else "xla-gather"
                        ), plan
                        assert "paged_impl" not in down, plan
                    else:
                        asked = {"pallas": "ragged", "xla": "xla-gather"}[impl]
                        assert (plan["attention"] == asked) != (
                            f"paged_impl={impl} ->" in down
                        ), plan
                    assert (plan["scatter"] == scatter) != (
                        f"scatter_impl={scatter} ->" in down
                    ), plan
        if kernel_serves:
            # both serving geometries keep the kernel on the chip, asked for
            # or not: 8 KV heads read a page as (ps * Hkv, D) rows in place
            # and take the all-heads variant (PERF.md section 6, PR 35)
            for impl in (None, "pallas"):
                plan = llama.paged_impl_plan(cfg, 16, impl, warn=False)
                assert (plan["attention"], plan["ragged_variant"]) == (
                    "ragged", "flat"
                ), plan

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize("family", ["deepseek_v2", "granite_hybrid", "glm_dsa"])
    def test_the_other_families_take_unset_as_xla(self, monkeypatch, jax, family, backend):
        """A latent cache and a 64-wide head keep their own plan, the loop:
        unset reads as ``xla`` does, whatever the backend, and the kernel
        asked for by name is refused as before."""
        import importlib

        module = importlib.import_module(f"modal_examples_tpu.models.{family}")
        cfg = next(
            getattr(module, name) for name in dir(module) if name.endswith("Config")
            and hasattr(getattr(module, name), "tiny")
        ).tiny()
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        unset = module.paged_impl_plan(cfg, 16)
        assert unset == module.paged_impl_plan(cfg, 16, None, "xla")
        assert unset == module.paged_impl_plan(cfg, 16, "xla", "xla")
        assert (unset["attention"], unset["ragged_variant"]) == ("xla-gather", None)
        with pytest.raises(NotImplementedError, match="Pallas paged_impl"):
            module.paged_impl_plan(cfg, 16, "pallas")

    def test_engine_leaves_an_unset_paged_impl_to_the_plan(self, monkeypatch):
        """No argument and no ``MTPU_PAGED_IMPL``: the engine holds None, not
        a default of its own, and hands it to the model's plan (on the CPU:
        the loop); an empty variable is unset too."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine

        for env in (None, ""):
            if env is None:
                monkeypatch.delenv("MTPU_PAGED_IMPL", raising=False)
            else:
                monkeypatch.setenv("MTPU_PAGED_IMPL", env)
            eng = LLMEngine(llama.LlamaConfig.tiny(), seed=0, max_slots=2, max_model_len=64)
            try:
                assert eng.paged_impl is None
                assert eng.impl_plan["attention"] == "xla-gather"
                assert eng.impl_plan["downgraded"] == []
            finally:
                eng.stop()

    def test_model_and_kernels_do_not_read_the_environment(self):
        """``decode_step`` is jitted by its callers: an environment read in
        it or below it happens at trace time and is in no jit cache key."""
        import ast
        from pathlib import Path

        import modal_examples_tpu

        root = Path(modal_examples_tpu.__file__).parent
        for rel in ("ops/paged_attention.py", "models/llama.py"):
            tree = ast.parse((root / rel).read_text())
            reads = [
                f"{rel}:{n.lineno}" for n in ast.walk(tree)
                if (isinstance(n, ast.Attribute) and n.attr in ("environ", "getenv"))
                or (isinstance(n, ast.Name) and n.id in ("environ", "getenv"))
            ]
            assert not reads, reads


class TestWhatThe4HeadFormLeftAlone:
    """PR 42 gave the ragged kernel a ring's first page and ``starts`` and
    let 4 K/V heads take the all-heads form; what ran before runs as it did:
    the 8-K/V-head call, the sizes, the other configurations' cache leaves
    and the plans of the families that look like 4 heads of 128 and are not
    (pinned to the parent commit ac1c821, PR 41)."""

    #: sha256 (16 hex) of the lowered text of the call below at the parent
    PARENT_CALL = {"flat": "989899418ecc2cf8", "grouped": "60347f364921d23c"}

    @pytest.mark.parametrize("variant", ["flat", "grouped"])
    def test_the_8_kv_head_call_lowers_to_the_parents_text(self, jax, jnp, variant):
        """Mistral's shapes (16 slots, 32 heads over 8 K/V heads of 128,
        pages of 16, a 256-page table, bf16), the ring's inputs unset: no
        row is padded, no scalar added, the kernel's body is the parent's."""
        import hashlib

        from modal_examples_tpu.ops import paged_decode_attention_ragged

        S, bf16 = jax.ShapeDtypeStruct, jnp.bfloat16
        text = jax.jit(
            lambda q, kp, vp, pt, lens, kn, vn: paged_decode_attention_ragged(
                q, kp, vp, jnp.int32(1), pt, lens, kn, vn, variant=variant
            )
        ).lower(
            S((16, 32, 128), bf16), S((2, 96, 16, 8, 128), bf16), S((2, 96, 16, 8, 128), bf16),
            S((16, 256), jnp.int32), S((16,), jnp.int32), S((16, 8, 128), bf16), S((16, 8, 128), bf16),
        ).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == self.PARENT_CALL[variant]

    @pytest.mark.parametrize(
        "args, want",
        [
            (("flat", 16, 8, 128, 2, 256), (16, 16)),  # Mistral, Mixtral: bf16 pages
            (("flat", 16, 8, 128, 1, 256), (32, 16)),  # ... int8 pages
            (("grouped", 16, 8, 128, 2, 256), (16, 8)),
            (("grouped", 16, 2, 128, 2, 256), (64, 8)),  # a head shard under tensor parallelism
            # SmallThinker's, derived by the same rule: 2048 logit columns an
            # update = 32 pages at 4 heads, a 2 MiB ring = 32 pages a half
            (("flat", 16, 4, 128, 2, 257), (32, 32)),
            (("flat", 16, 4, 128, 2, 512), (32, 32)),
        ],
    )
    def test_the_kernels_sizes_come_from_the_shapes(self, args, want):
        from modal_examples_tpu.ops import ragged_kernel_sizes

        assert ragged_kernel_sizes(*args) == want

    def test_the_default_variant_and_where_the_flat_view_is_free(self):
        from modal_examples_tpu.ops.paged_attention import (
            FLAT_VARIANT_HKV_MULTIPLE, flat_view_is_free, ragged_variant_for,
        )

        assert FLAT_VARIANT_HKV_MULTIPLE == 8
        assert [ragged_variant_for(n) for n in (8, 16, 32)] == ["flat"] * 3
        # a 4-head shard under tensor parallelism keeps what it had; a model
        # of 4 K/V heads on one chip asks for "flat" by name (its plan)
        assert [ragged_variant_for(n) for n in (1, 2, 4, 12)] == ["grouped"] * 4
        assert [n for n in range(1, 33) if flat_view_is_free(n)] == [4, 8, 16, 24, 32]

    #: [k_pages, v_pages, *beside] at 3 pages of 16 and one slot, bf16
    PARENT_LEAVES = {
        "mistral-7b-int8": ("llama.LlamaConfig", [[32, 3, 16, 8, 128]] * 2),
        "mixtral-8x7b-int8-1chip": ("llama.LlamaConfig", [[7, 3, 16, 8, 128]] * 2),
        "deepseek-v2-int8-ep4": (
            "deepseek_v2.DeepseekV2Config", [[8, 3, 16, 1, 512], [8, 3, 16, 1, 64]]),
        "glm-5.2-int8-ep16": (
            "glm_dsa.GlmDsaConfig", [[8, 3, 16, 1, 512], [8, 3, 16, 1, 64], [2, 3, 16, 1, 128]]),
        "granite-4.0-h-micro-bf16": (
            "granite_hybrid.GraniteHybridConfig",
            [[4, 3, 16, 4, 128], [4, 3, 16, 4, 128], [36, 1, 64, 64, 128], [36, 1, 3, 4352]]),
        "lfm2-24b-a2b-int8-1chip": (
            "lfm2.Lfm2Config", [[4, 3, 16, 4, 128], [4, 3, 16, 4, 128], [14, 1, 2, 2048]]),
    }

    @staticmethod
    def _config(name, where):
        import importlib

        module, cls = where.split(".")
        module = importlib.import_module(f"modal_examples_tpu.models.{module}")
        return module, getattr(module, cls).from_hf_config(f"benchmarks/serving/configs/{name}.json")

    @pytest.mark.parametrize("name", sorted(PARENT_LEAVES))
    def test_the_other_configurations_cache_leaves_are_the_parents(self, jnp, name):
        """The free view needed no new leaf shape, so ``serving/kv_cache.py``
        is the parent's and every other configuration of the benchmark gets
        the leaves it had."""
        from modal_examples_tpu.serving.kv_cache import PagedKVCache

        where, want = self.PARENT_LEAVES[name]
        _, cfg = self._config(name, where)
        cache = PagedKVCache.create(
            n_layers=getattr(cfg, "n_cache_layers", cfg.n_layers), leaf_shapes=cfg.cache_leaf_shapes,
            leaf_layers=getattr(cfg, "cache_leaf_layers", None), n_pages=3, page_size=16,
            kv_dtype=jnp.bfloat16, state_leaves=getattr(cfg, "state_leaves", ()), max_slots=1,
            window_group=getattr(cfg, "window_group", None), prefer_native=False,
        )
        assert [list(a.shape) for a in (cache.k_pages, cache.v_pages, *cache.beside)] == want

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize("name", ["granite-4.0-h-micro-bf16", "lfm2-24b-a2b-int8-1chip"])
    def test_heads_of_64_folded_into_rows_of_128_keep_the_loop(self, monkeypatch, jax, name, backend):
        """Granite's and LFM2's pages are ``[16, 4, 128]`` too, but a row is
        two heads of 64: the plan is the model's, and theirs is the loop."""
        module, cfg = self._config(name, self.PARENT_LEAVES[name][0])
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        kw = {"expert_dtype": "int8"} if name.startswith("lfm2") else {}
        plan = module.paged_impl_plan(cfg, 16, **kw)
        want = {
            "attention": "xla-gather", "ragged_variant": None, "scatter": "xla",
            "kv_dtype": "bfloat16", "tp": 1, "downgraded": [],
            "state_step": "pallas" if backend == "tpu" and name.startswith("granite") else "xla",
        }
        if name.startswith("lfm2"):
            want["expert_scan"] = "pallas" if backend == "tpu" else "xla"
        assert plan == want
        with pytest.raises(NotImplementedError, match="Pallas paged_impl"):
            module.paged_impl_plan(cfg, 16, "pallas")


class TestChunkedDecodeAttention:
    """ops.paged_decode_attention_chunked (the default decode path since PR
    25: the page table walked in chunks as far as the longest live prefix)
    against ops.reference at full width."""

    PS, PP = 16, 72  # 4.5 chunks of 16 pages: the last one is padded

    @staticmethod
    def _edges(ps, pp):
        from modal_examples_tpu.ops import decode_chunk_pages

        span = decode_chunk_pages(ps, pp) * ps
        assert pp * ps > 2 * span, "the table must hold several chunks"
        return span

    def _case(self, jax, jnp, kv, heads, prefix, L=2):
        """Inputs, and per layer the reference's answer: the in-flight token
        written behind each prefix (the table gets one more column for a
        prefix that fills it) and ops.reference over context = prefix + 1.
        An int8 cache goes quantized into the op and dequantized into the
        reference, which isolates the loop from quantization noise."""
        from modal_examples_tpu import ops
        from modal_examples_tpu.ops import reference

        ps, pp = self.PS, self.PP
        Hq, Hkv = heads
        D = 32
        B = len(prefix)
        dt = jnp.float32 if kv == "f32" else jnp.bfloat16
        ks = jax.random.split(jax.random.PRNGKey(len(prefix) + Hkv), 5)
        n_pages = 1 + B * (pp + 1)
        q = jax.random.normal(ks[0], (B, Hq, D), dt)
        kp = jax.random.normal(ks[1], (L, n_pages, ps, Hkv, D), dt)
        vp = jax.random.normal(ks[2], kp.shape, dt)
        k_new = jax.random.normal(ks[3], (B, Hkv, D), dt)
        v_new = jax.random.normal(ks[4], (B, Hkv, D), dt)
        wide = 1 + np.arange(B * (pp + 1), dtype=np.int32).reshape(B, pp + 1)
        prefix = np.asarray(prefix, np.int32)
        dead = prefix < 0
        prefix = np.where(dead, 0, prefix)
        tables = np.where(dead[:, None], 0, wide[:, :pp])  # dead: trash page
        if kv == "int8":
            kp, vp = ops.quantize_kv(kp), ops.quantize_kv(vp)
        page = wide[np.arange(B), prefix // ps]
        want = []
        for li in range(L):
            kd = ops.dequantize_kv(kp[li], dt).at[page, prefix % ps].set(k_new)
            vd = ops.dequantize_kv(vp[li], dt).at[page, prefix % ps].set(v_new)
            want.append(reference.paged_decode_attention(
                q, kd, vd, jnp.asarray(wide), jnp.asarray(prefix + 1)
            ))
        args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(prefix), k_new, v_new)
        return args, want, 2e-5 if kv == "f32" else 3e-2

    @pytest.mark.parametrize("how", ["jit", "scan"])
    @pytest.mark.parametrize("batch", ["edges", "short-and-dead"])
    @pytest.mark.parametrize("heads", [(32, 8), (32, 32)], ids=["gqa", "mha"])
    @pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
    def test_matches_reference(self, jax, jnp, kv, heads, batch, how):
        from modal_examples_tpu.ops import paged_decode_attention_chunked

        span = self._edges(self.PS, self.PP)
        if batch == "edges":
            # every edge of a chunk and of the table in one batch, with a
            # fresh slot (prefix 0) and a dead one (-1)
            prefix = [0, 1, span - 1, span, span + 1, self.PS * self.PP, -1]
        else:
            # one trip: nobody reaches the second chunk; two dead slots
            prefix = [-1, 5, span - 1, -1]
        (q, kp, vp, pt, pl_, kn, vn), want, tol = self._case(
            jax, jnp, kv, heads, prefix
        )
        if how == "jit":
            got = [
                jax.jit(paged_decode_attention_chunked)(
                    q, kp, vp, jnp.int32(li), pt, pl_, kn, vn
                )
                for li in range(len(want))
            ]
        else:
            # as decode_step runs it: inside the scan over layers
            def layer(carry, li):
                o = paged_decode_attention_chunked(
                    q, kp, vp, li, pt, pl_, kn, vn
                )
                return carry, o

            got = jax.jit(
                lambda: jax.lax.scan(layer, 0, jnp.arange(len(want)))[1]
            )()
        for li, w in enumerate(want):
            np.testing.assert_allclose(
                np.asarray(got[li], np.float32), np.asarray(w, np.float32),
                atol=tol, err_msg=f"layer {li}",
            )

    @pytest.mark.parametrize("chunks, plus, trips", [
        (0, 0, 0), (0, 1, 1), (1, -1, 1), (1, 0, 1), (1, 1, 2), (2, 0, 2),
        (2, 1, 3), (4, 1, 5), (4.5, 0, 5), (20, 0, 5),
    ])
    def test_trip_count(self, jax, jnp, chunks, plus, trips):
        """ceil(longest / chunk positions), never past the table (4.5
        chunks: 5 trips); the same for the host's numpy as for a traced
        scalar."""
        from modal_examples_tpu.ops import decode_chunk_trips

        longest = int(chunks * self._edges(self.PS, self.PP)) + plus
        assert decode_chunk_trips(np.int64(longest), self.PS, self.PP) == trips
        on_device = jax.jit(
            lambda n: decode_chunk_trips(n, self.PS, self.PP)
        )(jnp.int32(longest))
        assert int(on_device) == trips

    def test_a_longer_neighbour_changes_nothing(self, jax, jnp):
        """A chunk a slot has nothing in leaves its running state as it
        was (alpha 1, p 0): the slot's output is bit-identical however far
        a longer neighbour makes the loop run, which is what lets a
        failover replay (one slot alone) rebuild the KV decode wrote."""
        from modal_examples_tpu.ops import paged_decode_attention_chunked

        span = self._edges(self.PS, self.PP)
        (q, kp, vp, pt, pl_, kn, vn), _, _ = self._case(
            jax, jnp, "bf16", (32, 8), [span // 2, self.PS * self.PP - 1], L=1
        )
        fn = jax.jit(paged_decode_attention_chunked)
        both = fn(q, kp, vp, jnp.int32(0), pt, pl_, kn, vn)
        alone = fn(q, kp, vp, jnp.int32(0), pt, pl_.at[1].set(0), kn, vn)
        np.testing.assert_array_equal(
            np.asarray(both[0], np.float32), np.asarray(alone[0], np.float32)
        )

    def test_decode_step_compiles_to_a_loop_not_a_table_gather(self, jax, jnp):
        """The default decode step holds the chunk loop (a ``while`` nested
        in the layer scan's) and no array of a whole table's gathered pages
        ``[B, pages_per_seq, page_size, Hkv, D]``."""
        import re

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.ops import decode_chunk_pages

        cfg = llama.LlamaConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=2,
            ffn_dim=128, max_seq_len=2048, dtype="float32",
        )
        B, ps, pp, n_pages = 3, 16, 96, 8
        W = decode_chunk_pages(ps, pp)
        assert W < pp
        shape = (cfg.n_layers, n_pages, ps, cfg.n_kv_heads, cfg.head_dim)
        S = jax.ShapeDtypeStruct
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg)
        )
        text = jax.jit(
            lambda *a: llama.decode_step(*a, cfg)
        ).lower(
            params, S((B,), jnp.int32), S((B,), jnp.int32),
            S(shape, jnp.float32), S(shape, jnp.float32),
            S((B, pp), jnp.int32), S((B,), bool),
        ).compile().as_text()
        whiles = re.findall(r"= \([^\n]*\) while\(", text)
        assert len(whiles) >= 2, len(whiles)
        dims = f"[{B},{{n}},{ps},{cfg.n_kv_heads},{cfg.head_dim}]"
        assert dims.format(n=pp) not in text
        assert f"[{B * pp},{ps},{cfg.n_kv_heads},{cfg.head_dim}]" not in text
        # the chunk's gather is there instead
        assert (
            dims.format(n=W) in text
            or f"[{B * W},{ps},{cfg.n_kv_heads},{cfg.head_dim}]" in text
        )


class TestQuantizedMatmul:
    def test_quantize_roundtrip(self, jax, jnp):
        from modal_examples_tpu.ops import dequantize_int8, quantize_int8

        w = jax.random.normal(jax.random.PRNGKey(0), (256, 512))
        q, s = quantize_int8(w)
        w2 = dequantize_int8(q, s)
        assert float(jnp.max(jnp.abs(w - w2))) < float(jnp.max(s)) * 0.51

    def test_matmul_matches_dequantized(self, jax, jnp):
        from modal_examples_tpu.ops import dequantize_int8, quantize_int8, quantized_matmul

        ks = jax.random.split(jax.random.PRNGKey(3), 2)
        x = jax.random.normal(ks[0], (256, 512), jnp.float32)
        w = jax.random.normal(ks[1], (512, 256), jnp.float32)
        wq, ws = quantize_int8(w)
        out = quantized_matmul(x, wq, ws, block_m=128, block_n=128, block_k=256)
        want = x @ dequantize_int8(wq, ws)
        # kernel computes in bf16 on the MXU: tolerance = bf16 matmul error
        # (measured ~0.34 max for this size), not f32 error
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=0.5)

    def test_fallback_on_ragged_shapes(self, jax, jnp):
        from modal_examples_tpu.ops import quantize_int8, quantized_matmul

        x = jax.random.normal(jax.random.PRNGKey(0), (100, 300), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (300, 77), jnp.float32)
        wq, ws = quantize_int8(w)
        out = quantized_matmul(x, wq, ws)
        assert out.shape == (100, 77)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, jax, jnp, causal):
        from modal_examples_tpu.ops.ring_attention import ulysses_attention_sharded
        from modal_examples_tpu.ops import reference
        from modal_examples_tpu.parallel import make_mesh

        mesh = make_mesh({"seq": 4})
        B, H, S, D = 1, 8, 512, 64
        ks = jax.random.split(jax.random.PRNGKey(13), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
        out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
        want = reference.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-4
        )

    def test_rejects_indivisible_heads(self, jax, jnp):
        from modal_examples_tpu.ops.ring_attention import ulysses_attention_sharded
        from modal_examples_tpu.parallel import make_mesh

        mesh = make_mesh({"seq": 4})
        x = jnp.ones((1, 6, 128, 64))  # 6 heads not divisible by 4 shards
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention_sharded(x, x, x, mesh)


class TestRingAttention:
    @pytest.mark.slow
    def test_gradients_match_dense(self, jax, jnp):
        from modal_examples_tpu.ops import reference, ring_attention_sharded
        from modal_examples_tpu.parallel import make_mesh

        mesh = make_mesh({"seq": 2})
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64))
        k = jax.random.normal(ks[1], (1, 2, 256, 64))
        v = jax.random.normal(ks[2], (1, 2, 256, 64))
        g1 = jax.grad(
            lambda q, k, v: ring_attention_sharded(q, k, v, mesh, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: reference.attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-4
            )

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense_over_seq_mesh(self, jax, jnp, causal):
        from modal_examples_tpu.ops import reference, ring_attention_sharded
        from modal_examples_tpu.parallel import make_mesh

        mesh = make_mesh({"seq": 4})
        B, H, S, D = 1, 2, 512, 64  # 4 shards x 128
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        want = reference.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-4
        )
