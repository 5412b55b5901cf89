"""Disaggregated prefill/decode serving (serving/disagg, docs/disagg.md):
wire codec roundtrips + corruption detection, chunked transfer with
resumable retry, role-aware routing, the end-to-end token-identity
acceptance (prefill on A + migrate + decode on B == unified, bf16 AND int8,
including a host-tier prefix hit), mid-transfer death -> unified fallback,
and abort/deadline during an in-flight migration releasing reservations and
pages on BOTH replicas."""

import numpy as np
import pytest

from modal_examples_tpu.serving.disagg.transport import (
    ChunkAssembler,
    LoopbackChannel,
    TransferAborted,
    TransportError,
    chain_hashes,
    deserialize_block,
    iter_chunks,
    serialize_block,
    transfer,
)


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _cache(jax, kv_dtype, n_pages=6):
    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    return PagedKVCache.create(
        n_layers=2, n_kv_heads=2, head_dim=4, n_pages=n_pages, page_size=4,
        kv_dtype=kv_dtype, prefer_native=False,
    )


def _fill_cache(jax, cache, seed=0):
    """Write distinguishable values into every page of every leaf."""
    import jax.numpy as jnp

    from modal_examples_tpu.serving.disagg.transport import wire_leaves

    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten(cache)
    new = []
    for leaf in flat:
        vals = rng.normal(size=leaf.shape).astype(np.float32)
        new.append(jnp.asarray(vals).astype(leaf.dtype))
    rebuilt = jax.tree_util.tree_unflatten(treedef, new)
    cache.k_pages, cache.v_pages = rebuilt.k_pages, rebuilt.v_pages
    assert len(wire_leaves(cache)) == len(flat)


class TestTransport:
    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_extract_serialize_adopt_roundtrip_is_exact(self, jax, kv_dtype):
        """Every cache leaf survives the wire bit-exactly: extract ->
        serialize -> deserialize -> adopt into a second cache reproduces
        the source pages (the property token-identity rests on)."""
        from modal_examples_tpu.serving.disagg.transport import (
            adopt_pages,
            extract_pages,
            wire_leaves,
        )

        src = _cache(jax, kv_dtype)
        _fill_cache(jax, src, seed=1)
        page_ids = [2, 4, 1]  # arbitrary order: table order must be kept
        block = extract_pages(src, page_ids, meta={"position": 9})
        wire = serialize_block(block)
        back = deserialize_block(wire)
        assert back.kv_dtype == kv_dtype
        assert back.meta["position"] == 9
        dst = _cache(jax, kv_dtype)
        dst_ids = [3, 1, 5]
        adopt_pages(dst, back, dst_ids)
        for (name, s_leaf), (_, d_leaf) in zip(
            wire_leaves(src), wire_leaves(dst)
        ):
            s = np.asarray(s_leaf[:, np.asarray(page_ids)])
            d = np.asarray(d_leaf[:, np.asarray(dst_ids)])
            assert np.array_equal(s, d), name

    def test_int8_ships_scale_rows_and_half_the_bytes(self, jax):
        from modal_examples_tpu.serving.kv_cache import PagedKVCache
        from modal_examples_tpu.serving.disagg.transport import extract_pages

        def big(kv_dtype):  # realistic head_dim so scale overhead is ~6%
            c = PagedKVCache.create(
                n_layers=2, n_kv_heads=2, head_dim=64, n_pages=6,
                page_size=4, kv_dtype=kv_dtype, prefer_native=False,
            )
            _fill_cache(jax, c, seed=2)
            return c

        wire_bf = serialize_block(extract_pages(big("bfloat16"), [1, 2]))
        wire_q = serialize_block(extract_pages(big("int8"), [1, 2]))
        block_q = deserialize_block(wire_q)
        assert {n for n in block_q.leaves if n.endswith(".scale")}, (
            "int8 blocks must carry the f32 scale rows"
        )
        # int8 data halves the bf16 payload; f32 scales add ~1/D
        assert len(wire_q) < 0.65 * len(wire_bf)

    def test_corrupt_payload_is_a_loud_error(self, jax):
        from modal_examples_tpu.serving.disagg.transport import extract_pages

        src = _cache(jax, "int8")
        _fill_cache(jax, src, seed=3)
        wire = bytearray(serialize_block(extract_pages(src, [1])))
        wire[-3] ^= 0xFF  # flip a byte in the last leaf's payload
        with pytest.raises(TransportError, match="crc"):
            deserialize_block(bytes(wire))

    def test_dtype_and_geometry_mismatches_rejected(self, jax):
        from modal_examples_tpu.serving.disagg.transport import (
            adopt_pages,
            extract_pages,
        )

        src = _cache(jax, "int8")
        block = extract_pages(src, [1])
        with pytest.raises(TransportError, match="kv_dtype"):
            adopt_pages(_cache(jax, "bfloat16"), block, [1])
        with pytest.raises(TransportError, match="pages"):
            adopt_pages(_cache(jax, "int8"), block, [1, 2])

    def test_chain_hashes_are_position_dependent(self):
        a = chain_hashes([1, 2, 3, 4, 1, 2, 3, 4], page_size=4)
        assert len(a) == 2
        assert a[0] != a[1]  # same tokens, different depth -> different hash
        b = chain_hashes([9, 9, 9, 9, 1, 2, 3, 4], page_size=4)
        assert a[1] != b[1]  # the chain encodes the whole prefix


class TestChunkedTransfer:
    def test_chunks_reassemble(self):
        payload = bytes(range(256)) * 40
        chunks = iter_chunks(payload, "t1", chunk_bytes=1000)
        asm = ChunkAssembler("t1")
        for c in reversed(chunks):  # arrival order must not matter
            assert asm.add(c)
        assert asm.complete and asm.payload() == payload

    def test_missing_and_corrupt_chunks_are_tracked(self):
        payload = b"x" * 5000
        chunks = iter_chunks(payload, "t2", chunk_bytes=1000)
        asm = ChunkAssembler("t2")
        kind, tid, seq, total, crc, piece = chunks[2]
        asm.add((kind, tid, seq, total, crc, b"!" + piece[1:]))  # corrupt
        for c in chunks[:2] + chunks[3:]:
            asm.add(c)
        assert not asm.complete
        assert asm.missing() == [2] and asm.corrupt == 1
        asm.add(chunks[2])  # resumable retry: just the gap
        assert asm.complete and asm.payload() == payload

    def test_transfer_retries_only_the_gaps(self):
        """A channel that corrupts two chunks on the first pass: the second
        round re-sends exactly those and the transfer completes."""

        class Flaky(LoopbackChannel):
            def __init__(self):
                super().__init__()
                self.sent = []
                self._dropped = set()

            def send(self, chunk):
                self.sent.append(chunk[2])
                if chunk[2] in (1, 3) and chunk[2] not in self._dropped:
                    self._dropped.add(chunk[2])
                    mangled = chunk[:4] + (chunk[4], b"\x00" * len(chunk[5]))
                    super().send(mangled)
                    return
                super().send(chunk)

        ch = Flaky()
        payload = bytes(range(256)) * 30
        out = transfer(payload, ch, transfer_id="t3", chunk_bytes=1024)
        assert out == payload
        # second round resent ONLY the two corrupt sequence numbers
        n_chunks = len(iter_chunks(payload, "t3", 1024))
        assert ch.sent == list(range(n_chunks)) + [1, 3]

    def test_transfer_gives_up_loudly(self):
        class Dead(LoopbackChannel):
            def send(self, chunk):
                pass  # every chunk vanishes

        with pytest.raises(TransportError, match="missing"):
            transfer(b"abc" * 100, Dead(), transfer_id="t4", chunk_bytes=64,
                     max_rounds=2)

    def test_transfer_abort_checks_between_chunks(self):
        sent = []

        class Counting(LoopbackChannel):
            def send(self, chunk):
                sent.append(chunk)
                super().send(chunk)

        with pytest.raises(TransferAborted):
            transfer(
                b"z" * 4096,
                Counting(),
                transfer_id="t5",
                chunk_bytes=256,
                should_abort=lambda: len(sent) >= 3,
            )
        assert len(sent) == 3  # stopped mid-stream, not after the tail


def _tiny_engine(jax, seed=0, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (32,))
    return LLMEngine(llama.LlamaConfig.tiny(), seed=seed, **kw)


def _pair(jax, kv_dtype=None, seed=0, coord_kw=None, prefill_kw=None,
          decode_kw=None):
    from modal_examples_tpu.scheduling import EngineReplica
    from modal_examples_tpu.serving.disagg import DisaggCoordinator

    kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
    ep = _tiny_engine(jax, seed=seed, **kw, **(prefill_kw or {}))
    ed = _tiny_engine(jax, seed=seed, **kw, **(decode_kw or {}))
    co = DisaggCoordinator(
        [
            EngineReplica(ep, "pre-0", role="prefill"),
            EngineReplica(ed, "dec-0", role="decode"),
        ],
        **{"chunk_bytes": 512, **(coord_kw or {})},
    )
    return ep, ed, co


def _drain_used(engine) -> int:
    """Pages still allocated after draining the zero-ref prefix cache —
    the leak detector: 0 means nothing is orphaned."""
    if engine.prefix_cache is not None:
        engine.prefix_cache.evict(10_000)
    return (engine.cache.n_pages - 1) - engine.cache.allocator.available


PROMPT = "the quick brown fox jumps over the lazy dog and then some more"


class TestDisaggE2E:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["bf16", "int8"])
    @pytest.mark.parametrize("temperature", [0.0, 1.0],
                             ids=["greedy", "seeded"])
    def test_token_identical_to_unified(self, jax, kv_dtype, temperature):
        """Acceptance: a request prefilled on replica A and decoded on
        replica B produces token-identical output to the same request on a
        unified replica, bf16 and int8, greedy and seeded sampling."""
        from modal_examples_tpu.serving import SamplingParams

        params = SamplingParams(max_tokens=12, temperature=temperature,
                                seed=None if temperature == 0.0 else 123)
        kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
        # PROMPT fills 63 of _tiny_engine's 64 positions: with room for all
        # 12 tokens, eleven of them are decoded on replica B
        room = {"max_model_len": 128}
        uni = _tiny_engine(jax, seed=0, **kw, **room)
        try:
            ref_req = uni.submit(PROMPT, params)
            ref = "".join(uni.stream(ref_req))
        finally:
            uni.stop()
        # identity is held token id by token id: what a random-weight model's
        # ids happen to decode to (ids past the byte range: nothing) is luck
        ref_ids = list(ref_req.generated_tokens)
        assert len(ref_ids) == params.max_tokens
        ep, ed, co = _pair(jax, kv_dtype, seed=0, prefill_kw=room,
                           decode_kw=room)
        try:
            req = co.submit(PROMPT, params)
            out = "".join(co.stream(req))
            assert list(req.generated_tokens) == ref_ids
            assert out == ref
            assert req.finish_reason in ("stop", "length")
            assert co.migrations_ok == 1 and co.migrations_fallback == 0
            # no leaked pages or reservations on either replica
            assert ed.admission.reserved_pages == 0
            assert _drain_used(ep) == 0
            assert _drain_used(ed) == 0
        finally:
            ed.stop()

    def test_host_tier_prefix_hit_still_token_identical(self, jax):
        """Acceptance (tiered): the shared prefix is evicted from the
        prefill replica's HBM trie into the host-RAM tier, and the next
        disagg request promotes it back — tier hit recorded, output still
        token-identical to unified."""
        from modal_examples_tpu.serving import SamplingParams

        params = SamplingParams(max_tokens=10, temperature=0.0)
        uni = _tiny_engine(jax, seed=0, kv_dtype="int8")
        try:
            ref = uni.generate(PROMPT, params)
        finally:
            uni.stop()
        ep, ed, co = _pair(
            jax, "int8", seed=0,
            prefill_kw={"tiered_prefix": {"host_bytes": 1 << 20}},
        )
        try:
            first = co.submit(PROMPT, params)
            assert "".join(co.stream(first)) == ref
            # evict the trie: pages spill to the host tier
            ep.prefix_cache.evict(10_000)
            assert ep.tiered.stats()["host"]["blocks"] > 0
            again = co.submit(PROMPT, params)
            assert "".join(co.stream(again)) == ref
            assert ep.tiered.stats()["hits"]["host"] > 0
        finally:
            ed.stop()

    def test_mid_transfer_death_falls_back_to_unified(self, jax):
        """Acceptance: the channel dies mid-stream (replica death) — the
        coordinator re-prefills on the decode-capable replica, output still
        matches unified, and the router keeps serving afterwards."""
        from modal_examples_tpu.serving import SamplingParams

        params = SamplingParams(max_tokens=10, temperature=0.0)
        uni = _tiny_engine(jax, seed=0)
        try:
            ref = uni.generate(PROMPT, params)
        finally:
            uni.stop()

        class DiesMidStream(LoopbackChannel):
            def __init__(self):
                super().__init__()
                self.n = 0

            def send(self, chunk):
                self.n += 1
                if self.n == 2:
                    raise ConnectionError("prefill replica died")
                super().send(chunk)

        ep, ed, co = _pair(
            jax, seed=0, coord_kw={"channel_factory": DiesMidStream}
        )
        try:
            req = co.submit(PROMPT, params)
            out = "".join(co.stream(req))
            assert out == ref
            assert co.migrations_fallback == 1
            assert ed.admission.reserved_pages == 0
            # router is not wedged: the next request also completes (its
            # migration dies too; fallback keeps serving)
            req2 = co.submit(PROMPT, params)
            assert "".join(co.stream(req2)) == ref
            assert _drain_used(ed) == 0
        finally:
            ed.stop()

    def test_no_prefill_peer_serves_unified(self, jax):
        """Fallback by plan: a fleet with no prefill replicas routes
        straight to unified serving, no migration attempted."""
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving.disagg import DisaggCoordinator

        ed = _tiny_engine(jax, seed=0)
        co = DisaggCoordinator([EngineReplica(ed, "solo", role="unified")])
        try:
            req = co.submit(PROMPT, SamplingParams(max_tokens=4))
            "".join(co.stream(req))
            assert req.finish_reason in ("stop", "length")
            assert co.migrations_ok == 0
        finally:
            ed.stop()


class TestAbortDuringMigration:
    """The PR 4 abort-of-queued regression, extended to the migration
    window: a client abort or deadline expiry while pages are ON THE WIRE
    must release the decode-side reservation and leave no orphaned pages on
    either replica."""

    def _gated_pair(self, jax, clock=None):
        """Coordinator whose channel fires a callback after the first
        chunk — the deterministic 'mid-transfer' hook."""
        hook = {"fn": None}

        class Gated(LoopbackChannel):
            def __init__(self):
                super().__init__()
                self.n = 0

            def send(self, chunk):
                self.n += 1
                if self.n == 1 and hook["fn"] is not None:
                    hook["fn"]()
                super().send(chunk)

        decode_kw = {"clock": clock} if clock is not None else {}
        ep, ed, co = _pair(
            jax, seed=0,
            coord_kw={"channel_factory": Gated, "chunk_bytes": 64},
            decode_kw=decode_kw,
        )
        return ep, ed, co, hook

    def test_client_abort_mid_transfer_releases_both_sides(self, jax):
        from modal_examples_tpu.serving import SamplingParams

        ep, ed, co, hook = self._gated_pair(jax)
        try:
            hook["fn"] = lambda: co.migrations()[0].request.__setattr__(
                "aborted", True
            )
            req = co.submit(PROMPT, SamplingParams(max_tokens=16))
            assert "".join(co.stream(req)) == ""  # nothing decoded
            assert req.finish_reason == "stop"
            assert co.migrations_aborted == 1
            assert ed.admission.reserved_pages == 0
            assert _drain_used(ep) == 0, "orphaned pages on the prefill side"
            assert _drain_used(ed) == 0, "orphaned pages on the decode side"
            assert co.migrations() == []
        finally:
            ed.stop()

    def test_deadline_expiry_mid_transfer_is_a_deadline_miss(self, jax):
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.utils.prometheus import default_registry

        clock = FakeClock()
        ep, ed, co, hook = self._gated_pair(jax, clock=clock)
        try:
            hook["fn"] = lambda: clock.advance(10.0)  # blow the deadline
            misses_before = default_registry.value(
                C.DEADLINE_MISSES_TOTAL, {"stage": "migrating"}
            )
            req = co.submit(
                PROMPT, SamplingParams(max_tokens=16, deadline_s=1.0)
            )
            assert "".join(co.stream(req)) == ""
            assert req.finish_reason == "deadline"
            assert default_registry.value(
                C.DEADLINE_MISSES_TOTAL, {"stage": "migrating"}
            ) == misses_before + 1
            assert ed.admission.reserved_pages == 0
            assert _drain_used(ep) == 0
            assert _drain_used(ed) == 0
        finally:
            ed.stop()

    def test_abort_of_adopted_queued_request_releases_reservation(self, jax):
        """After a successful migration the request queues on the decode
        policy like any other — abort-of-queued must release its
        reservation AND drop the adopted block without a slot ever being
        claimed (the decode engine never runs here)."""
        from modal_examples_tpu.serving import SamplingParams

        ep, ed, co = _pair(jax, seed=0)
        try:
            req = co.submit(PROMPT, SamplingParams(max_tokens=8))
            # migration done, request queued on the (never-started) decode
            # engine; abort before any scheduler tick
            assert ed.policy.total_depth() == 1
            co.abort(req)
            assert ed.policy.total_depth() == 0
            assert ed.admission.reserved_pages == 0
            assert req.out_queue.get(timeout=1).reason == "stop"
            assert _drain_used(ep) == 0
            assert _drain_used(ed) == 0
        finally:
            ed.stop()


class TestRolesAndRouting:
    def test_route_never_places_on_prefill_replicas(self, jax):
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )

        ep = _tiny_engine(jax, seed=0)
        ed = _tiny_engine(jax, seed=0)
        router = PrefixAffinityRouter(
            [
                EngineReplica(ep, "pre", role="prefill"),
                EngineReplica(ed, "dec", role="decode"),
            ]
        )
        for prompt in ("alpha", "beta", "gamma", PROMPT):
            assert router.route(prompt).name == "dec"
        pre, dec = router.plan(PROMPT)
        assert pre.name == "pre" and dec.name == "dec"

    def test_plan_with_no_prefillers_returns_none(self, jax):
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )

        ed = _tiny_engine(jax, seed=0)
        router = PrefixAffinityRouter([EngineReplica(ed, "u")])
        pre, dec = router.plan(PROMPT)
        assert pre is None and dec.name == "u"

    def test_prefill_only_fleet_is_rejected(self, jax):
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )

        ep = _tiny_engine(jax, seed=0)
        with pytest.raises(ValueError, match="decode-capable"):
            PrefixAffinityRouter([EngineReplica(ep, "p", role="prefill")])

    def test_bad_role_rejected(self, jax):
        from modal_examples_tpu.scheduling import EngineReplica

        with pytest.raises(ValueError, match="role"):
            EngineReplica(_tiny_engine(jax, seed=0), "x", role="turbo")

    def test_coordinator_rejects_mixed_cache_geometry(self, jax):
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving.disagg import DisaggCoordinator

        a = _tiny_engine(jax, seed=0)
        b = _tiny_engine(jax, seed=0, kv_dtype="int8")
        with pytest.raises(ValueError, match="kv_dtype"):
            DisaggCoordinator(
                [
                    EngineReplica(a, "a", role="prefill"),
                    EngineReplica(b, "b", role="decode"),
                ]
            )

    def test_serving_engines_excludes_prefill(self, jax):
        ep, ed, co = _pair(jax, seed=0)
        assert co.serving_engines() == [ed]
        ed.stop()

    def test_prefill_sync_refuses_running_engine(self, jax):
        from modal_examples_tpu.serving import SamplingParams

        eng = _tiny_engine(jax, seed=0)
        eng.start()
        try:
            req = eng.make_request("hello", SamplingParams(max_tokens=2))
            with pytest.raises(RuntimeError, match="scheduler loop"):
                eng.prefill_sync(req)
        finally:
            eng.stop()

    def test_replica_role_metric_emitted(self, jax):
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.utils.prometheus import default_registry

        ep, ed, co = _pair(jax, seed=0)
        assert default_registry.value(
            C.REPLICA_ROLE, {"replica": "pre-0", "role": "prefill"}
        ) == 1.0
        assert default_registry.value(
            C.REPLICA_ROLE, {"replica": "dec-0", "role": "decode"}
        ) == 1.0
        ed.stop()


class TestTieredCache:
    def test_spill_promote_and_volume_churn_survival(self, jax):
        """Evicted prefix pages spill host-ward; a tiny host budget demotes
        them to the Volume; a FRESH engine over the same Volume promotes
        yesterday's prefix — warm prefixes survive replica churn."""
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.storage.volume import Volume

        params = SamplingParams(max_tokens=4, temperature=0.0)
        with Volume.ephemeral() as vol:
            tiered = {"host_bytes": 2048, "volume": vol}
            e1 = _tiny_engine(jax, seed=0, kv_dtype="int8",
                              tiered_prefix=tiered)
            try:
                ref = e1.generate(PROMPT, params)
            finally:
                e1.stop()
            e1.prefix_cache.evict(10_000)
            st = e1.tiered.stats()
            assert st["spilled"] > 0
            assert st["volume"]["blocks"] > 0, (
                "tiny host budget must demote blocks to the volume tier"
            )
            # push the remaining host-resident blocks down too, so the
            # fresh replica's CONSECUTIVE promote walk starts at page 0
            for h, data in list(e1.tiered._host.items()):
                e1.tiered._demote_to_volume(h, data)
            # replica churn: a brand-new engine finds the volume blocks
            e2 = _tiny_engine(jax, seed=0, kv_dtype="int8",
                              tiered_prefix=tiered)
            try:
                out = e2.generate(PROMPT, params)
            finally:
                e2.stop()
            assert out == ref
            assert e2.tiered.stats()["hits"]["volume"] > 0

    def test_corrupt_tier_block_is_dropped_not_adopted(self, jax):
        from modal_examples_tpu.serving import SamplingParams

        params = SamplingParams(max_tokens=4, temperature=0.0)
        e = _tiny_engine(jax, seed=0, tiered_prefix={"host_bytes": 1 << 20})
        try:
            ref = e.generate(PROMPT, params)
            e.prefix_cache.evict(10_000)
            # corrupt every spilled block in place
            for h in list(e.tiered._host):
                e.tiered._host[h] = e.tiered._host[h][:-4] + b"\x00123"
            out = e.generate(PROMPT, params)  # promote fails -> recompute
            assert out == ref
            assert e.tiered.stats()["hits"]["host"] == 0
        finally:
            e.stop()

    def test_tier_gauges_emitted(self, jax):
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.utils.prometheus import default_registry

        e = _tiny_engine(jax, seed=0, tiered_prefix={"host_bytes": 1 << 20})
        try:
            e.generate(PROMPT, SamplingParams(max_tokens=2))
            e.prefix_cache.evict(10_000)
            assert default_registry.value(
                C.PREFIX_TIER_PAGES, {"tier": "host"}
            ) > 0
        finally:
            e.stop()


class TestRequestTracing:
    """ISSUE 9 acceptance: one disagg request = ONE distributed trace id
    whose merged span tree covers queue, placement, prefill, per-chunk
    transfer, adoption, and decode with correct parentage — across
    per-replica TraceStores — plus span closure under failure (mid-
    transfer death, abort-during-migration: no dangling spans)."""

    def _traced_pair(self, jax, tmp_path, coord_kw=None, prefill_kw=None):
        from modal_examples_tpu.observability.trace import TraceStore

        stores = {
            "pre": TraceStore(root=tmp_path / "pre"),
            "dec": TraceStore(root=tmp_path / "dec"),
            "gw": TraceStore(root=tmp_path / "gw"),
        }
        ep, ed, co = _pair(
            jax, "int8", seed=0,
            prefill_kw={"trace_store": stores["pre"], **(prefill_kw or {})},
            decode_kw={"trace_store": stores["dec"]},
            coord_kw={"trace_store": stores["gw"], **(coord_kw or {})},
        )
        return ep, ed, co, list(stores.values())

    def test_disagg_request_yields_one_stitched_trace(self, jax, tmp_path):
        from modal_examples_tpu.observability import reqtrace as rt
        from modal_examples_tpu.observability.export import (
            spans_to_chrome_trace,
        )
        from modal_examples_tpu.serving import SamplingParams

        params = SamplingParams(max_tokens=6, temperature=0.0)
        ep, ed, co, stores = self._traced_pair(
            jax, tmp_path,
            prefill_kw={"tiered_prefix": {"host_bytes": 1 << 20}},
        )
        try:
            seed_req = co.submit(PROMPT, params)  # warms the prefix trie
            "".join(co.stream(seed_req))
            # spill the prefill replica's trie so the NEXT request's claim
            # promotes from the host tier — the acceptance's tiered hit
            ep.prefix_cache.evict(10_000)
            assert ep.tiered.stats()["host"]["blocks"] > 0
            req = co.submit(PROMPT, params)
            "".join(co.stream(req))
            assert req.finish_reason in ("stop", "length")
            assert req.trace is not None and req.trace.open_spans() == []
        finally:
            ed.stop()

        spans = rt.read_trace(req.request_id, stores=stores)
        assert spans and {s["trace_id"] for s in spans} == {req.request_id}
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        assert {
            "request", "queue", "placement", "prefill", "migrate",
            "transfer", "chunk", "adopt", "decode", "tier_promote",
        } <= set(by), sorted(by)
        # every recorded span is CLOSED
        assert all(s["end"] is not None for s in spans)
        # parentage: queue/placement/migrate/decode under the root;
        # prefill + transfer + adopt under the migrate span; every chunk
        # under the transfer span
        root = by["request"][0]
        mig = by["migrate"][0]
        tr = by["transfer"][0]
        assert root["parent_id"] is None
        for name in ("queue", "placement", "migrate", "decode"):
            assert by[name][0]["parent_id"] == root["span_id"], name
        for name in ("prefill", "transfer", "adopt"):
            assert by[name][0]["parent_id"] == mig["span_id"], name
        assert all(c["parent_id"] == tr["span_id"] for c in by["chunk"])
        assert len(by["chunk"]) == -(-mig["attrs"]["wire_bytes"] // 512)
        # replica attribution: the spans landed in DIFFERENT stores yet
        # stitch — prefill on rep A, adopt/decode on rep B
        assert by["prefill"][0]["attrs"]["replica"] == "pre-0"
        assert by["adopt"][0]["attrs"]["replica"] == "dec-0"
        assert by["decode"][0]["attrs"]["replica"] == "dec-0"
        assert by["tier_promote"][0]["attrs"]["tier"] == "host"
        # the queue span's wait_s is ITS OWN residency, not the whole
        # migration (which is the migrate span's story)
        q = by["queue"][0]
        assert q["attrs"]["wait_s"] == pytest.approx(
            q["end"] - q["start"], abs=0.05
        )
        assert mig["attrs"]["result"] == "ok"
        assert mig["attrs"]["pages"] > 0
        assert root["attrs"]["finish_reason"] == req.finish_reason
        assert root["attrs"]["ttft_s"] > 0

        # `tpurun explain` renders the narrative from the merged stores
        lines = rt.explain_lines(spans, req.request_id)
        text = "\n".join(lines)
        assert "prefill on pre-0" in text
        assert "migrated" in text and "pre-0 -> dec-0" in text
        assert "decode on dec-0" in text and "TTFT" in text

        # the Perfetto export passes the existing schema check, with one
        # track per replica and the migration flow link
        doc = spans_to_chrome_trace(spans, req.request_id)
        assert doc["traceEvents"] and doc["displayTimeUnit"] in ("ms", "ns")
        for ev in doc["traceEvents"]:
            assert {"ph", "pid", "tid", "name"} <= set(ev), ev
            assert ev["ph"] in ("X", "i", "M", "s", "f"), ev
            if ev["ph"] == "X":
                assert ev["dur"] > 0 and ev["ts"] >= 0
        tracks = {
            ev["args"]["name"]
            for ev in doc["traceEvents"]
            if ev["ph"] == "M" and ev["name"] == "thread_name"
        }
        assert {"gateway", "pre-0", "dec-0"} <= tracks
        assert any(ev["ph"] == "s" for ev in doc["traceEvents"])

    def test_mid_transfer_death_closes_all_spans(self, jax, tmp_path):
        """Failure propagation: the channel dies mid-stream — unified
        fallback serves the request, and the trace closes every span
        (migrate/transfer marked error, no dangling chunk span)."""
        from modal_examples_tpu.observability import reqtrace as rt
        from modal_examples_tpu.serving import SamplingParams

        class DiesMidStream(LoopbackChannel):
            def __init__(self):
                super().__init__()
                self.n = 0

            def send(self, chunk):
                self.n += 1
                if self.n == 2:
                    raise ConnectionError("prefill replica died")
                super().send(chunk)

        ep, ed, co, stores = self._traced_pair(
            jax, tmp_path, coord_kw={"channel_factory": DiesMidStream}
        )
        try:
            req = co.submit(PROMPT, SamplingParams(max_tokens=6,
                                                   temperature=0.0))
            "".join(co.stream(req))
            # served = it produced tokens; whether a random-weight model's
            # ids decode to any text is luck
            assert req.generated_tokens
            assert req.finish_reason in ("stop", "length")
            assert co.migrations_fallback == 1
            assert req.trace is not None and req.trace.open_spans() == []
        finally:
            ed.stop()
        spans = rt.read_trace(req.request_id, stores=stores)
        assert all(s["end"] is not None for s in spans)
        by = {s["name"]: s for s in spans}
        assert by["migrate"]["attrs"]["result"] == "fallback"
        assert by["migrate"]["status"] == "error"
        assert by["transfer"]["status"] == "error"
        # the fallback re-prefill recorded on the DECODE replica, at root
        prefills = [s for s in spans if s["name"] == "prefill"]
        fallback = [p for p in prefills if p["attrs"]["replica"] == "dec-0"]
        assert fallback and fallback[0]["parent_id"] == by["request"]["span_id"]
        assert by["request"]["attrs"]["finish_reason"] == req.finish_reason

    def test_abort_mid_migration_closes_all_spans(self, jax, tmp_path):
        from modal_examples_tpu.observability import reqtrace as rt
        from modal_examples_tpu.serving import SamplingParams

        hook = {"fn": None}

        class Gated(LoopbackChannel):
            def __init__(self):
                super().__init__()
                self.n = 0

            def send(self, chunk):
                self.n += 1
                if self.n == 1 and hook["fn"] is not None:
                    hook["fn"]()
                super().send(chunk)

        ep, ed, co, stores = self._traced_pair(
            jax, tmp_path,
            coord_kw={"channel_factory": Gated, "chunk_bytes": 64},
        )
        try:
            hook["fn"] = lambda: co.migrations()[0].request.__setattr__(
                "aborted", True
            )
            req = co.submit(PROMPT, SamplingParams(max_tokens=16))
            assert "".join(co.stream(req)) == ""
            assert req.finish_reason == "stop"
            assert req.trace is not None and req.trace.open_spans() == []
        finally:
            ed.stop()
        spans = rt.read_trace(req.request_id, stores=stores)
        assert all(s["end"] is not None for s in spans)
        by = {s["name"]: s for s in spans}
        assert by["migrate"]["attrs"]["result"] == "aborted"
        assert by["request"]["attrs"]["finish_reason"] == "stop"
        assert "decode" not in by  # nothing ever decoded

    def test_wire_context_rides_the_mtkv1_envelope(self, jax):
        """The block meta carries {trace_id, parent_id} — what a
        cross-process decode replica reconstructs the context from."""
        from modal_examples_tpu.serving import SamplingParams

        eng = _tiny_engine(jax, seed=0)
        req = eng.make_request("hello wire", SamplingParams(max_tokens=2))
        req._trace_parent = "sp-migrate-x"
        state = eng.prefill_sync(req)
        block = eng.extract_request_pages(req, state)
        eng.release_claim(state["claim"])
        assert block.meta["trace"] == {
            "trace_id": req.request_id, "parent_id": "sp-migrate-x",
        }


class TestGatewaySnapshot:
    def test_disagg_snapshot_shape(self, jax):
        """The gateway /disagg payload renders from the live registry."""
        from modal_examples_tpu.web.gateway import _disagg_snapshot

        ep, ed, co = _pair(jax, seed=0)
        try:
            from modal_examples_tpu.serving import SamplingParams

            req = co.submit(PROMPT, SamplingParams(max_tokens=2))
            "".join(co.stream(req))
        finally:
            ed.stop()
        snap = _disagg_snapshot()
        assert snap["replicas"].get("pre-0") == "prefill"
        assert snap["migrations"]["pages"] > 0
        assert "tiers" in snap
