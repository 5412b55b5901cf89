"""ISSUE 12 acceptance: in-flight request failover (docs/failover.md).

The exactness contract, pinned as a matrix: a stream resumed from a
:class:`~modal_examples_tpu.serving.failover.DecodeCheckpoint` — reactive
re-prefill of prompt+generated-prefix, or proactive live KV migration —
is **token-identical** to the uninterrupted run, greedy AND seeded, at
resume positions {first token, mid-stream, last token}, for bf16 AND int8
KV. Plus the failure-hygiene half: abort/deadline during an in-flight live
migration releases pages and reservations on BOTH replicas, and fleet
scale-in of a busy replica completes via migration in bounded time."""

import threading
import time

import pytest


PROMPT = "the quick brown fox jumps over the lazy dog and naps in the sun"


def _drain_queue(req, timeout=60.0) -> str:
    """Drain a request's out_queue to its terminal marker (the engine's
    ``stream()`` without an engine — for requests terminated outside any
    scheduler, e.g. an aborted migration)."""
    import queue as _q

    from modal_examples_tpu.serving.engine import _Finish

    out = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            item = req.out_queue.get(timeout=0.2)
        except _q.Empty:
            continue
        if isinstance(item, _Finish):
            req.finish_reason = item.reason
            return "".join(out)
        out.append(item)
    raise AssertionError("no terminal marker arrived")


def _mk_engine(kv_dtype="bfloat16", params=None, **kw):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (16, 32))
    return LLMEngine(
        llama.LlamaConfig.tiny(), seed=0, params=params,
        kv_dtype=kv_dtype, **kw,
    )


def _drained(eng) -> list:
    from modal_examples_tpu.faults.chaos import check_drained

    return check_drained({"eng": eng})


def _wait_tokens(req, n, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(req.generated_tokens) >= n:
            return True
        time.sleep(0.005)
    return False


class TestResumeDeterminism:
    """checkpoint -> resubmit -> byte-compare against the uninterrupted
    run: greedy + seeded, resume positions {first, mid, last}, bf16 +
    int8 KV, on the engine that made the reference and on a peer that rolls
    another number of steps into a dispatch (no resume position is a
    multiple of either block: a checkpoint holds harvested tokens only, and
    sampling is (seed, position)-keyed)."""

    @pytest.mark.parametrize("peer", ["same-engine", "decode_block-4"])
    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    @pytest.mark.parametrize("sampling", ["greedy", "seeded"])
    def test_resume_matrix(self, jax_cpu, kv_dtype, sampling, peer):
        from modal_examples_tpu.serving import SamplingParams

        sp = (
            SamplingParams(max_tokens=12, temperature=0.0)
            if sampling == "greedy"
            else SamplingParams(max_tokens=12, temperature=0.9, seed=7)
        )
        eng = _mk_engine(kv_dtype)
        to = eng if peer == "same-engine" else _mk_engine(
            kv_dtype, params=eng.params, decode_block=4,
        )
        try:
            ref = eng.submit(PROMPT, sp)
            ref_text = "".join(eng.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            n = ref.n_generated
            assert n == 12 and len(ref_tokens) == 12
            # {first token, mid-stream, last token}: k tokens were
            # accepted before the failure
            for k in (1, n // 2, n - 1):
                req = to.make_request(PROMPT, sp)
                req.auto_seed = ref.auto_seed  # rides the checkpoint
                to.submit_resumed(
                    req,
                    prompt_tokens=ref.prompt_tokens,
                    generated=ref_tokens[:k],
                    emitted_len=0,
                )
                out = "".join(to.stream(req))
                assert req.generated_tokens == ref_tokens, (
                    sampling, kv_dtype, k,
                )
                # emitted_len=0 re-emits from the start: the resumed
                # stream's text IS the full uninterrupted text, byte for
                # byte (tokens identical => detok identical)
                assert out == ref_text, (sampling, kv_dtype, k)
                assert req.finish_reason == ref.finish_reason
            assert _drained(eng) == [] and _drained(to) == []
        finally:
            eng.stop()
            if to is not eng:
                to.stop()

    def test_resume_emission_cursor_dedupes(self, jax_cpu):
        """The emitted-text cursor: a resume with emitted_len=E emits
        exactly ref_text[E:] — no duplicated chars, no gaps."""
        from modal_examples_tpu.serving import SamplingParams

        sp = SamplingParams(max_tokens=10, temperature=0.0)
        eng = _mk_engine()
        try:
            ref = eng.submit(PROMPT, sp)
            ref_text = "".join(eng.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            for cut in (0, 1, 3, len(ref_text)):
                req = eng.make_request(PROMPT, sp)
                req.auto_seed = ref.auto_seed
                eng.submit_resumed(
                    req,
                    prompt_tokens=ref.prompt_tokens,
                    generated=ref_tokens[:4],
                    emitted_len=cut,
                )
                out = "".join(eng.stream(req))
                assert out == ref_text[cut:], cut
        finally:
            eng.stop()

    def test_resume_past_the_end_finishes_without_a_slot(self, jax_cpu):
        """A checkpoint taken on the final token (max_tokens already
        reached) has nothing left to decode: the resumed stream delivers
        a terminal 'length' immediately — never an extra token."""
        from modal_examples_tpu.serving import SamplingParams

        sp = SamplingParams(max_tokens=8, temperature=0.0)
        eng = _mk_engine()
        try:
            ref = eng.submit(PROMPT, sp)
            ref_text = "".join(eng.stream(ref))
            req = eng.make_request(PROMPT, sp)
            req.auto_seed = ref.auto_seed
            eng.submit_resumed(
                req,
                prompt_tokens=ref.prompt_tokens,
                generated=list(ref.generated_tokens),
                emitted_len=len(ref_text),
            )
            out = "".join(eng.stream(req))
            assert out == ""
            assert req.finish_reason == "length"
            assert req.generated_tokens == ref.generated_tokens
            assert _drained(eng) == []
        finally:
            eng.stop()

    def test_checkpoint_from_request_is_original_prompt_based(self, jax_cpu):
        """A second checkpoint of an already-resumed request must not
        double-count the replayed prefix (the _orig_prompt_tokens rule)."""
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        sp = SamplingParams(max_tokens=10, temperature=0.0)
        eng = _mk_engine()
        try:
            ref = eng.submit(PROMPT, sp)
            ref_text = "".join(eng.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            req = eng.make_request(PROMPT, sp)
            req.auto_seed = ref.auto_seed
            eng.submit_resumed(
                req, prompt_tokens=ref.prompt_tokens,
                generated=ref_tokens[:3], emitted_len=0,
            )
            "".join(eng.stream(req))
            ckpt = fo.checkpoint_request(req)
            assert ckpt.prompt_tokens == list(ref.prompt_tokens)
            assert ckpt.generated == ref_tokens
            # a SECOND resume from that checkpoint still reproduces
            req.trace = None
            eng.submit_resumed(
                req, prompt_tokens=ckpt.prompt_tokens,
                generated=ckpt.generated[:6], emitted_len=0,
            )
            out = "".join(eng.stream(req))
            assert out == ref_text
            assert req.generated_tokens == ref_tokens
        finally:
            eng.stop()


class TestLiveMigration:
    """Proactive path: extract mid-decode on the victim's scheduler
    thread, ship via the chunked MTKV1 wire (decode-state leg), adopt on
    the target — the stream continues token-identically."""

    def _fleet(self, **eng_kw):
        from modal_examples_tpu.scheduling import EngineReplica

        eng_a = _mk_engine(**eng_kw)
        eng_b = _mk_engine(params=eng_a.params, **eng_kw)
        rep_a = EngineReplica(eng_a, "mig-a", role="unified")
        rep_b = EngineReplica(eng_b, "mig-b", role="unified")
        return eng_a, eng_b, rep_a, rep_b

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_migrate_mid_decode_token_identical(self, jax_cpu, kv_dtype):
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        sp = SamplingParams(max_tokens=48, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet(kv_dtype=kv_dtype)
        try:
            ref = eng_b.submit(PROMPT, sp)  # fault-free reference on B
            ref_text = "".join(eng_b.stream(ref))
            ref_tokens = list(ref.generated_tokens)

            req = rep_a.submit(PROMPT, sp)
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng_a.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 5)
            result = fo.migrate_request(
                rep_a, rep_b, req, chunk_bytes=512
            )
            assert result == "ok"
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.finish_reason == ref.finish_reason
            assert req.generated_tokens == ref_tokens
            assert "".join(pieces) == ref_text
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_migrate_queued_request_resubmits_fresh(self, jax_cpu):
        """A still-queued request has nothing to ship: migration drains
        its reservation on the victim and resubmits it fresh on the
        target — token-identical (nothing was emitted)."""
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        sp = SamplingParams(max_tokens=12, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet(max_slots=1)
        try:
            eng_a.start()
            ref = eng_b.submit(PROMPT, sp)
            ref_text = "".join(eng_b.stream(ref))
            blocker = rep_a.submit(
                "blocker " * 3, SamplingParams(max_tokens=48)
            )
            queued = rep_a.submit(PROMPT, sp)
            assert _wait_tokens(blocker, 1)
            result = fo.migrate_request(rep_a, rep_b, queued)
            assert result in ("resumed", "ok")
            out = "".join(eng_b.stream(queued))
            assert out == ref_text
            assert queued.generated_tokens == ref.generated_tokens
            "".join(eng_a.stream(blocker))
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_abort_during_migration_releases_both_sides(self, jax_cpu):
        """Client abort between transfer chunks: the target's admission
        reservation and the victim's pages both release; the stream
        terminates honestly with 'stop'."""
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo
        from modal_examples_tpu.serving.disagg.transport import (
            LoopbackChannel,
        )

        sp = SamplingParams(max_tokens=64, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet()
        try:
            eng_a.start()
            req = rep_a.submit(PROMPT, sp)
            assert _wait_tokens(req, 4)

            class AbortingChannel(LoopbackChannel):
                def send(self, chunk):
                    req.aborted = True  # client disconnects mid-transfer
                    super().send(chunk)

            result = fo.migrate_request(
                rep_a, rep_b, req, chunk_bytes=64,
                channel_factory=AbortingChannel,
            )
            assert result == "aborted"
            _drain_queue(req)
            assert req.finish_reason == "stop"
            assert eng_b.admission.reserved_pages == 0
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_deadline_during_migration_is_an_honest_deadline(self, jax_cpu):
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo

        from modal_examples_tpu.serving.disagg.transport import (
            LoopbackChannel,
        )

        sp = SamplingParams(max_tokens=64, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet()
        try:
            eng_a.start()
            req = rep_a.submit(PROMPT, sp)
            assert _wait_tokens(req, 2)

            class DeadlineChannel(LoopbackChannel):
                def send(self, chunk):
                    # the deadline lapses while chunks are on the wire
                    # (after extraction, so the victim's own deadline
                    # sweep cannot race this)
                    req.deadline = eng_b._clock() - 1.0
                    super().send(chunk)

            result = fo.migrate_request(
                rep_a, rep_b, req, chunk_bytes=64,
                channel_factory=DeadlineChannel,
            )
            assert result == "aborted"
            _drain_queue(req)
            assert req.finish_reason == "deadline"
            assert eng_b.admission.reserved_pages == 0
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_wire_failure_falls_back_to_reactive_resume(self, jax_cpu):
        """A transfer that cannot complete (dead channel) falls back to
        the checkpoint-only re-prefill resume — still token-identical,
        still zero client-visible errors."""
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo
        from modal_examples_tpu.serving.disagg.transport import (
            LoopbackChannel,
        )

        # generous max_tokens: after the gate releases, the reactive
        # fallback races the victim's resumed decode — if the request
        # FINISHES first, migrate_out honestly reports "gone". The long
        # tail keeps the request mid-decode through that window.
        sp = SamplingParams(max_tokens=128, temperature=0.0)
        eng_a, eng_b, rep_a, rep_b = self._fleet()
        try:
            ref = eng_b.submit(PROMPT, sp)
            ref_text = "".join(eng_b.stream(ref))

            req = rep_a.submit(PROMPT, sp)
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(eng_a.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 4)
            # park the victim's scheduler on a blocking control command
            # (the bench _measure_failover trick): without it, decode
            # races the migration to max_tokens under CI load and
            # migrate_out honestly reports "gone" — the gate guarantees
            # the migration lands mid-decode, deterministically
            import queue as _queue

            gate = threading.Event()
            eng_a._ctrl.append((gate.wait, _queue.Queue()))

            class BlackholeChannel(LoopbackChannel):
                def send(self, chunk):
                    pass  # every chunk vanishes; rounds exhaust

            box: dict = {}

            def migrate():
                box["result"] = fo.migrate_request(
                    rep_a, rep_b, req, chunk_bytes=512, max_rounds=2,
                    channel_factory=BlackholeChannel,
                )

            mt = threading.Thread(target=migrate)
            mt.start()
            # release the gate only once the migration's own control
            # command is queued behind it
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if eng_a._ctrl and eng_a._ctrl[-1][0] is not gate.wait:
                    break
                time.sleep(0.002)
            gate.set()
            mt.join(timeout=120)
            assert not mt.is_alive()
            assert box.get("result") == "resumed"
            t.join(timeout=120)
            assert not t.is_alive()
            assert req.finish_reason == ref.finish_reason
            assert "".join(pieces) == ref_text
            assert req.generated_tokens == ref.generated_tokens
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()


class TestReactiveStreamFailover:
    """Replica death mid-stream: the router-level stream resumes on a
    healthy peer from the request's own checkpoint — the consumer sees
    one uninterrupted, token-identical stream."""

    def test_router_stream_survives_scheduler_crash(self, jax_cpu):
        from modal_examples_tpu.faults.inject import FaultPlan, active
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import SamplingParams

        sp = SamplingParams(max_tokens=48, temperature=0.0)
        eng_a = _mk_engine()
        eng_b = _mk_engine(params=eng_a.params)
        rep_a = EngineReplica(eng_a, "re-a", role="unified")
        rep_b = EngineReplica(eng_b, "re-b", role="unified")
        router = PrefixAffinityRouter([rep_a, rep_b], reprobe_s=0.2)
        try:
            ref = eng_b.submit(PROMPT, sp)
            ref_text = "".join(eng_b.stream(ref))
            ref_tokens = list(ref.generated_tokens)
            eng_b.stop()  # fresh again for the takeover
            eng_b.revive() if eng_b._stopped_on_error else None

            req = rep_a.submit(PROMPT, sp)
            req._router_replica = rep_a
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(router.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 4)
            # only eng_a's loop is running -> the injected crash lands
            # deterministically on the request's owner
            plan = FaultPlan({"engine.scheduler_crash": {"on_hit": 1}})
            with active(plan):
                deadline = time.monotonic() + 30
                while not plan.fired() and time.monotonic() < deadline:
                    time.sleep(0.005)
            assert plan.fired().get("engine.scheduler_crash") == 1
            t.join(timeout=120)
            assert not t.is_alive()
            # zero client-visible errors: the stream finished normally,
            # token-identical, no duplicated or missing chars
            assert req.finish_reason == ref.finish_reason
            assert req.generated_tokens == ref_tokens
            assert "".join(pieces) == ref_text
            assert _drained(eng_a) == [] and _drained(eng_b) == []
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_failover_metrics_and_span_recorded(self, jax_cpu):
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.observability import reqtrace as rt
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving import failover as fo
        from modal_examples_tpu.utils.prometheus import default_registry

        sp = SamplingParams(max_tokens=16, temperature=0.0)
        eng_a = _mk_engine()
        eng_b = _mk_engine(params=eng_a.params)
        rep_b = EngineReplica(eng_b, "fm-b", role="unified")
        before = default_registry.total(C.FAILOVER_TOTAL)
        try:
            eng_a.start()
            req = eng_a.submit(PROMPT, sp)
            assert _wait_tokens(req, 3)
            # simulate death: engine A releases everything with "error"
            eng_a.stop()
            from modal_examples_tpu.serving.engine import _Finish

            req.finish_reason = None  # consumer has not drained yet
            assert fo.resume_request(req, rep_b, source="fm-a")
            drained = []
            while True:
                item = req.out_queue.get(timeout=60)
                if isinstance(item, _Finish):
                    req.finish_reason = item.reason
                    break
                drained.append(item)
            assert req.finish_reason in ("stop", "length")
            after = default_registry.total(C.FAILOVER_TOTAL)
            assert after >= before + 1
            # the failover span rides the SAME trace id past the dead
            # replica's terminal close
            if req.trace is not None:
                spans = rt.read_trace(req.request_id)
                names = {s["name"] for s in spans}
                assert "failover" in names
        finally:
            eng_a.stop()
            eng_b.stop()


class TestFleetDrainMigration:
    """Fleet scale-in of a BUSY replica completes via live migration in
    bounded time — one migration per request, not request completion —
    and fleet.jsonl records tokens_migrated (the forced-reap fix)."""

    def test_scale_in_busy_replica_migrates_then_reaps(
        self, jax_cpu, tmp_path
    ):
        import json

        from modal_examples_tpu.fleet.autoscaler import FleetAutoscaler
        from modal_examples_tpu.scheduling import (
            EngineReplica,
            PrefixAffinityRouter,
        )
        from modal_examples_tpu.serving import SamplingParams

        sp = SamplingParams(max_tokens=96, temperature=0.0)
        eng_a = _mk_engine(max_model_len=192)
        eng_b = _mk_engine(params=eng_a.params, max_model_len=192)
        rep_a = EngineReplica(eng_a, "seed-a", role="unified")
        rep_b = EngineReplica(eng_b, "owned-b", role="unified")
        router = PrefixAffinityRouter([rep_a])
        journal = tmp_path / "fleet.jsonl"
        scaler = FleetAutoscaler(
            router,
            factory=lambda name, role: (_ for _ in ()).throw(
                AssertionError("no builds in this test")
            ),
            journal_path=journal,
            drain_timeout_s=60.0,
        )
        try:
            ref = eng_a.submit(PROMPT, sp)
            ref_text = "".join(eng_a.stream(ref))
            ref_tokens = list(ref.generated_tokens)

            router.add_replica(rep_b)
            scaler._owned["decode"].append("owned-b")
            req = rep_b.submit(PROMPT, sp)
            req._router_replica = rep_b
            pieces: list[str] = []
            t = threading.Thread(
                target=lambda: pieces.extend(router.stream(req))
            )
            t.start()
            assert _wait_tokens(req, 5)
            n_before = len(req.generated_tokens)

            # scale-in picks the BUSY owned replica (migration makes it
            # drain-safe) and the next reap pass migrates its stream off
            act = scaler._scale_down("decode", {})
            assert act is not None and act["replica"] == "owned-b"
            assert all(r.name != "owned-b" for r in router.replicas)
            t0 = time.monotonic()
            deadline = time.monotonic() + 60
            while scaler._draining and time.monotonic() < deadline:
                scaler._reap_drained(scaler._clock())
                time.sleep(0.01)
            assert not scaler._draining, "victim did not drain"
            drain_s = time.monotonic() - t0

            t.join(timeout=120)
            assert not t.is_alive()
            # the stream survived scale-in, token-identical
            assert req.finish_reason == ref.finish_reason
            assert req.generated_tokens == ref_tokens
            assert "".join(pieces) == ref_text
            # bounded by the migration, not by request completion: the
            # victim was gone long before the 96-token stream finished
            assert drain_s < 30.0
            assert not eng_b._running  # reaped after the drain
            records = [
                json.loads(line)
                for line in journal.read_text().splitlines()
                if line.strip()
            ]
            drains = [
                r for r in records if r.get("action") == "drain_migrate"
            ]
            assert drains, records
            assert sum(r.get("tokens_migrated", 0) for r in drains) >= min(
                n_before, 5
            )
            # no forced reap killed the stream
            assert not any(
                r.get("trigger") == "drain_timeout" for r in records
            )
            assert _drained(eng_a) == []
        finally:
            scaler.stop(drain=False)
            eng_a.stop()
            eng_b.stop()


class TestWireEnvelopeCompat:
    """The decode-state leg is purely additive: plain PR-6 first-token
    blocks still decode and adopt; extended blocks round-trip."""

    def test_plain_block_still_adopts_first_token_lane(self, jax_cpu):
        from modal_examples_tpu.scheduling import EngineReplica
        from modal_examples_tpu.serving import SamplingParams
        from modal_examples_tpu.serving.disagg import DisaggCoordinator

        eng_p = _mk_engine()
        eng_d = _mk_engine(params=eng_p.params)
        coord = DisaggCoordinator(
            [
                EngineReplica(eng_p, "cp-pre", role="prefill"),
                EngineReplica(eng_d, "cp-dec", role="decode"),
            ],
            chunk_bytes=256,
        )
        try:
            ref = eng_d.submit(
                PROMPT, SamplingParams(max_tokens=8, temperature=0.0)
            )
            ref_text = "".join(eng_d.stream(ref))
            req = coord.submit(
                PROMPT, SamplingParams(max_tokens=8, temperature=0.0)
            )
            out = "".join(coord.stream(req))
            assert out == ref_text
        finally:
            eng_d.stop()

    def test_extended_block_roundtrips_resume_leg(self, jax_cpu):
        from modal_examples_tpu.serving.disagg.transport import (
            deserialize_block,
            extract_pages,
            serialize_block,
        )

        eng = _mk_engine()
        block = extract_pages(
            eng.cache, [1, 2],
            meta={
                "position": 17,
                "first_token": 42,
                "resume": {"generated": [1, 2, 3], "emitted_len": 5},
            },
        )
        out = deserialize_block(serialize_block(block))
        assert out.meta["resume"] == {
            "generated": [1, 2, 3], "emitted_len": 5,
        }
        assert out.meta["position"] == 17
