"""Serving tests: allocator, sampling, continuous-batching engine, and the
OpenAI-compatible HTTP surface (health/models/completions/streaming — the
client contract from vllm_inference.py:243-345)."""

import json
import threading
import urllib.request

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def engine(jax):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    cfg = llama.LlamaConfig.tiny()
    eng = LLMEngine(
        cfg, max_slots=4, max_model_len=128, page_size=16,
        prefill_buckets=(32, 64), seed=0,
    )
    yield eng
    eng.stop()


class TestAllocator:
    def test_alloc_free_cycle(self):
        from modal_examples_tpu.serving import OutOfPages, PageAllocator

        a = PageAllocator(8)  # page 0 reserved -> 7 usable
        pages = a.alloc(7)
        assert 0 not in pages
        with pytest.raises(OutOfPages):
            a.alloc(1)
        a.free(pages)
        assert a.available == 7


class TestSampling:
    def test_greedy_at_zero_temperature(self, jax):
        import jax.numpy as jnp

        from modal_examples_tpu.serving import sample

        logits = jnp.array([[0.0, 5.0, 1.0], [3.0, 0.0, 1.0]])
        out = sample(
            logits, jax.random.PRNGKey(0),
            jnp.zeros(2), jnp.ones(2), jnp.zeros(2, jnp.int32),
        )
        assert out.tolist() == [1, 0]

    @pytest.mark.slow
    def test_top_k_masks_tail(self, jax):
        import jax.numpy as jnp

        from modal_examples_tpu.serving import sample

        logits = jnp.array([[10.0, 9.0, -10.0, -10.0]])
        outs = {
            int(
                sample(
                    logits, jax.random.PRNGKey(i),
                    jnp.ones(1), jnp.ones(1), jnp.full(1, 2, jnp.int32),
                )[0]
            )
            for i in range(50)
        }
        assert outs <= {0, 1}

    @pytest.mark.slow
    def test_top_p_keeps_nucleus(self, jax):
        import jax.numpy as jnp

        from modal_examples_tpu.serving import sample

        logits = jnp.array([[10.0, 1.0, 0.5, 0.1]])
        outs = {
            int(
                sample(
                    logits, jax.random.PRNGKey(i),
                    jnp.ones(1), jnp.full(1, 0.5), jnp.zeros(1, jnp.int32),
                )[0]
            )
            for i in range(50)
        }
        assert outs == {0}


class TestEngine:
    def test_generate_respects_max_tokens(self, engine):
        from modal_examples_tpu.serving import SamplingParams

        req = engine.submit("hello", SamplingParams(max_tokens=5, temperature=1.0))
        text = "".join(engine.stream(req))
        n = len(engine.tokenizer.encode(text, add_bos=False))
        # n == 0 is legitimate: EOS can be the first sampled token
        assert n <= 5 + 1
        assert req.finish_reason in ("length", "stop")

    def test_greedy_deterministic(self, engine):
        from modal_examples_tpu.serving import SamplingParams

        p = SamplingParams(max_tokens=8, temperature=0.0)
        a = engine.generate("determinism", p)
        b = engine.generate("determinism", p)
        assert a == b

    def test_continuous_batching_many_requests(self, engine):
        from modal_examples_tpu.serving import SamplingParams

        # 2x oversubscribed vs slots: exercises admission + completion reuse
        reqs = [
            engine.submit(f"req {i}", SamplingParams(max_tokens=4, temperature=1.0))
            for i in range(8)
        ]
        outs = ["".join(engine.stream(r)) for r in reqs]
        assert len(outs) == 8

    def test_stop_safe_len_withholds_partial_stop(self):
        # OpenAI/vLLM contract: never emit a prefix of a stop string before
        # the match can resolve (stop='END' arriving token-wise as E,N,D)
        from modal_examples_tpu.serving.engine import _stop_safe_len

        assert _stop_safe_len("hello EN", ("END",)) == len("hello ")
        assert _stop_safe_len("hello E", ("END",)) == len("hello ")
        assert _stop_safe_len("hello ENX", ("END",)) == len("hello ENX")
        assert _stop_safe_len("hello", ()) == 5
        # multiple stops: the longest pending hold wins
        assert _stop_safe_len("abc<|e", ("<|end|>", "STOP")) == 3
        # (complete matches never reach here: the caller truncates via
        # text.find before computing the safe length)

    def test_stop_string_never_leaks_into_stream(self, jax):
        # end-to-end: patch detokenization so generation deterministically
        # walks through a stop string char by char; the stream must not
        # contain any prefix of it
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            page_size=16, prefill_buckets=(32,), seed=0,
        )
        script = "abETCdef"  # stop 'ETC' arrives split across steps
        eng.tokenizer.decode = lambda toks: script[: len(toks)]
        try:
            req = eng.submit(
                "x", SamplingParams(max_tokens=16, temperature=1.0, stop=("ETC",))
            )
            pieces = list(eng.stream(req))
            assert "".join(pieces) == "ab"
            assert req.finish_reason == "stop"
            for p in pieces:
                assert "E" not in p and "T" not in p and "C" not in p
        finally:
            eng.stop()

    def test_finish_reason_length_on_max_tokens(self, engine):
        from modal_examples_tpu.serving import SamplingParams

        req = engine.submit("hi", SamplingParams(max_tokens=3, temperature=1.0))
        text = "".join(engine.stream(req))
        assert req.finish_reason in ("length", "stop")
        if req.finish_reason == "stop":
            # only legitimate if EOS actually fired before the cap
            n = len(engine.tokenizer.encode(text, add_bos=False))
            assert n < 3 + 1

    def test_stop_releases_inflight_callers(self, jax):
        """stop() must unblock stream()/generate() callers rather than
        leaving them waiting on a dead scheduler."""
        import threading

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(32,), seed=3,
        )
        eng.start()
        req = eng.submit("drain me", SamplingParams(max_tokens=10_000))
        got_out = threading.Event()

        def consume():
            for _ in eng.stream(req):
                pass
            got_out.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        import time

        time.sleep(0.5)  # let it start decoding
        eng.stop()
        assert got_out.wait(timeout=10), "stream() caller still blocked after stop()"

    def test_concurrent_client_threads(self, engine):
        """Many client threads submit/stream at once: the single scheduler
        thread must serve all without loss, duplication, or deadlock."""
        import threading

        from modal_examples_tpu.serving import SamplingParams

        engine.start()
        results: dict[int, str] = {}
        errors: list[BaseException] = []
        lock = threading.Lock()

        def client(i: int):
            try:
                out = engine.generate(
                    f"thread {i}", SamplingParams(max_tokens=3, temperature=1.0)
                )
                with lock:
                    results[i] = out
            except BaseException as e:
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert len(results) == 12

    def test_warmup_precompiles_all_shapes(self, jax):
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            prefill_buckets=(32,), seed=1,
        )
        try:
            t = eng.warmup()
            assert t > 0
            sizes = {
                b: fn._cache_size() for b, fn in eng._prefill_jits.items()
            }
            assert all(s >= 1 for s in sizes.values())
            decode_size = eng._block_jit._cache_size()
            assert decode_size >= 1
            # serving a request must NOT trigger new compiles
            eng.generate("warm", SamplingParams(max_tokens=2, temperature=0.0))
            assert eng._block_jit._cache_size() == decode_size
            assert all(
                fn._cache_size() == sizes[b]
                for b, fn in eng._prefill_jits.items()
            )
            # and warmup after start() is refused (donation race guard)
            with pytest.raises(RuntimeError, match="before start"):
                eng.warmup()
        finally:
            eng.stop()

    def test_abort_frees_slot(self, engine):
        from modal_examples_tpu.serving import SamplingParams

        req = engine.submit("abort me", SamplingParams(max_tokens=64, temperature=1.0))
        engine.start()
        engine.abort(req)
        out = "".join(engine.stream(req))  # must terminate promptly
        # all slots eventually free again
        import time

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(s.free for s in engine.slots):
                break
            time.sleep(0.05)
        assert all(s.free for s in engine.slots)

    @pytest.mark.parametrize("how, reason", [("abort", "stop"), ("deadline", "deadline")])
    def test_ends_between_harvests_leave_nothing_behind(self, engine, how, reason):
        """An abort, or a deadline that lapses, after some tokens were
        accepted and with a decode block in flight: the block's unharvested
        tokens are dropped at the next harvest, the stream ends with the
        honest reason, and the slot and its pages are free."""
        import time

        from modal_examples_tpu.faults.chaos import check_drained
        from modal_examples_tpu.serving import SamplingParams

        def poll(done, timeout=60.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline and not done():
                time.sleep(0.01)
            return done()

        req = engine.submit(
            "end me early", SamplingParams(max_tokens=96, temperature=0.0),
        )
        t = threading.Thread(target=lambda: list(engine.stream(req)))
        t.start()
        assert poll(lambda: len(req.generated_tokens) >= 4)
        if how == "abort":
            engine.abort(req)
        else:
            req.deadline = engine._clock() - 1.0
        t.join(timeout=120)
        assert not t.is_alive()
        assert req.finish_reason == reason
        assert len(req.generated_tokens) < 96
        # the marker is delivered at once, the slot is reaped at the next tick
        assert poll(lambda: check_drained({"eng": engine}) == [])

    def test_abort_queued_frees_reservation_and_depth(self, jax):
        """Regression (ISSUE 4 satellite): aborting a request that never
        reached a slot must free its reserved KV pages and decrement the
        queue-depth gauge immediately — without the scheduler thread ever
        running — and release the caller's stream."""
        from modal_examples_tpu.models import llama
        from modal_examples_tpu.observability import catalog as C
        from modal_examples_tpu.serving import LLMEngine, SamplingParams
        from modal_examples_tpu.utils.prometheus import default_registry

        eng = LLMEngine(
            llama.LlamaConfig.tiny(), max_slots=2, max_model_len=64,
            page_size=16, prefill_buckets=(32,), seed=9,
        )
        try:
            req = eng.submit("never scheduled", SamplingParams(max_tokens=16))
            assert eng.policy.total_depth() == 1
            assert eng.admission.reserved_pages > 0
            assert default_registry.value(C.KV_PAGES_RESERVED) > 0
            eng.abort(req)
            assert eng.policy.total_depth() == 0
            assert eng.admission.reserved_pages == 0
            assert default_registry.value(C.KV_PAGES_RESERVED) == 0
            assert default_registry.value(
                C.SCHED_QUEUE_DEPTH, {"class": "default"}
            ) == 0
            # the stream terminates promptly (marker already queued)
            item = req.out_queue.get(timeout=5)
            assert hasattr(item, "reason")
            # and the page pool is untouched: nothing was ever claimed
            assert eng.cache.occupancy()["pages_used"] == 0
        finally:
            eng.stop()

    def test_seeded_sampling_deterministic_across_batches(self, engine):
        """A seeded request must sample identically whether it runs alone or
        alongside other traffic (the OpenAI `seed` contract)."""
        from modal_examples_tpu.serving import SamplingParams

        p = SamplingParams(max_tokens=6, temperature=1.0, seed=1234)
        alone = engine.generate("seeded prompt", p)
        # now with concurrent unseeded traffic sharing the batch
        noise = [
            engine.submit(f"noise {i}", SamplingParams(max_tokens=6, temperature=1.0))
            for i in range(3)
        ]
        busy = engine.generate("seeded prompt", p)
        for r in noise:
            "".join(engine.stream(r))
        assert alone == busy

    def test_unseeded_sampling_timing_independent(self, jax):
        """Unseeded requests auto-derive a seed from (engine seed, submission
        index): outputs depend only on the submission sequence, never on
        scheduler timing (how many blocks/keys the engine burned in between).
        This is the deflake contract — the old engine-key path made every
        temperature>0 test order- and load-dependent."""
        import time

        from modal_examples_tpu.models import llama
        from modal_examples_tpu.serving import LLMEngine, SamplingParams

        cfg = llama.LlamaConfig.tiny()
        hot = SamplingParams(max_tokens=5, temperature=1.0)

        def run(churn):
            eng = LLMEngine(
                cfg, max_slots=2, max_model_len=64, page_size=16,
                prefill_buckets=(32,), seed=7,
            )
            outs = []
            for i in range(3):
                outs.append(eng.generate(f"prompt {i}", hot))
                if churn:
                    time.sleep(0.05)  # extra idle scheduler ticks
            eng.stop()
            return outs

        assert run(False) == run(True)

    def test_stats_accumulate(self, engine):
        assert engine.stats.generated_tokens > 0
        assert engine.stats.steps > 0


class TestOpenAIServer:
    @pytest.fixture(scope="class")
    def server(self, engine):
        from modal_examples_tpu.serving import OpenAIServer

        srv = OpenAIServer(engine, model_name="tiny-test", host="127.0.0.1", port=0)
        srv.start()
        yield srv
        srv.httpd.shutdown()

    def _url(self, server, path):
        return f"http://127.0.0.1:{server.port}{path}"

    def test_health_and_models(self, server):
        with urllib.request.urlopen(self._url(server, "/health")) as r:
            assert json.load(r)["status"] == "ok"
        with urllib.request.urlopen(self._url(server, "/v1/models")) as r:
            models = json.load(r)
        assert models["data"][0]["id"] == "tiny-test"

    def test_chat_completion(self, server):
        body = json.dumps(
            {
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4,
                "temperature": 0.0,
            }
        ).encode()
        req = urllib.request.Request(
            self._url(server, "/v1/chat/completions"),
            data=body,
            headers={"content-type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            out = json.load(r)
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"]["role"] == "assistant"
        assert out["usage"]["prompt_tokens"] > 0

    def test_streaming_sse(self, server):
        body = json.dumps(
            {
                "messages": [{"role": "user", "content": "stream"}],
                "max_tokens": 4,
                "stream": True,
            }
        ).encode()
        req = urllib.request.Request(
            self._url(server, "/v1/chat/completions"),
            data=body,
            headers={"content-type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            payload = r.read().decode()
        assert payload.strip().endswith("data: [DONE]")
        chunks = [
            json.loads(line[6:])
            for line in payload.splitlines()
            if line.startswith("data: ") and line != "data: [DONE]"
        ]
        assert chunks and chunks[0]["object"] == "chat.completion.chunk"

    def test_n_choices(self, server):
        body = json.dumps(
            {
                "messages": [{"role": "user", "content": "pick"}],
                "max_tokens": 3,
                "n": 3,
                "temperature": 1.0,
            }
        ).encode()
        req = urllib.request.Request(
            self._url(server, "/v1/chat/completions"),
            data=body,
            headers={"content-type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            out = json.load(r)
        assert [c["index"] for c in out["choices"]] == [0, 1, 2]
        assert all("content" in c["message"] for c in out["choices"])

    def test_metrics_endpoint(self, server):
        with urllib.request.urlopen(self._url(server, "/metrics")) as r:
            text = r.read().decode()
        assert "mtpu_generated_tokens_total" in text
        # the process registry's engine series (latency histograms) are part
        # of the exposition, and no metric name appears in both the
        # hand-built block and the registry block
        assert 'mtpu_tick_phase_seconds_bucket{phase="harvest"' in text
        names = [
            l.split("{")[0].split(" ")[0]
            for l in text.splitlines()
            if l and not l.startswith("#")
        ]
        gauges = [n for n in names if n == "mtpu_active_slots"]
        assert len(gauges) == 1, "duplicate series between blocks"
