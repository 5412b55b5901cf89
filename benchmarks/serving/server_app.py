"""The serving container of a benchmark run: the program's normal path,
``App.run()`` -> ``@app.server`` class -> ``LLMEngine`` behind
``serving/openai_api.py``, with the benchmark's seeded weights.

It differs from examples/06_gpu_and_ml/llm-serving/llm_inference.py in what a
benchmark has to own: the configuration comes from a file of published sizes,
and what the engine is given for it, the seeded weights and the reference
that sees the same ones without taking anything the program made all come
from the file of the configuration's family (``families/<family>.py``); the
tokenizer is vocabulary-complete (``tokenizer.py``). Every engine setting
the configuration file does not name stays at the program's default.

Beside the OpenAI port the container opens a control port for what only the
process that owns the chip can do: start and stop the profiler and reduce
its trace, report the device and its peak memory, hand out the engine's own
timestamps of the requests it served, and, after the window, free the engine
and run the reference over a sample of what was served.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import modal_examples_tpu as mtpu  # noqa: E402

CONFIG_FILE = os.environ.get("BENCH_CONFIG_FILE", "")
SEED = int(os.environ.get("BENCH_SEED", "0"))
PORT = int(os.environ.get("BENCH_PORT", "8000"))
CTRL_PORT = int(os.environ.get("BENCH_CTRL_PORT", "8001"))
TPU = os.environ.get("BENCH_TPU", "v5e-1") or None  # "" = the CPU rehearsal
TRACE_DIR = os.environ.get("BENCH_TRACE_DIR", "")
# the one fault the tests inject under the timed path: every served token
# altered where it is produced. Never set by run.py.
BREAK = os.environ.get("BENCH_BREAK_TIMED_PATH", "")

app = mtpu.App("bench-serving")


class _Control(BaseHTTPRequestHandler):
    owner: "BenchServer"

    def log_message(self, fmt, *args):
        pass

    def _reply(self, obj, code: int = 200) -> None:
        data = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("content-type", "application/json")
        self.send_header("content-length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        try:
            if self.path == "/device":
                self._reply(self.owner.device())
            elif self.path == "/requests":
                self._reply(self.owner.request_log())
            else:
                self._reply({"error": "not found"}, 404)
        except Exception as e:  # the parent turns any 500 into a failed run
            import traceback

            traceback.print_exc()
            self._reply({"error": f"{type(e).__name__}: {e}"}, 500)

    def do_POST(self):
        length = int(self.headers.get("content-length") or 0)
        body = json.loads(self.rfile.read(length)) if length else {}
        try:
            if self.path == "/trace/start":
                self._reply(self.owner.trace_start())
            elif self.path == "/trace/stop":
                self._reply(self.owner.trace_stop(body))
            elif self.path == "/check":
                self._reply(self.owner.check(body))
            else:
                self._reply({"error": "not found"}, 404)
        except Exception as e:
            import traceback

            traceback.print_exc()
            self._reply({"error": f"{type(e).__name__}: {e}"}, 500)


@app.server(
    port=PORT,
    tpu=TPU,
    image=mtpu.Image.tpu_base(),
    startup_timeout=20 * 60,
    scaledown_window=15 * 60,
    target_concurrency=100,
    unauthenticated=True,
)
class BenchServer:
    @mtpu.enter()
    def start(self):
        import jax

        import manifest
        from tokenizer import IdTokenizer

        from modal_examples_tpu.models.quantize import QuantizedWeight
        from modal_examples_tpu.serving import OpenAIServer
        from modal_examples_tpu.serving.engine import LLMEngine

        self.config = json.loads(Path(CONFIG_FILE).read_text())
        self.family = manifest.load_family(self.config)
        self.dims = self.family.dims_of(self.config)
        cfg = self.family.program_config(CONFIG_FILE)
        tree = self.family.make_tree(SEED, self.dims)
        params = jax.tree.map(
            lambda leaf: QuantizedWeight(q=leaf["q"], scale=leaf["scale"])
            if isinstance(leaf, dict) else leaf,
            tree,
            is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "scale"},
        )
        del tree
        self.engine = LLMEngine(
            cfg, params, seed=SEED % (2**31 - 1), **self.config["engine"]
        )
        del params
        vocab = int(self.config["vocab_size"])
        self.engine.tokenizer = IdTokenizer(vocab)
        self.server = OpenAIServer(
            self.engine, model_name=self.config["name"], port=PORT
        )
        # the engine's own view of every request, for the per-layer metrics
        # that compare it with the client's (spans recorded from the
        # benchmark's side of the call into the layer)
        self.requests: list = []
        submit = self.server.submit

        def logged_submit(prompt, params, image=None, **sched):
            req, eng = submit(prompt, params, image=image, **sched)
            self.requests.append(req)
            return req, eng

        self.server.submit = logged_submit
        if BREAK == "alter-token":
            accept = self.engine._accept_token
            self.engine._accept_token = lambda slot, token: accept(
                slot, 3 + (int(token) + 7) % (vocab - 3)
            )
        self.static = {
            "impl_plan": {k: str(v) for k, v in self.engine.impl_plan.items()},
            "kv_pages": int(self.engine.cache.allocator.n_pages),
            "prefill_buckets": list(self.engine.prefill_buckets),
            "decode_block": int(self.engine.decode_block),
        }
        _Control.owner = self
        self.control = ThreadingHTTPServer(("127.0.0.1", CTRL_PORT), _Control)
        threading.Thread(target=self.control.serve_forever, daemon=True).start()
        self.server.start()  # the replica is advertised once the port accepts

    @mtpu.exit()
    def shutdown(self):
        self.control.shutdown()
        self.control.server_close()
        if self.engine is not None:
            self.server.stop()

    # -- control ------------------------------------------------------------

    def device(self) -> dict:
        import jax

        devs = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devs]
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
            "memory_limit_bytes": max(s.get("bytes_limit", 0) for s in stats),
            **self.static,
        }

    def request_log(self) -> list[dict]:
        return [
            {
                "id": r.request_id, "created": r.created,
                "first_token_at": r.first_token_at,
                "last_token_at": r.last_token_at,
                "n_generated": r.n_generated,
                "n_prompt": len(r.prompt_tokens or []),
                "cached_prompt_tokens": int(r.cached_prompt_tokens),
                "finish_reason": r.finish_reason,
            }
            for r in list(self.requests)
        ]

    def trace_start(self) -> dict:
        import jax

        # the reduction reads the device planes only. The profiler's Python
        # tracer, on by default, hooks every Python call of the process: in a
        # decode-bound cell (128 tokens a tick through the detokenizer and
        # the streams) it held the scheduler 0.6 s a tick and the traced
        # device idled 73% where the untraced run's idles ~13% (PERF.md
        # section 6). The host's TraceMe events (the program's
        # ``mtpu.tick/<phase>`` and ``mtpu.dispatch/<program>``) stay.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        return {"ok": True}

    def trace_stop(self, body: dict) -> dict:
        import jax

        import trace_reduce

        jax.profiler.stop_trace()
        return trace_reduce.reduce_dir(TRACE_DIR, float(body.get("sample_seconds", 0)))

    def check(self, body: dict) -> dict:
        """Free the engine, then run the reference over the samples: the
        reference gets the device to itself and the engine's peak stays the
        program's. The server answers nothing after this."""
        import gc
        import time

        import jax

        import reference

        t0 = time.monotonic()
        self.server.stop()
        self.engine = None
        gc.collect()
        # weights and cache, whatever their leaves are called: every device
        # array the process still holds
        for array in jax.live_arrays():
            array.delete()
        out = reference.served_gaps(
            self.family, SEED, self.dims, body["samples"], control=bool(body.get("control")),
            detail=bool(body.get("detail")),
        )
        out["check_s"] = time.monotonic() - t0
        return out
