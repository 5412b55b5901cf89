"""OpenAI-compatible HTTP server over the LLM engine.

Mirrors the API surface the reference's north-star example serves and its
client exercises (vllm_inference.py:243-345: /health, /v1/models,
/v1/chat/completions with SSE streaming; openai_compatible/client.py).
Stdlib HTTP (fastapi/uvicorn are optional in this image); threads per
connection; the engine's continuous batching does the multiplexing.
"""

from __future__ import annotations

import json
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import math

from ..observability import catalog as _C
from ..observability import profiler as _profiler
from ..observability import reqtrace as _rt
from ..scheduling.admission import ShedError
from ..utils.prometheus import default_registry
from .engine import LLMEngine
from .sampling import SamplingParams


def _extract_images(messages: list) -> tuple[list, object]:
    """OpenAI multimodal content parts -> (text-flattened messages, image).

    Accepts ``content`` as a list of parts ({"type": "text"} /
    {"type": "image_url", "image_url": {"url": "data:image/..;base64,.."}}),
    the shape the reference serves via SGLang (sglang_vlm.py) and queries in
    chat_with_pdf_vision.py. Only data: URIs are accepted — this image has
    zero egress, and fetching remote URLs server-side is a SSRF hazard
    anyway. Single-image prompts only (v1 limit): a second image is a 400.
    """
    import base64
    import io

    image = None
    flat = []
    for m in messages:
        content = m.get("content")
        if not isinstance(content, list):
            flat.append(m)
            continue
        texts = []
        for part in content:
            ptype = part.get("type")
            if ptype == "text":
                texts.append(part.get("text", ""))
            elif ptype == "image_url":
                url = (part.get("image_url") or {}).get("url", "")
                if not url.startswith("data:"):
                    raise ValueError(
                        "only data: URIs are supported for image_url "
                        "(inline base64; this server does not fetch URLs)"
                    )
                if image is not None:
                    # silently answering about only the first image would
                    # return a confidently wrong result for "compare these"
                    raise ValueError(
                        "multiple images per request are not supported"
                    )
                b64 = url.split(",", 1)[1] if "," in url else ""
                raw = base64.b64decode(b64)
                try:
                    from PIL import Image

                    image = Image.open(io.BytesIO(raw))
                    image.load()
                except Exception as e:
                    raise ValueError(f"could not decode image: {e}") from e
            else:
                raise ValueError(f"unsupported content part type {ptype!r}")
        flat.append({**m, "content": "\n".join(t for t in texts if t)})
    return flat, image


def _params_from_body(body: dict, headers=None) -> SamplingParams:
    # per-request deadline: the x-mtpu-deadline-ms header wins over a
    # deadline_ms body field (headers let proxies inject budgets without
    # rewriting payloads)
    deadline_ms = body.get("deadline_ms")
    if headers is not None and headers.get("x-mtpu-deadline-ms"):
        deadline_ms = headers.get("x-mtpu-deadline-ms")
    return SamplingParams(
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        max_tokens=int(body.get("max_tokens", 128)),
        stop=tuple(
            [body["stop"]] if isinstance(body.get("stop"), str)
            else body.get("stop") or []
        ),
        seed=int(body["seed"]) if body.get("seed") is not None else None,
        deadline_s=(
            float(deadline_ms) / 1000.0 if deadline_ms is not None else None
        ),
    )


def _sched_kwargs(body: dict, headers) -> dict:
    """Scheduling identity for one request: priority class from the
    x-mtpu-priority header (or a "priority" body field), tenant from
    x-mtpu-tenant (or OpenAI's own "user" field — the natural tenant key)."""
    from ..scheduling.policy import validate_class

    priority = body.get("priority") or "default"
    tenant = body.get("user") or "default"
    if headers is not None:
        priority = headers.get("x-mtpu-priority") or priority
        tenant = headers.get("x-mtpu-tenant") or tenant
    return {
        "priority": validate_class(str(priority)),  # typo'd class -> 400
        "tenant": str(tenant),
    }


class _Handler(BaseHTTPRequestHandler):
    server_ref: "OpenAIServer"

    def log_message(self, fmt, *args):
        pass

    def _json(self, code: int, obj, extra_headers: dict | None = None) -> None:
        data = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("content-type", "application/json")
        self.send_header("content-length", str(len(data)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _shed_response(self, e: ShedError) -> None:
        """Admission rejected the request: 429 + Retry-After (the OpenAI
        rate_limit_error shape) — overload is a fast honest no, not an
        unbounded queue."""
        self._json(
            429,
            {"error": {
                "message": str(e),
                "type": "rate_limit_error",
                "code": e.reason,
            }},
            extra_headers={"retry-after": str(math.ceil(e.retry_after_s))},
        )

    def do_GET(self):
        srv = self.server_ref
        if self.path == "/health":
            self._json(200, {"status": "ok"})
        elif self.path == "/v1/models":
            self._json(
                200,
                {
                    "object": "list",
                    "data": [
                        {
                            "id": srv.model_name,
                            "object": "model",
                            "owned_by": "modal-examples-tpu",
                        }
                    ],
                },
            )
        elif self.path == "/metrics":
            eng = srv.engine
            s = eng.stats
            active = sum(1 for sl in eng.slots if not sl.free)
            pc = eng.prefix_cache
            # the process registry carries the engine's histogram/gauge series
            # (mtpu_tick_phase_seconds etc., recorded by the batch loop) —
            # without it a scraper could never see the latency distributions
            reg_text = default_registry.expose()
            reg_names = set(re.findall(r"^# TYPE (\S+)", reg_text, re.M))
            # metric names come from the central catalog (no stringly-typed
            # drift; tests/test_static.py enforces this package-wide); series
            # the registry already owns are skipped so names never duplicate
            occ = eng.cache.occupancy()
            hand_built = [
                (_C.GENERATED_TOKENS_TOTAL, f"{s.generated_tokens}"),
                (_C.PROMPT_TOKENS_TOTAL, f"{s.prompt_tokens}"),
                (_C.DECODE_STEPS_TOTAL, f"{s.steps}"),
                (_C.TOKENS_PER_SECOND, f"{s.tokens_per_second():.3f}"),
                (_C.ACTIVE_SLOTS, f"{active}"),
                (_C.WAITING_REQUESTS, f"{eng.policy.total_depth()}"),
                (_C.KV_PAGES_FREE, f"{occ['pages_free']}"),
                (_C.KV_PAGES_USED, f"{occ['pages_used']}"),
                (_C.KV_PAGE_OCCUPANCY, f"{occ['occupancy']:.4f}"),
                (_C.SCHEDULER_ERRORS_TOTAL, f"{eng.error_count}"),
            ]
            if eng.spec_gamma:
                hand_built += [
                    (_C.SPEC_PROPOSED_TOTAL, f"{s.spec_proposed}"),
                    (_C.SPEC_ACCEPTED_TOTAL, f"{s.spec_accepted}"),
                    (_C.SPEC_ACCEPTANCE_RATE, f"{s.acceptance_rate():.4f}"),
                ]
            if pc is not None:
                hand_built += [
                    (_C.PREFIX_CACHE_HITS_TOTAL, f"{pc.hits}"),
                    (_C.PREFIX_CACHE_MISSES_TOTAL, f"{pc.misses}"),
                    (_C.PREFIX_CACHED_PAGES, f"{pc.cached_pages}"),
                    (_C.PREFIX_CACHE_EVICTIONS_TOTAL, f"{pc.evictions}"),
                ]
            lines = [
                f"{name} {value}"
                for name, value in hand_built
                if name not in reg_names
            ]
            if _C.DECODE_IMPL not in reg_names:
                # the engine normally owns this gauge in the registry (with
                # tp + per-shard variant labels); hand-build only when this
                # process' registry never saw an engine init
                lines.append(
                    f'{_C.DECODE_IMPL}{{attention="'
                    f'{eng.impl_plan["attention"]}",scatter='
                    f'"{eng.impl_plan["scatter"]}",kv_dtype='
                    f'"{eng.impl_plan["kv_dtype"]}",tp='
                    f'"{eng.impl_plan.get("tp", 1)}",variant='
                    f'"{eng.impl_plan.get("ragged_variant") or "-"}",'
                    f'downgraded="{len(eng.impl_plan["downgraded"])}",'
                    f'allocator="{eng.impl_plan["allocator"]}",state_step='
                    f'"{eng.impl_plan.get("state_step") or "-"}",expert_scan='
                    f'"{eng.impl_plan.get("expert_scan") or "-"}"}} 1'
                )
            import jax

            for dev in jax.local_devices():
                stats = dev.memory_stats() or {}  # the CPU reports none
                for kind, key in (
                    ("in_use", "bytes_in_use"),
                    ("peak", "peak_bytes_in_use"),
                    ("limit", "bytes_limit"),
                ):
                    if key in stats:
                        lines.append(
                            f'{_C.DEVICE_MEMORY_BYTES}{{device="{dev.id}",'
                            f'kind="{kind}"}} {stats[key]}'
                        )
            body = ("\n".join(lines) + "\n" + reg_text).encode()
            self.send_response(200)
            self.send_header("content-type", "text/plain; version=0.0.4")
            self.send_header("content-length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._json(404, {"error": "not found"})

    def do_POST(self):
        length = int(self.headers.get("content-length") or 0)
        try:
            body = json.loads(self.rfile.read(length)) if length else {}
        except json.JSONDecodeError:
            self._json(400, {"error": "invalid JSON"})
            return
        if self.path == "/v1/chat/completions":
            self._completions(body, chat=True)
        elif self.path == "/v1/completions":
            self._completions(body, chat=False)
        else:
            self._json(404, {"error": "not found"})

    def _completions(self, body: dict, chat: bool) -> None:
        srv = self.server_ref
        image = None
        try:
            if chat:
                messages = body.get("messages") or []
                messages, image = _extract_images(messages)
                prompt = srv.engine.tokenizer.apply_chat_template(messages)
            else:
                prompt = body.get("prompt") or ""
            if image is not None and srv.engine.vision_cfg is None:
                raise ValueError(
                    "this model does not accept images (engine has no "
                    "vision tower)"
                )
            params = _params_from_body(body, self.headers)
            sched = _sched_kwargs(body, self.headers)
            srv.engine.validate_params(params)
        except ValueError as e:
            self._json(400, {"error": {
                "message": str(e), "type": "invalid_request_error",
            }})
            return
        stream = bool(body.get("stream", False))
        n = max(1, int(body.get("n", 1)))
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        created = int(time.time())
        kind = "chat.completion" if chat else "text_completion"

        if n > 1 and stream:
            # OpenAI supports streaming multiple choices interleaved; this
            # server intentionally does not (one slot per SSE connection) —
            # reject loudly rather than silently returning one choice
            self._json(400, {"error": {
                "message": "n > 1 with stream=true is not supported",
                "type": "invalid_request_error",
            }})
            return
        if n > 1:
            # OpenAI `n`: fan out engine requests, one choice each (the
            # engine's continuous batching runs them concurrently). A fixed
            # seed derives per-choice seeds (seed+i) — otherwise seeded
            # sampling depends only on (seed, position) and every choice
            # would be identical.
            import dataclasses as _dc

            pairs = []
            try:
                for i in range(n):
                    pairs.append(srv.submit(
                        prompt,
                        _dc.replace(params, seed=params.seed + i)
                        if params.seed is not None
                        else params,
                        image=image,
                        **sched,
                    ))
            except ShedError as e:
                # partial fan-out shed: cancel the admitted siblings (their
                # slots go back to the pool) and reject the whole call
                for r, eng in pairs:
                    srv.abort_request(r, eng)
                    for _ in srv.stream_request(r, eng):
                        pass
                self._shed_response(e)
                return
            reqs = [r for r, _eng in pairs]
            texts = ["".join(srv.stream_request(r, eng)) for r, eng in pairs]
            if any(r.finish_reason == "error" for r in reqs):
                self._json(500, {"error": {
                    "message": "engine error while processing the request",
                    "type": "server_error",
                }})
                return
            choices = []
            for i, text in enumerate(texts):
                content = (
                    {"message": {"role": "assistant", "content": text}}
                    if chat
                    else {"text": text}
                )
                choices.append({
                    "index": i, **content,
                    "finish_reason": reqs[i].finish_reason or "stop",
                })
            n_prompt = len(reqs[0].prompt_tokens or [])
            n_out = sum(
                len(srv.engine.tokenizer.encode(t, add_bos=False)) for t in texts
            )
            self._json(
                200,
                {
                    "id": rid, "object": kind, "created": created,
                    "model": srv.model_name, "choices": choices,
                    "usage": {
                        "prompt_tokens": n_prompt,
                        "completion_tokens": n_out,
                        "total_tokens": n_prompt + n_out,
                        # real OpenAI field: prompt tokens served from the
                        # prefix cache (engine page claim) instead of
                        # recomputed — n>1 rows share one prompt, like
                        # prompt_tokens above
                        "prompt_tokens_details": {
                            "cached_tokens": int(
                                getattr(reqs[0], "cached_prompt_tokens", 0)
                            ),
                        },
                    },
                },
            )
            return

        include_usage = bool(
            (body.get("stream_options") or {}).get("include_usage")
        )
        try:
            req, eng = srv.submit(prompt, params, image=image, **sched)
        except ShedError as e:
            self._shed_response(e)
            return
        if stream:
            self.send_response(200)
            self.send_header("content-type", "text/event-stream")
            self.send_header("cache-control", "no-cache")
            # the engine request id (== distributed trace id): curl it back
            # into `tpurun explain` / GET /traces/<id> to see the lifecycle
            self.send_header("x-mtpu-request-id", req.request_id)
            self.end_headers()
            def chunk_of(**fields) -> dict:
                chunk = {
                    "id": rid,
                    "object": kind + ".chunk",
                    "created": created,
                    "model": srv.model_name,
                    **fields,
                }
                if include_usage and "usage" not in chunk:
                    # OpenAI stream_options.include_usage contract: every
                    # content chunk carries "usage": null; only the final
                    # dedicated chunk carries the totals
                    chunk["usage"] = None
                return chunk

            def usage_chunk() -> dict:
                n_prompt = len(req.prompt_tokens or [])
                return chunk_of(choices=[], usage={
                    "prompt_tokens": n_prompt,
                    "completion_tokens": req.n_generated,
                    "total_tokens": n_prompt + req.n_generated,
                    # real OpenAI field: prefix-cache hits at page claim
                    "prompt_tokens_details": {
                        "cached_tokens": int(
                            getattr(req, "cached_prompt_tokens", 0)
                        ),
                    },
                })

            try:
                for piece in srv.stream_request(req, eng):
                    delta = (
                        {"delta": {"content": piece}} if chat else {"text": piece}
                    )
                    chunk = chunk_of(
                        choices=[{"index": 0, **delta, "finish_reason": None}]
                    )
                    self.wfile.write(f"data: {json.dumps(chunk)}\n\n".encode())
                    self.wfile.flush()
                if req.finish_reason == "error":
                    # headers already sent: surface an SSE error event (the
                    # OpenAI stream-error shape) rather than a fake 'stop'
                    err = {"error": {
                        "message": "engine error while processing the request",
                        "type": "server_error",
                    }}
                    self.wfile.write(f"data: {json.dumps(err)}\n\n".encode())
                else:
                    final = chunk_of(choices=[{
                        "index": 0,
                        **({"delta": {}} if chat else {"text": ""}),
                        "finish_reason": req.finish_reason or "stop",
                    }])
                    self.wfile.write(f"data: {json.dumps(final)}\n\n".encode())
                if include_usage:
                    # usage ships on the error path too: a client doing
                    # billing/accounting still learns what the partial
                    # generation consumed
                    self.wfile.write(
                        f"data: {json.dumps(usage_chunk())}\n\n".encode()
                    )
                self.wfile.write(b"data: [DONE]\n\n")
                self.wfile.flush()
            except BrokenPipeError:
                # client went away mid-stream: stop decoding for it so the
                # slot and its KV pages go back to the pool (vLLM aborts on
                # client disconnect the same way). Only drain when the
                # request is still live — a disconnect during the final
                # chunk/[DONE] writes arrives after the terminal marker was
                # already consumed, and draining then would block forever.
                if req.finish_reason is None:
                    srv.abort_request(req, eng)
                    for _ in srv.stream_request(req, eng):  # drain to _FINISH
                        pass
            return

        text = "".join(srv.stream_request(req, eng))
        if req.finish_reason == "error":
            # engine-side prefill/decode failure: a 5xx, not a fake success
            # with a non-OpenAI finish_reason
            self._json(500, {"error": {
                "message": "engine error while processing the request",
                "type": "server_error",
            }}, extra_headers={"x-mtpu-request-id": req.request_id})
            return
        n_prompt = len(req.prompt_tokens or [])
        n_out = len(srv.engine.tokenizer.encode(text, add_bos=False))
        content = (
            {"message": {"role": "assistant", "content": text}}
            if chat
            else {"text": text}
        )
        self._json(
            200,
            {
                "id": rid,
                "object": kind,
                "created": created,
                "model": srv.model_name,
                "choices": [{
                    "index": 0, **content,
                    "finish_reason": req.finish_reason or "stop",
                }],
                "usage": {
                    "prompt_tokens": n_prompt,
                    "completion_tokens": n_out,
                    "total_tokens": n_prompt + n_out,
                    # real OpenAI field: prefix-cache hits at page claim
                    "prompt_tokens_details": {
                        "cached_tokens": int(
                            getattr(req, "cached_prompt_tokens", 0)
                        ),
                    },
                },
            },
            extra_headers={"x-mtpu-request-id": req.request_id},
        )


class OpenAIServer:
    """HTTP front end; start() binds and serves in a background thread.

    Fronts either ONE engine (``engine=``, the per-process deployed shape)
    or N replicas behind a ``PrefixAffinityRouter`` (``router=``): with a
    router, every submit routes by shared-prefix affinity and streams from
    the replica that owns the request. ``self.engine`` stays the primary
    replica's engine (tokenizer, /metrics, validate_params — replicas serve
    one model, so any replica answers those)."""

    def __init__(self, engine: LLMEngine | None = None,
                 model_name: str = "mtpu-llm",
                 host: str = "0.0.0.0", port: int = 8000, *, router=None):
        if (engine is None) == (router is None):
            raise ValueError("pass exactly one of engine= or router=")
        self.router = router
        if engine is not None:
            self.engine = engine
        else:
            # primary = the first replica that can own a request end to end
            # (skips prefill-role replicas under a disagg coordinator)
            serving = [
                r for r in router.replicas
                if getattr(r, "serves_requests", True)
            ]
            self.engine = (serving or router.replicas)[0].engine
        self.model_name = model_name
        handler = type("BoundHandler", (_Handler,), {"server_ref": self})
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.host, self.port = self.httpd.server_address[:2]
        self._thread: threading.Thread | None = None
        self._canary = None

    def _maybe_start_canary(self) -> None:
        """Env-gated like the MTPU_TSDB sampler: exporting
        ``MTPU_CANARY_INTERVAL`` arms always-on golden-set probing for the
        fleet this server fronts, with zero further wiring
        (docs/observability.md#correctness-canary). Router fronts only —
        the prober walks ``router.replicas`` and down-weights via
        ``set_health_weight``."""
        import os

        from ..observability.canary import INTERVAL_ENV, CanaryProber

        if self.router is None or not os.environ.get(INTERVAL_ENV):
            return
        # a DisaggCoordinator front exposes the weight-bearing router
        # underneath it; a bare PrefixAffinityRouter is its own
        target = getattr(self.router, "router", self.router)
        self._canary = CanaryProber(target).start()

    def submit(self, prompt, params, image=None, **sched):
        """Place one request; returns (request, owning engine). Raises
        ShedError when the target engine's admission rejects it.

        The distributed request trace is minted HERE — the fleet entry
        point — and propagated down through router placement, queues, and
        (under a disagg coordinator) the page-migration wire; the trace id
        becomes the request id, echoed to the client as
        ``x-mtpu-request-id`` so ``tpurun explain <id>`` finds it."""
        trace = _rt.start_request_trace(entry="api")
        if self.router is not None:
            req = self.router.submit(
                prompt, params, image=image, trace=trace, **sched
            )
            return req, self.router.replica_for(req).engine
        return (
            self.engine.submit(prompt, params, image=image, trace=trace,
                               **sched),
            self.engine,
        )

    def stream_request(self, req, eng):
        """Stream one submitted request's text pieces. With a router
        front this rides the failover path (serving/failover.py): a
        replica dying mid-stream is checkpoint-resumed on a healthy peer
        and the SSE stream continues token-identically — already-emitted
        text is deduped at the seam, so the client sees zero errors and
        zero duplicated chars (docs/failover.md)."""
        if self.router is not None:
            return self.router.stream(req)
        return eng.stream(req)

    def abort_request(self, req, eng) -> None:
        """Abort wherever the request now lives — after a failover the
        owning replica may not be the one that first accepted it."""
        if self.router is not None:
            self.router.abort(req)
        else:
            eng.abort(req)

    def _engines(self):
        """Engines whose scheduler loop this server owns. A role-aware
        front (``DisaggCoordinator``) exposes ``serving_engines()`` so
        prefill-role replicas are NEVER started: their engines run the
        synchronous prefill path, and a scheduler loop racing it would
        donate the same cache buffers twice."""
        if self.router is not None:
            serving = getattr(self.router, "serving_engines", None)
            if serving is not None:
                return serving()
            return [r.engine for r in self.router.replicas]
        return [self.engine]

    def start(self) -> "OpenAIServer":
        with _profiler.boot_mark("server_start"):
            for eng in self._engines():
                eng.start()
            self._maybe_start_canary()
            self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
            self._thread.start()
        return self

    def serve_forever(self) -> None:
        for eng in self._engines():
            eng.start()
        self._maybe_start_canary()
        self.httpd.serve_forever()

    def stop(self) -> None:
        if self._canary is not None:
            self._canary.stop()
            self._canary = None
        self.httpd.shutdown()
        self.httpd.server_close()
        for eng in self._engines():
            eng.stop()
