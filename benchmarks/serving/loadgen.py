"""The load generator: one process, a thread per request in flight, payloads
built before the window. It never imports JAX.

Open loop: a dispatcher sleeps until each request is due and starts its
thread; a request is scored from the time it was *due*, so a stall taxes the
requests behind it, and how late the generator itself ran is reported.
Closed loop: each client sends its next request when the last completes.
All times are ``time.monotonic()``, which on Linux is one clock for every
process of the host, so they compare with the engine's own timestamps.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time
from urllib.parse import urlparse

from tokenizer import WORD, ids_of, text_of
from traffic import RequestSpec

REQUEST_TIMEOUT_S = 180.0


@dataclasses.dataclass
class Outcome:
    spec: RequestSpec
    due_t: float | None = None
    sent_t: float | None = None
    first_t: float | None = None
    last_t: float | None = None
    done_t: float | None = None
    pieces: list = dataclasses.field(default_factory=list)  # (time, n_tokens)
    text: str = ""
    status: int | None = None
    finish: str | None = None
    prompt_tokens: int | None = None
    completion_tokens: int | None = None
    cached_tokens: int | None = None
    request_id: str | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return (
            self.status == 200 and self.error is None
            and self.finish in ("length", "stop")
        )

    @property
    def n_out(self) -> int:
        return sum(n for _t, n in self.pieces)

    @property
    def ended_early(self) -> bool:
        return self.ok and self.n_out < self.spec.max_tokens

    def served_ids(self) -> list[int]:
        return ids_of(self.text)


def payload(spec: RequestSpec) -> bytes:
    ids = spec.prompt_ids
    messages = []
    if spec.system_len:
        messages.append({"role": "system", "content": text_of(ids[: spec.system_len])})
    messages.append({"role": "user", "content": text_of(ids[spec.system_len:])})
    return json.dumps({
        "messages": messages, "max_tokens": spec.max_tokens,
        "temperature": spec.temperature, "stream": True,
        "stream_options": {"include_usage": True},
    }).encode()


def send(url: str, body: bytes, out: Outcome) -> None:
    """One streamed /v1/chat/completions request; fills ``out``."""
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=REQUEST_TIMEOUT_S)
    try:
        out.sent_t = time.monotonic()
        conn.request("POST", "/v1/chat/completions", body=body,
                     headers={"content-type": "application/json"})
        resp = conn.getresponse()
        out.status = resp.status
        out.request_id = resp.getheader("x-mtpu-request-id")
        if resp.status != 200:
            out.error = resp.read()[-300:].decode(errors="replace")
            return
        for raw in resp:
            if not raw.startswith(b"data: ") or raw.startswith(b"data: [DONE]"):
                continue
            now = time.monotonic()
            chunk = json.loads(raw[6:])
            if "error" in chunk:
                out.error = chunk["error"].get("message", "error")
                continue
            for choice in chunk.get("choices") or []:
                piece = (choice.get("delta") or {}).get("content") or ""
                if piece:
                    if out.first_t is None:
                        out.first_t = now
                    out.last_t = now
                    out.pieces.append((now, len(piece) // WORD))
                    out.text += piece
                out.finish = choice.get("finish_reason") or out.finish
            usage = chunk.get("usage")
            if usage:
                out.prompt_tokens = usage["prompt_tokens"]
                out.completion_tokens = usage["completion_tokens"]
                out.cached_tokens = (
                    usage.get("prompt_tokens_details") or {}
                ).get("cached_tokens")
    except Exception as e:  # a failed request is a result, not a crash
        out.error = f"{type(e).__name__}: {e}"
    finally:
        out.done_t = time.monotonic()
        conn.close()


def run_open(url: str, specs: list[RequestSpec], t_open: float) -> list[Outcome]:
    """Send each request at ``t_open + due_s``; returns when all are done."""
    specs = sorted(specs, key=lambda s: s.due_s)
    outcomes = [Outcome(spec=s, due_t=t_open + s.due_s) for s in specs]
    bodies = [payload(s) for s in specs]
    threads = [
        threading.Thread(target=send, args=(url, b, o), daemon=True)
        for b, o in zip(bodies, outcomes)
    ]
    for thread, out in zip(threads, outcomes):
        delay = out.due_t - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        thread.start()
    for thread in threads:
        thread.join(REQUEST_TIMEOUT_S + 30)
    return outcomes


def run_closed(url: str, sessions: list[list[RequestSpec]], starts: list[float],
               t_ramp: float, t_close: float) -> list[Outcome]:
    """``len(starts)`` clients, client c starting ``starts[c]`` seconds after
    ``t_ramp``; each takes the next unasked session, asks its turns one after
    the other, and stops taking new requests at ``t_close``."""
    bodies = [[payload(s) for s in sess] for sess in sessions]
    outcomes: list[Outcome] = []
    lock = threading.Lock()
    cursor = [0]

    def client(start_after: float) -> None:
        delay = t_ramp + start_after - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while True:
            with lock:
                index = cursor[0]
                cursor[0] += 1
            if index >= len(sessions):
                return
            for spec, body in zip(sessions[index], bodies[index]):
                if time.monotonic() >= t_close:
                    return
                out = Outcome(spec=spec)
                with lock:
                    outcomes.append(out)
                send(url, body, out)
                if not out.ok:
                    break  # the rest of the session has lost its prefix

    threads = [threading.Thread(target=client, args=(s,), daemon=True) for s in starts]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    return outcomes
