#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py             # on a machine with one TPU chip
    python chip_smoke.py --chips 4   # a four-chip host: the TP=4 server leg

Drives the README's serving path once at the full width of one supported
model — ``MTPU_MODEL=mistral-7b MTPU_QUANT=int8`` (Mistral-7B-v0.1's published
32 layers / 4096 / 32 heads / 8 KV heads / head_dim 128 / FFN 14336, seeded
random weights), the example's 8 slots x 1024 context — through the entry
points a user calls: ``App.run()`` -> ``LLMServer.serve()`` from
examples/06_gpu_and_ml/llm-serving/llm_inference.py, then HTTP.

This process never touches a JAX backend. It runs the legs below one after
another, each in a process group of its own, and checks that a leg's
processes are gone before the next one attaches — a chip belongs to one
process at a time:

1. ``device``   JAX finds a TPU, or the run fails here, before any compile.
2. ``kernels``  every ``pl.pallas_call`` in ops/ compiled by Mosaic
                (``interpret=False`` asserted) and checked against its XLA
                reference: the ops/probes.py registry plus the ragged /
                scatter / flash cases at the smoke model's own geometry.
3. ``server-default``  what ``tpurun serve`` gives a user today (Pallas
                flash prefill, the decode attention the plan picks: the
                ragged kernel on the chip): /health, /v1/models and
                13 /v1/chat/completions requests — sequential, concurrent,
                streamed, over three prompt lengths.
4. ``server-pallas``   the same with ``paged_impl=pallas scatter_impl=pallas``
                (what bench.py passes for every config): ``impl_plan`` must
                read ragged / flat / pallas with nothing downgraded.
                Once with bf16 KV and, while the time limit allows, once
                with int8 KV.

A server leg passes when every request returned 200 and ended by length or
stop, the engine's ``mtpu_scheduler_errors_total`` is 0, ``mtpu_decode_impl``
shows the expected plan with ``downgraded="0"``, and its container is gone.
Any failed leg, a leg past its limit, or a leg that exits 0 without
reporting ``ok`` makes the run exit non-zero with no result line.

The last line of stdout on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

``--rehearse-cpu`` runs the same legs at ``tiny`` size on the CPU backend
(Pallas in interpret mode) to rehearse the plumbing — legs, HTTP client,
pass conditions — without a chip. Its result line says so; it is never a
chip result, no environment variable implies it, and the plain invocation
never takes it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
EXAMPLE = HERE / "examples/06_gpu_and_ml/llm-serving/llm_inference.py"
LOG_DIR = HERE / "chiprun_out" / "chip_smoke"

#: the whole run, compilation included (the driver allows 1200 s)
TOTAL_BUDGET_S = 1140.0
#: what an extra server leg needs; the int8-KV leg is skipped below this
OPTIONAL_LEG_NEEDS_S = 330.0

#: the example's own sizes for each model it serves (llm_inference.py)
SMOKE_MODEL = dict(model="mistral-7b", quant="int8", slots=8, max_len=1024)
REHEARSAL_MODEL = dict(model="tiny", quant="", slots=4, max_len=128)
PAGE_SIZE = 16  # LLMEngine's default, which the example does not override
PREFILL_BUCKETS = (128, 256, 512, 1024, 2048)  # likewise
ERRORS = "mtpu_scheduler_errors_total"


class LegFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# the parent: never imports JAX
# ---------------------------------------------------------------------------


def _processes() -> dict[int, tuple[int, int, str]]:
    """Live (not zombie) processes: pid -> (ppid, pgid, command line)."""
    out = {}
    for stat in Path("/proc").glob("[0-9]*/stat"):
        try:
            state, ppid, pgid = stat.read_text().rsplit(")", 1)[1].split()[:3]
            cmd = (stat.parent / "cmdline").read_bytes().replace(b"\0", b" ")
        except (OSError, ValueError):
            continue  # it exited while we were looking
        if state != "Z":
            out[int(stat.parent.name)] = (
                int(ppid), int(pgid), cmd.decode(errors="replace").strip()
            )
    return out


def _group_members(pgid: int) -> dict[int, str]:
    return {pid: cmd for pid, (_pp, pg, cmd) in _processes().items() if pg == pgid}


def _descendants(root: int) -> dict[int, str]:
    procs, found, frontier = _processes(), {}, {root}
    while frontier:
        frontier = {p for p, (pp, _pg, _c) in procs.items() if pp in frontier} - set(found)
        found.update({p: procs[p][2] for p in frontier})
    return found


def run_leg(name: str, argv: list[str], env: dict, limit_s: float) -> dict:
    """Run one leg as a process group; returns its result object. Raises
    :class:`LegFailed` unless it exited 0, inside its limit, reporting ok,
    and left no process behind."""
    LOG_DIR.mkdir(parents=True, exist_ok=True)
    err_path = LOG_DIR / f"{name}.stderr.log"
    print(f"=== leg {name} (limit {limit_s:.0f}s) ===", flush=True)
    t0 = time.monotonic()
    with open(err_path, "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, str(HERE / "chip_smoke.py"), "--leg", name, *argv],
            stdout=subprocess.PIPE, stderr=err, text=True, env=env,
            cwd=str(HERE), start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=limit_s)
            timed_out = False
        except subprocess.TimeoutExpired:
            timed_out = True
            out = ""
        leftovers = _group_members(proc.pid)
        if leftovers:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        if timed_out:
            out, _ = proc.communicate()
        deadline = time.monotonic() + 30
        while _group_members(proc.pid) and time.monotonic() < deadline:
            time.sleep(0.2)
    result = None
    for line in out.splitlines():
        print(f"[{name}] {line}", flush=True)
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                pass
    problem = None
    if timed_out:
        problem = f"ran past its {limit_s:.0f}s limit and was killed"
    elif proc.returncode != 0:
        problem = f"exited with code {proc.returncode}"
    elif not (isinstance(result, dict) and result.get("ok") is True):
        problem = "exited 0 without reporting ok"
    elif leftovers:
        problem = f"left processes behind after it exited: {leftovers}"
    elif _group_members(proc.pid):
        problem = "its processes could not be stopped"
    if problem:
        tail = err_path.read_bytes()[-6000:].decode(errors="replace")
        sys.stderr.write(f"--- {name}: last stderr ---\n{tail}\n")
        raise LegFailed(f"leg {name} {problem}")
    print(f"=== leg {name} passed in {time.monotonic() - t0:.1f}s ===", flush=True)
    return result


def parent(args) -> int:
    if not (HERE / "modal_examples_tpu").is_dir() or not EXAMPLE.is_file():
        sys.stderr.write(
            "chip_smoke.py must run from the root of the repository it "
            "tests (modal_examples_tpu/ and examples/ not found)\n"
        )
        return 2
    sys.path.insert(0, str(HERE))
    from modal_examples_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    print(f"compile cache directory: {cache_dir}", flush=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(HERE)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    rehearse = ["--rehearse-cpu"] if args.rehearse_cpu else []
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    deadline = time.monotonic() + TOTAL_BUDGET_S

    def left() -> float:
        return deadline - time.monotonic()

    pallas = ["--paged-impl", "pallas", "--scatter-impl", "pallas"]
    if args.chips > 1:
        legs = [
            ("device", ["--chips", str(args.chips)], 120, False),
            ("server-tp", ["--tp", str(args.chips)], 900, False),
        ]
    else:
        legs = [
            ("device", [], 120, False),
            ("kernels", [], 420, False),
            ("server-default", [], 480, False),
            ("server-pallas", pallas, 480, False),
            ("server-pallas-int8kv", pallas + ["--kv-dtype", "int8"], 480, True),
        ]
    device = None
    try:
        for name, argv, limit, optional in legs:
            if optional and left() < OPTIONAL_LEG_NEEDS_S:
                print(
                    f"=== leg {name} skipped: {left():.0f}s left of the time "
                    "limit ===", flush=True,
                )
                continue
            result = run_leg(
                name, argv + rehearse, env, max(10.0, min(limit, left()))
            )
            device = device or result.get("device")
    except LegFailed as e:
        sys.stderr.write(f"chip_smoke: FAILED: {e}\n")
        return 1
    if "jax" in sys.modules:
        sys.stderr.write("chip_smoke: FAILED: the parent imported JAX\n")
        return 1
    final = {"ok": True, "device": device}
    if args.rehearse_cpu:
        final["rehearsal"] = "cpu backend, tiny model: not a chip result"
    print(json.dumps(final), flush=True)
    return 0


# ---------------------------------------------------------------------------
# legs 1 and 2: in-process, this process owns the chip
# ---------------------------------------------------------------------------


def _require_backend(rehearse: bool):
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        raise LegFailed(
            f"JAX found no TPU: platform={dev.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    return jax


def leg_device(args) -> dict:
    from importlib import metadata

    jax = _require_backend(args.rehearse_cpu)
    import jaxlib

    devs = jax.devices()
    if args.chips > 1 and len(devs) != args.chips:
        raise LegFailed(f"--chips {args.chips} but JAX reports {len(devs)} devices")
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir != os.environ["JAX_COMPILATION_CACHE_DIR"]:
        raise LegFailed(
            f"JAX caches in {cache_dir!r}, the environment says "
            f"{os.environ['JAX_COMPILATION_CACHE_DIR']!r}"
        )
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(
        f"platform: {devs[0].platform}  device_kind: {devs[0].device_kind}  "
        f"devices: {len(devs)}"
    )
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu {libtpu}")
    print(f"compile cache directory: {cache_dir}")
    return {
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
    }


def leg_kernels(args) -> dict:
    jax = _require_backend(args.rehearse_cpu)
    from jax.experimental import pallas as pl

    from modal_examples_tpu.ops.probes import (
        CELL_FLASH_PROBES,
        KERNEL_PROBES,
        model_geometry_probes,
    )
    from modal_examples_tpu.serving.engine import MODEL_PRESETS
    from modal_examples_tpu.serving.kv_cache import PagedKVCache

    on_tpu = jax.default_backend() == "tpu"
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count_cache_event(event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    mosaic_calls = 0
    real_pallas_call = pl.pallas_call

    def checked_pallas_call(*a, **kw):
        nonlocal mosaic_calls
        if on_tpu and kw.get("interpret") is not False:
            raise AssertionError(
                f"pallas_call(interpret={kw.get('interpret')!r}) on a TPU: "
                "the kernel would run in the interpreter, not through Mosaic"
            )
        mosaic_calls += 1
        return real_pallas_call(*a, **kw)

    pl.pallas_call = checked_pallas_call

    size = REHEARSAL_MODEL if args.rehearse_cpu else SMOKE_MODEL
    cfg = MODEL_PRESETS[size["model"]]()
    probes = dict(KERNEL_PROBES)
    if args.rehearse_cpu:  # the interpreter is slow: a flat and a DMA kernel
        probes = {k: probes[k] for k in ("ragged_decode", "scatter_kv")}
    else:
        probes.update(CELL_FLASH_PROBES)
    probes.update(model_geometry_probes(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_layers=cfg.n_layers, page_size=PAGE_SIZE,
        pages_per_seq=size["max_len"] // PAGE_SIZE, slots=size["slots"],
        prefill_batch=min(4, size["slots"]),
        prefill_bucket=max(b for b in PREFILL_BUCKETS if b <= size["max_len"]),
    ))
    failed = []
    for name, probe in probes.items():
        before, t0 = mosaic_calls, time.monotonic()
        try:
            out = probe()
            status = f"ok {out}"
        except Exception as e:  # run every probe, report every refusal
            failed.append(name)
            status = f"FAILED {type(e).__name__}: {str(e)[:1500]}"
        print(
            f"{name}: {status}  pallas_calls={mosaic_calls - before}  "
            f"{time.monotonic() - t0:.1f}s", flush=True,
        )
    cache = PagedKVCache.create(
        n_layers=1, n_kv_heads=1, head_dim=8, n_pages=4, page_size=PAGE_SIZE
    )
    print(f"page allocator: {cache.allocator_impl}")
    # a miss is a compile JAX then wrote to the persistent cache: a second
    # run against the same directory should count none
    print(f"persistent compile cache: {cache_events}")
    if failed:
        raise LegFailed(f"kernel probes failed: {failed}")
    if on_tpu and mosaic_calls == 0:
        raise LegFailed("no pallas_call was traced")
    return {
        "probes": len(probes), "pallas_calls": mosaic_calls,
        "interpret": not on_tpu, "allocator": cache.allocator_impl,
    }


# ---------------------------------------------------------------------------
# legs 3 and 4: this process drives HTTP; the container it boots owns the chip
# ---------------------------------------------------------------------------

class SmokeClient:
    """The HTTP side of a server leg: requests and /metrics."""

    def __init__(self, url: str):
        import threading

        self.url = url
        self.results: list[dict] = []
        self.errors_seen = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._poller = threading.Thread(target=self._poll, daemon=True)
        self._poller.start()

    def get(self, path: str, timeout: float = 30.0) -> bytes:
        import urllib.request

        with urllib.request.urlopen(self.url + path, timeout=timeout) as r:
            if r.status != 200:
                raise LegFailed(f"GET {path} -> {r.status}")
            return r.read()

    def metrics(self):
        """/metrics as a Registry: ``.total(name)``, ``.series(name)``."""
        from modal_examples_tpu.utils.prometheus import parse_exposition

        return parse_exposition(self.get("/metrics").decode())

    def settled_metrics(self):
        time.sleep(0.6)  # the engine flushes its counters every 0.25 s
        return self.metrics()

    def _poll(self) -> None:
        """A failing program must end the leg when it fails, not when the
        last request times out: /health says ok regardless."""
        while not self._stop.wait(0.5):
            try:
                self.errors_seen = self.metrics().total(ERRORS)
            except Exception:
                continue  # the verdict reads /metrics again, loudly

    def close(self) -> None:
        self._stop.set()
        self._poller.join(timeout=5)

    def chat(self, n_prompt_bytes: int, max_tokens: int, stream: bool,
             temperature: float) -> None:
        """One /v1/chat/completions request; its outcome lands in results."""
        import urllib.error
        import urllib.request

        words = "the quick brown fox jumps over the lazy dog "
        content = (words * (n_prompt_bytes // len(words) + 1))[:n_prompt_bytes]
        body = {
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": temperature,
            "stream": stream,
        }
        if stream:
            body["stream_options"] = {"include_usage": True}
        rec = {"stream": stream, "prompt_bytes": n_prompt_bytes,
               "max_tokens": max_tokens, "status": None, "finish": None,
               "prompt_tokens": None, "completion_tokens": None, "error": None}
        req = urllib.request.Request(
            self.url + "/v1/chat/completions", data=json.dumps(body).encode(),
            headers={"content-type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=420) as r:
                rec["status"] = r.status
                if stream:
                    for raw in r:
                        line = raw.decode().strip()
                        if not line.startswith("data: ") or line == "data: [DONE]":
                            continue
                        chunk = json.loads(line[6:])
                        if "error" in chunk:
                            rec["error"] = chunk["error"]["message"]
                        for ch in chunk.get("choices") or []:
                            rec["finish"] = ch.get("finish_reason") or rec["finish"]
                        if chunk.get("usage"):
                            rec["prompt_tokens"] = chunk["usage"]["prompt_tokens"]
                            rec["completion_tokens"] = chunk["usage"]["completion_tokens"]
                else:
                    out = json.load(r)
                    rec["finish"] = out["choices"][0]["finish_reason"]
                    rec["prompt_tokens"] = out["usage"]["prompt_tokens"]
        except urllib.error.HTTPError as e:
            rec["status"], rec["error"] = e.code, e.read()[-300:].decode(errors="replace")
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        with self._lock:
            self.results.append(rec)

    def wave(self, specs: list[tuple[int, int, bool, float]]) -> None:
        """Send the requests concurrently; stop waiting the moment the
        engine reports a scheduler error."""
        import threading

        threads = [
            threading.Thread(target=self.chat, args=s, daemon=True) for s in specs
        ]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            if self.errors_seen:
                raise LegFailed(
                    f"{ERRORS} = {self.errors_seen:.0f} while requests were "
                    "in flight (the container's log has the traceback)"
                )
            time.sleep(0.1)


def request_ok(rec: dict) -> bool:
    if rec["status"] != 200 or rec["error"] or rec["finish"] not in ("length", "stop"):
        return False
    if rec["finish"] == "length" and rec["completion_tokens"] is not None:
        return rec["completion_tokens"] == rec["max_tokens"]
    return True


def check_boot(client: SmokeClient, model: str, want: dict, tp: int) -> None:
    """/health, /v1/models, the plan that actually runs, device memory."""
    if json.loads(client.get("/health"))["status"] != "ok":
        raise LegFailed("/health is not ok")
    models = json.loads(client.get("/v1/models"))["data"]
    if [m["id"] for m in models] != [model]:
        raise LegFailed(f"/v1/models lists {models}")
    reg = client.metrics()
    plans = [labels for labels, _v in reg.series("mtpu_decode_impl")]
    if len(plans) != 1:
        raise LegFailed(f"expected one mtpu_decode_impl series: {plans}")
    print(f"impl_plan: {json.dumps(plans[0], sort_keys=True)}", flush=True)
    wrong = {k: (plans[0].get(k), v) for k, v in want.items() if plans[0].get(k) != v}
    if wrong:
        raise LegFailed(f"impl_plan (got, wanted): {wrong}")
    memory = {"in_use": {}, "peak": {}, "limit": {}}
    for labels, value in reg.series("mtpu_device_memory_bytes"):
        memory[labels["kind"]][int(labels["device"])] = value
    for kind in ("in_use", "peak"):
        if memory[kind]:  # the CPU backend reports none
            print(f"device memory {kind} after boot: " + "  ".join(
                f"dev{d}={b / 2**30:.2f}GiB" for d, b in sorted(memory[kind].items())
            ), flush=True)
    in_use = memory["in_use"]
    if tp > 1 and (
        len(in_use) != tp or min(in_use.values()) < sum(in_use.values()) / tp / 2
    ):
        raise LegFailed(f"weights and KV are not spread over {tp} devices: {in_use}")


def drive_traffic(client: SmokeClient, slots: int, max_len: int) -> dict:
    """13 requests in three waves (9 for the 4-slot rehearsal): one alone,
    ``slots`` at once, four of other lengths — streamed and not, greedy and
    sampled. Returns what the engine's counters say about them."""
    short, medium, long_ = max_len // 16, max_len // 5, max_len * 5 // 8

    def counters():
        reg = client.settled_metrics()
        return reg.total("mtpu_generated_tokens_total"), reg.total("mtpu_decode_steps_total")

    tokens0, _ = counters()
    t0 = time.monotonic()
    client.wave([(short, 16, False, 0.0)])  # first compile: on its own
    first_request_s = time.monotonic() - t0
    tokens1, steps1 = counters()
    client.wave([  # a full decode batch
        (short, max_len // 4, i % 2 == 0, 0.8 if i % 3 else 0.0)
        for i in range(slots)
    ])
    tokens2, steps2 = counters()
    client.wave([  # other prefill buckets, side by side
        (medium, 16, True, 0.8), (medium, 16, False, 0.0),
        (long_, 16, True, 0.0), (long_, 16, False, 0.8),
    ])
    requests_s = time.monotonic() - t0
    reg = client.settled_metrics()
    return {
        "first_request_s": round(first_request_s, 1),
        "mean_decode_batch": round((tokens2 - tokens1) / max(1.0, steps2 - steps1), 2),
        "requests_s": round(requests_s, 1),
        "generated_tokens": int(reg.total("mtpu_generated_tokens_total") - tokens0),
        "error_count": int(reg.total(ERRORS)),
    }


def leg_server(args) -> dict:
    """Boot the example's server through App.run() -> LLMServer.serve(),
    drive it over HTTP, print what ran, raise unless all of it passed."""
    import shutil
    import socket
    import tempfile

    size = REHEARSAL_MODEL if args.rehearse_cpu else SMOKE_MODEL
    max_len, slots = size["max_len"], size["slots"]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    state_dir = tempfile.mkdtemp(prefix="chip-smoke-state-")
    os.environ.update({
        "MTPU_MODEL": size["model"], "MTPU_QUANT": size["quant"],
        "MTPU_PORT": str(port), "MTPU_STATE_DIR": state_dir,
        "MTPU_TP": str(args.tp),
        "MTPU_TPU": "" if args.rehearse_cpu else f"v5e-{args.tp}",
    })
    # the engine's own selection knobs; a leg that names none runs the defaults
    for var, value in (("MTPU_PAGED_IMPL", args.paged_impl),
                       ("MTPU_SCATTER_IMPL", args.scatter_impl),
                       ("MTPU_KV_DTYPE", args.kv_dtype)):
        os.environ.pop(var, None)
        if value:
            os.environ[var] = value
    # what llama.paged_impl_plan resolves: a head shard of 8 KV heads (the
    # smoke model on one chip) takes the ragged kernel's flat variant, the
    # rehearsal's 2 and a TP shard's 8 // tp the grouped one; with nothing
    # asked for the plan picks the kernel on the chip and the loop off it
    kv_heads = (2 if args.rehearse_cpu else 8) // args.tp
    variant = "grouped" if kv_heads % 8 else "flat"
    if args.paged_impl == "pallas":
        want = {"attention": "ragged", "variant": variant, "scatter": "pallas"}
    elif not args.paged_impl and not args.rehearse_cpu:
        want = {"attention": "ragged", "variant": variant, "scatter": "xla"}
    else:
        want = {"attention": "xla-gather", "variant": "-", "scatter": "xla"}
    want.update(tp=str(args.tp), downgraded="0",
                kv_dtype=args.kv_dtype or "bfloat16")
    from modal_examples_tpu.core.app import load_module_from_path

    mod = load_module_from_path(str(EXAMPLE))
    try:
        with mod.app.run():
            t0 = time.monotonic()
            url = mod.LLMServer.serve()  # raises the container's boot error
            out = {"boot_s": round(time.monotonic() - t0, 1)}
            client = SmokeClient(url)
            try:
                check_boot(client, size["model"], want, args.tp)
                out.update(drive_traffic(client, slots, max_len))
            finally:
                client.close()
            results = client.results
            failed = [r for r in results if not request_ok(r)]
            buckets = sorted({
                min(b for b in PREFILL_BUCKETS if b >= r["prompt_tokens"])
                for r in results if r["prompt_tokens"]
            })
            by_length = sum(r["max_tokens"] for r in results if r["finish"] == "length")
            out.update(
                requests_sent=len(results), succeeded=len(results) - len(failed),
                failed=len(failed), prefill_buckets=buckets,
            )
            print(json.dumps(out), flush=True)
            if failed:
                raise LegFailed(f"{len(failed)} of {len(results)} requests failed: {failed[:3]}")
            if out["error_count"]:
                raise LegFailed(f"{ERRORS} = {out['error_count']}")
            if out["generated_tokens"] < by_length:
                raise LegFailed(
                    f"engine counted {out['generated_tokens']} generated tokens; "
                    f"the requests that ended by length asked for {by_length}"
                )
            if max_len > PREFILL_BUCKETS[0] and len(buckets) < 2:
                raise LegFailed(f"only prefill buckets {buckets} ran")
            if out["mean_decode_batch"] < 1.5:
                raise LegFailed(
                    f"{slots} concurrent requests decoded at a mean batch of "
                    f"{out['mean_decode_batch']}"
                )
            mod.LLMServer.stop()
        # the container must be gone before the next leg attaches
        deadline = time.monotonic() + 90
        while (alive := _descendants(os.getpid())) and time.monotonic() < deadline:
            time.sleep(0.2)
        if alive:
            raise LegFailed(f"container still alive: {alive}")
    finally:
        shutil.rmtree(state_dir, ignore_errors=True)
    if "jax" in sys.modules:
        raise LegFailed("the server leg's driver imported JAX")
    return {}


LEGS = {
    "device": leg_device,
    "kernels": leg_kernels,
    "server-default": leg_server,
    "server-pallas": leg_server,
    "server-pallas-int8kv": leg_server,
    "server-tp": leg_server,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse-cpu", action="store_true")
    # internal: one leg, in this process
    ap.add_argument("--leg", choices=sorted(LEGS), help=argparse.SUPPRESS)
    ap.add_argument("--tp", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--paged-impl", help=argparse.SUPPRESS)
    ap.add_argument("--scatter-impl", help=argparse.SUPPRESS)
    ap.add_argument("--kv-dtype", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg is None:
        return parent(args)
    try:
        result = LEGS[args.leg](args)
    except LegFailed as e:
        sys.stderr.write(f"leg {args.leg}: {e}\n")
        return 1
    print(json.dumps({"leg": args.leg, "ok": True, **result}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
