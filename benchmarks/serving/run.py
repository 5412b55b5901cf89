#!/usr/bin/env python3
"""One cell of the serving benchmark, once.

    python3 benchmarks/serving/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It boots the cell's configuration through the
program's normal path (``App.run()`` -> ``@app.server`` -> ``LLMEngine``
behind ``serving/openai_api.py``; the container it starts owns the chip and
this process never imports JAX), warms up the shapes the cell's traffic uses,
ramps, measures for ``--seconds``, compares a sample of what the window served
with the plain reference, and prints one JSON line. Everything before the
window opens is ``setup_s``. It exits non-zero, and prints no result, where
it finds no TPU.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import manifest as M  # noqa: E402
import traffic  # noqa: E402
from rundata import RunData, parse_exposition  # noqa: E402
from tokenizer import BOS_ID  # noqa: E402

WARMUP_TOKENS = 9  # a first token and one whole decode block
ERRORS = "mtpu_scheduler_errors_total"


class RunFailed(Exception):
    pass


def _get(url: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _post(url: str, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"content-type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise RunFailed(f"POST {url} -> {e.code}: {e.read()[-2000:].decode(errors='replace')}")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def _descendants(root: int) -> dict[int, str]:
    """Live (not zombie) processes below ``root``."""
    procs = {}
    for stat in Path("/proc").glob("[0-9]*/stat"):
        try:
            state, ppid = stat.read_text().rsplit(")", 1)[1].split()[:2]
            cmd = (stat.parent / "cmdline").read_bytes().replace(b"\0", b" ")
        except (OSError, ValueError):
            continue
        if state != "Z":
            procs[int(stat.parent.name)] = (int(ppid), cmd.decode(errors="replace"))
    found: dict[int, str] = {}
    frontier = {root}
    while frontier:
        frontier = {p for p, (pp, _c) in procs.items() if pp in frontier} - set(found)
        found.update({p: procs[p][1] for p in frontier})
    return found


def warmup_requests(mix: dict, seconds: float, device: dict, vocab: int) -> list:
    """One request for each compiled prefill shape the cell's prompts use:
    the longest prompt of each bucket, and the longest beyond the largest
    bucket (the chunked path), in ascending order. This cell's shapes and no
    others."""
    import numpy as np

    lengths = sorted(set(traffic.prompt_lengths(mix, seconds)))
    buckets = sorted(device["prefill_buckets"])
    picks, low = [], 0
    for b in buckets + [10**9]:
        inside = [n for n in lengths if low < n <= b]
        if inside:
            picks.append(max(inside))
        low = b
    rng = np.random.default_rng(12345)
    return [
        traffic.RequestSpec(
            rid=-1 - i, phase="warmup", due_s=None,
            prompt_ids=rng.integers(3, vocab, size=n - 1).tolist(),
            max_tokens=WARMUP_TOKENS, temperature=float(mix["temperature"]),
        )
        for i, n in enumerate(picks)
    ]


def pick_samples(scored: list, seed: int, count: int) -> list:
    """The requests the reference reads again: greedy ones the window
    finished, the longest among them, the rest drawn from the seed."""
    import numpy as np

    greedy = [o for o in scored if o.ok and o.spec.temperature == 0.0 and o.n_out >= 2]
    if not greedy:
        return []
    greedy.sort(key=lambda o: (len(o.spec.prompt_ids) + o.n_out, o.spec.rid))
    longest = greedy.pop()
    rng = np.random.default_rng([int(seed), 4])
    rest = [greedy[int(i)] for i in rng.permutation(len(greedy))[: max(0, count - 1)]]
    return [longest] + rest


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True, control: bool = False,
             extra_env: dict | None = None, mix_overrides: dict | None = None,
             dump: str | None = None) -> dict:
    """Drive one run; returns the result object. Raises where the run cannot
    report: no TPU, a failed boot, a metric that could not be read. The
    keyword arguments are for ``probe.py`` and the tests; the command sets
    none of them."""
    info = M.resolve(M.load_manifest(root), workload, root)
    info["mix"].update(mix_overrides or {})
    cell, config, mix = info["cell"], info["config"], info["mix"]
    vocab = int(config["vocab_size"])
    sys.path.insert(0, str(root))
    from modal_examples_tpu.core.app import load_module_from_path
    from modal_examples_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    trace_dir = Path(root) / ".bench_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    state_dir = tempfile.mkdtemp(prefix="bench-serving-state-")
    port, ctrl_port = _free_port(), _free_port()
    ctrl = f"http://127.0.0.1:{ctrl_port}"
    os.environ.update({
        "BENCH_CONFIG_FILE": info["config_file"], "BENCH_SEED": str(int(seed)),
        "BENCH_PORT": str(port), "BENCH_CTRL_PORT": str(ctrl_port),
        "BENCH_TPU": f"v5e-{cell['chips']}" if require_tpu else "",
        "BENCH_TRACE_DIR": str(trace_dir), "MTPU_STATE_DIR": state_dir,
        "PYTHONPATH": os.pathsep.join(
            [str(root)] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
        ),
        **(extra_env or {}),
    })
    times = {"process_start": PROCESS_START}
    mod = load_module_from_path(str(HERE / "server_app.py"))
    try:
        with mod.app.run():
            try:
                url = mod.BenchServer.serve()  # raises the container's boot error
                if json.loads(_get(url + "/health"))["status"] != "ok":
                    raise RunFailed("/health is not ok")
                times["health_ok"] = time.monotonic()
                device = json.loads(_get(ctrl + "/device"))
                if require_tpu and (
                    device["platform"] != "tpu" or device["count"] < cell["chips"]
                ):
                    raise RunFailed(
                        f"the cell needs {cell['chips']} TPU chip(s); JAX reports "
                        f"{device['count']} x {device['platform']}"
                    )
                result = _measure(
                    url, ctrl, info, int(seed), float(seconds), trace, control,
                    times, device, vocab, dump,
                )
            finally:
                mod.BenchServer.stop()
        deadline = time.monotonic() + 90
        while (alive := _descendants(os.getpid())) and time.monotonic() < deadline:
            time.sleep(0.2)
        if alive:
            raise RunFailed(f"the serving container is still alive: {alive}")
    finally:
        shutil.rmtree(state_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if "jax" in sys.modules:
        raise RunFailed("the load generator's process imported JAX")
    return result


def _measure(url, ctrl, info, seed, seconds, trace, control, times, device, vocab, dump) -> dict:
    cell, config, mix = info["cell"], info["config"], info["mix"]

    def scrape() -> dict:
        return parse_exposition(_get(url + "/metrics").decode())

    # -- warm-up: every shape the window will use, in a fixed order ---------
    times["warmup_start"] = time.monotonic()
    for spec in warmup_requests(mix, seconds, device, vocab):
        out = loadgen.Outcome(spec=spec)
        loadgen.send(url, loadgen.payload(spec), out)
        if not out.ok or out.n_out != WARMUP_TOKENS:
            raise RunFailed(f"warm-up request failed: {out.error or out.finish} ({out.n_out} tokens)")

    # -- the traffic, built before the ramp ---------------------------------
    ramp_s = float(mix["ramp_s"])
    holder: dict = {}
    if mix["loop"] == "open":
        specs = traffic.open_loop(mix, seed, seconds, vocab)
        t_open = time.monotonic() + ramp_s + 0.25
        target = lambda: holder.update(out=loadgen.run_open(url, specs, t_open))  # noqa: E731
    else:
        sessions = traffic.closed_loop(mix, seed, vocab)
        starts = traffic.client_starts(mix, seed)
        t_ramp = time.monotonic() + 0.25
        t_open = t_ramp + ramp_s
        target = lambda: holder.update(  # noqa: E731
            out=loadgen.run_closed(url, sessions, starts, t_ramp, t_open + seconds)
        )
    t_close = t_open + seconds
    load = threading.Thread(target=target, daemon=True)
    load.start()

    # -- the window ----------------------------------------------------------
    _sleep_until(t_open)
    counters_open = scrape()
    times["window_open"] = t_open
    kv_peak, reduced = None, None
    if trace:
        trace_s = min(float(mix.get("trace_s", 6.0)), seconds / 2.0)
        peak = [0.0]
        stop_poll = threading.Event()

        def poll() -> None:
            while not stop_poll.wait(1.0):
                for _lab, v in scrape().get("mtpu_kv_pages_used", []):
                    peak[0] = max(peak[0], v)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        _sleep_until(t_close - trace_s)
        _post(ctrl + "/trace/start", {}, 120)
    _sleep_until(t_close)
    counters_close = scrape()
    times["window_close"] = t_close
    if trace:
        stop_poll.set()
        poller.join()
        reduced = _post(ctrl + "/trace/stop", {"sample_seconds": 0.6 if dump else 0}, 300)
        kv_peak = peak[0]
    load.join(loadgen.REQUEST_TIMEOUT_S + 60)
    if load.is_alive() or "out" not in holder:
        raise RunFailed("the load generator did not finish")
    outcomes = holder["out"]

    # -- what is scored ------------------------------------------------------
    if mix["loop"] == "open":
        scored = [o for o in outcomes if o.spec.phase == "window"]
        open_at_close = sum(1 for o in outcomes if o.done_t is None or o.done_t > t_close)
    else:
        scored = [o for o in outcomes if o.done_t is not None and t_open <= o.done_t < t_close]
        open_at_close = sum(1 for o in outcomes if o.done_t is not None and o.done_t >= t_close)
    failed = [o for o in scored if not o.ok]
    early = [o for o in scored if o.ended_early]
    engine_log = {e["id"]: e for e in json.loads(_get(ctrl + "/requests"))}
    device = json.loads(_get(ctrl + "/device"))  # the peak, after the window
    errors = sum(v for _l, v in scrape().get(ERRORS, []))

    # -- the comparison with the reference, after the window ----------------
    limits = config["check"]
    samples = pick_samples(scored, seed, int(mix["check_samples"]))
    if not samples:
        raise RunFailed("the window finished no greedy request to compare")
    checked = _post(ctrl + "/check", {
        "samples": [
            {"prompt": [BOS_ID] + o.spec.prompt_ids, "served": o.served_ids()} for o in samples
        ],
        "control": control, "detail": bool(dump),
    }, 600)
    detail = {k: checked.pop(k) for k in ("gaps", "margins", "control_gaps") if k in checked}
    compared = {
        **{name: (checked[name], limit) for name, limit in limits.items()},
        "ended_early": (len(early), 0),
        "scheduler_errors": (errors, 0),
    }
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", flush=True)
    print(f"reference: {checked['served_tokens']} served tokens of {len(samples)} "
          f"requests in {checked['check_s']:.1f}s (weights {checked['weights_s']:.1f}s, "
          f"layers {checked['layers_s']:.1f}s)", flush=True)
    correct = all(value <= limit for value, limit in compared.values())

    run = RunData(
        cell=cell, config=config, mix=mix, times=times,
        outcomes=outcomes, scored=scored, counters_open=counters_open,
        counters_close=counters_close, kv_pages_peak=kv_peak, engine_log=engine_log,
        device=device, trace=reduced,
    )
    readers = M.load_readers()
    metrics = {}
    for m in info["per_layer" if trace else "end_to_end"]:
        value = readers[M.quantity(m["name"])](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not trace:
            raise RunFailed(f"end-to-end metric {m['name']} could not be read")
    out_device = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    result = {
        "correct": correct, "attempted": len(scored), "failed": len(failed),
        "metrics": metrics, "device": out_device,
        "open_at_close": open_at_close, "compared": checked,
    }
    if dump:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        Path(dump).write_text(json.dumps({
            "result": result, "times": times, "device": device, "trace": reduced,
            "check_detail": detail,
            "all_metrics": {name: fn(run) for name, fn in readers.items()},
            "requests": [
                {"rid": o.spec.rid, "phase": o.spec.phase, "due": o.due_t, "sent": o.sent_t,
                 "first": o.first_t, "last": o.last_t, "done": o.done_t, "n_out": o.n_out,
                 "n_prompt": o.prompt_tokens, "cached": o.cached_tokens, "ok": o.ok,
                 "temperature": o.spec.temperature, "error": o.error}
                for o in outcomes
            ],
        }))
    if trace:
        out_device["busy_s"] = reduced["busy_s"]
        out_device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except Exception as e:
        import traceback

        traceback.print_exc()
        sys.stderr.write(f"benchmarks/serving/run.py: FAILED: {e}\n")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
