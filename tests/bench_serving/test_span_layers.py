"""The readers of the scheduler's spans and counters
(``benchmarks/serving/layers/spans.py``): each on hand-made scrapes of
/metrics and a hand-made table of programs, None where the program exports
no such series, the manifest's new entries against the contract's rules, and
one traced run on the CPU at a tiny size that has to print every new metric
that comes from a counter."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "serving"
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402
from rundata import RunData, parse_exposition  # noqa: E402

READERS = M.load_readers()
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

COUNTER_SOURCED = (
    "tick_host_ms", "harvest_wait_pct", "starved_pct", "decode_step_gap_ms",
    "prefill_wait_p50_ms", "prefill_useful_pct", "compiles_in_window",
)
OPEN = """
mtpu_tick_phase_seconds_sum{phase="admit"} 1.0
mtpu_tick_phase_seconds_sum{phase="decode_dispatch"} 2.0
mtpu_tick_phase_seconds_sum{phase="harvest"} 30.0
mtpu_tick_phase_seconds_sum{phase="total"} 33.5
mtpu_tick_phase_seconds_count{phase="admit"} 100
mtpu_tick_phase_seconds_count{phase="total"} 100
mtpu_device_starved_seconds_total{phase="admit"} 0.5
mtpu_decode_stall_seconds_sum 10.0
mtpu_decode_steps_total 400
mtpu_engine_first_token_wait_seconds_bucket{le="0.25"} 2
mtpu_engine_first_token_wait_seconds_bucket{le="0.5"} 4
mtpu_engine_first_token_wait_seconds_bucket{le="+Inf"} 4
mtpu_prefill_positions_total{kind="computed"} 8192
mtpu_prefill_positions_total{kind="needed"} 1000
mtpu_compiles_total{program="block",cache="miss"} 1
mtpu_compiles_total{program="block",cache="hit"} 50
mtpu_compiles_total{program="prefill",cache="miss"} 5
mtpu_compile_seconds_sum{program="block"} 4.0
mtpu_compile_seconds_sum{program="prefill"} 8.5
"""
CLOSE = """
mtpu_tick_phase_seconds_sum{phase="admit"} 1.5
mtpu_tick_phase_seconds_sum{phase="decode_dispatch"} 3.5
mtpu_tick_phase_seconds_sum{phase="harvest"} 70.0
mtpu_tick_phase_seconds_sum{phase="total"} 76.0
mtpu_tick_phase_seconds_count{phase="admit"} 300
mtpu_tick_phase_seconds_count{phase="total"} 300
mtpu_device_starved_seconds_total{phase="admit"} 1.0
mtpu_device_starved_seconds_total{phase="prefill_dispatch"} 0.25
mtpu_decode_stall_seconds_sum 60.0
mtpu_decode_steps_total 1400
mtpu_engine_first_token_wait_seconds_bucket{le="0.25"} 2
mtpu_engine_first_token_wait_seconds_bucket{le="0.5"} 12
mtpu_engine_first_token_wait_seconds_bucket{le="+Inf"} 14
mtpu_prefill_positions_total{kind="computed"} 49152
mtpu_prefill_positions_total{kind="needed"} 7144
mtpu_compiles_total{program="block",cache="miss"} 1
mtpu_compiles_total{program="block",cache="hit"} 250
mtpu_compiles_total{program="prefill",cache="miss"} 5
mtpu_compile_seconds_sum{program="block"} 4.0
mtpu_compile_seconds_sum{program="prefill"} 8.5
"""
PROGRAMS = {
    "jit__decode_block_fn": {"time_s": 4.0, "count": 10},
    "jit__prefill_and_sample": {"time_s": 1.0, "count": 3},
    "jit_prefill_chunk_off2048": {"time_s": 0.5, "count": 2},
    "jit__threefry_fold_in": {"time_s": 0.001, "count": 40},
    "jit_concatenate": {"time_s": 0.001, "count": 25},
}


def _run(open_text=OPEN, close_text=CLOSE, programs=PROGRAMS):
    return RunData(
        cell={}, config={}, mix={"loop": "open"},
        times={"window_open": 100.0, "window_close": 150.0},
        outcomes=[], scored=[], counters_open=parse_exposition(open_text),
        counters_close=parse_exposition(close_text), kv_pages_peak=None,
        engine_log={}, device={},
        trace=None if programs is None else {"programs": programs, "window_s": 6.0},
    )


@pytest.mark.parametrize("quantity, expected", [
    # every phase but harvest, over the busy ticks: (0.5 + 1.5) s / 200
    ("tick_host_ms", 10.0),
    ("harvest_wait_pct", 80.0),  # 40 s of a 50 s window
    ("starved_pct", 1.5),  # 0.5 + 0.25 s of 50
    ("decode_step_gap_ms", 50.0),  # 50 s between dispatches over 1000 steps
    ("prefill_wait_p50_ms", 406.25),  # 5th of 10, of 8 in (0.25, 0.5]
    ("prefill_useful_pct", 15.0),  # 6144 needed of 40960 computed
    ("compiles_in_window", 0.0),
    ("helper_programs_per_block", 6.5),  # 65 helper calls over 10 blocks
    ("compile_s", 12.5),  # what had been built when the window opened
])
def test_reader_on_hand_made_counters(quantity, expected):
    assert READERS[quantity](_run()) == pytest.approx(expected)


def test_a_build_inside_the_window_is_counted():
    close = CLOSE.replace(
        'mtpu_compiles_total{program="prefill",cache="miss"} 5',
        'mtpu_compiles_total{program="prefill",cache="miss"} 7',
    )
    assert READERS["compiles_in_window"](_run(close_text=close)) == 2.0


def test_starved_seconds_by_phase_for_the_dump():
    assert READERS["starved_by_phase_s"](_run()) == {
        "admit": pytest.approx(0.5), "prefill_dispatch": pytest.approx(0.25),
    }


@pytest.mark.parametrize("quantity", [*COUNTER_SOURCED, "compile_s",
                                      "helper_programs_per_block"])
def test_reader_is_none_where_the_program_exports_no_such_series(quantity):
    """A commit from before these series: /metrics holds the old ones only,
    and an untraced run has no table of programs."""
    old = "mtpu_generated_tokens_total 5\nmtpu_kv_pages_used 3\n"
    assert READERS[quantity](_run(old, old, programs=None)) is None


def test_decode_step_gap_reads_the_parent_too():
    """Its two series are older than this file: the reader finds them in a
    program that has none of the others."""
    old_open = "mtpu_decode_stall_seconds_sum 1.0\nmtpu_decode_steps_total 10\n"
    old_close = "mtpu_decode_stall_seconds_sum 3.0\nmtpu_decode_steps_total 50\n"
    assert READERS["decode_step_gap_ms"](_run(old_open, old_close)) == pytest.approx(50.0)


def test_manifest_with_the_new_entries_has_no_problems():
    assert M.problems(MANIFEST, ROOT) == []
    names = {m["name"] for m in MANIFEST["per_layer"]}
    for quantity in (*COUNTER_SOURCED, "helper_programs_per_block"):
        assert {f"reason.{quantity}", f"closed.{quantity}"} <= names
        assert quantity in READERS
    compile_s = next(m for m in MANIFEST["per_layer"] if m["name"] == "compile_s")
    assert compile_s["moves"] == "setup_s" and "workloads" not in compile_s


def test_new_metrics_keep_the_layers_names():
    layers = {m["layer"] for m in MANIFEST["per_layer"][:30]}
    for m in MANIFEST["per_layer"][30:]:
        assert m["layer"] in layers, m


# -- one traced run on the CPU -------------------------------------------------

TINY = {
    "name": "tiny-dense", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "vocab_size": 512, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "quantization": "int8",
    "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 64, "max_model_len": 256,
               "prefill_buckets": [32, 64]},
    "check": {"served_gap_max": 0.15, "served_gap_mean": 0.03},
}
CLOSED = {
    "loop": "closed", "clients": 3,
    "session": {
        "turns": 2, "sessions": 12, "pool": 4,
        # longer than the largest bucket: the chunked path, as in docqa-closed
        "document": {"dist": "uniform", "min": 100, "max": 180},
        "question": {"dist": "uniform", "min": 4, "max": 12},
        "answer": {"dist": "uniform", "min": 6, "max": 12},
    },
    "temperature": 0.7, "greedy_every": 1, "stagger_s": 0.5, "ramp_s": 1.5, "trace_s": 1,
    "check_samples": 2,
}
DRIVER = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1] + "/benchmarks/serving/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
print(json.dumps(run.run_cell("tiny-dense.tiny-closed", 2**31 + 29, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False)))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration and mix beside the
    files that are there, and the manifest's metrics pointed at the one cell."""
    root = tmp_path_factory.mktemp("bench-spans")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs/tiny-dense.json").write_text(json.dumps(TINY))
    (bench / "mixes/tiny-closed.json").write_text(json.dumps(CLOSED))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    closed_cells = {w["name"] for w in manifest["workloads"] if w["traffic"] == "docqa-closed"}
    manifest["configs"] = [{
        "name": "tiny-dense", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-dense.json",
    }]
    manifest["workloads"] = [{
        "name": "tiny-dense.tiny-closed", "config": "tiny-dense",
        "traffic": "tiny-closed", "chips": 1, "why": "test",
    }]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for metric in manifest[group]:
            if "workloads" not in metric:
                kept.append(metric)
            elif set(metric["workloads"]) <= closed_cells:
                kept.append(dict(metric, workloads=["tiny-dense.tiny-closed"]))
        manifest[group] = kept
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    spec = importlib.util.spec_from_file_location("bench_spans_manifest", bench / "manifest.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    assert copied.problems(manifest, root) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MTPU_PROFILE", None)  # as the benchmark's command: nothing set
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(root)],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(root),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_traced_cpu_run_prints_every_counter_sourced_metric(traced):
    metrics = traced["metrics"]
    for quantity in COUNTER_SOURCED:
        assert f"closed.{quantity}" in metrics, (quantity, sorted(metrics))
    assert "compile_s" in metrics
    assert not any(name.startswith("reason.") for name in metrics)
    assert traced["correct"] is True and traced["failed"] == 0


def test_traced_cpu_run_counts_what_it_should(traced):
    """Counts, not speeds: a CPU says nothing about how fast the chip is,
    but what the program counts is the same everywhere."""
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert value["closed.compiles_in_window"] == 0  # the warm-up built every shape
    assert value["compile_s"] > 0
    # every prompt is longer than the largest bucket (64): chunk calls of 64
    # positions, and second turns find their document on cached pages
    assert 0 < value["closed.prefill_useful_pct"] < 100
    assert 0 <= value["closed.starved_pct"] <= 100
    assert 0 < value["closed.harvest_wait_pct"] <= 100
    assert value["closed.tick_host_ms"] > 0
    assert value["closed.decode_step_gap_ms"] > 0
    assert value["closed.prefill_wait_p50_ms"] > 0
