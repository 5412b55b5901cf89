"""The readers of set-up (``benchmarks/serving/layers/setup.py``): the
container's boot by phase and the program builds by kind, on hand-made
/metrics scrapes, None on a scrape from a program without the series, the
entries a ``benchmark`` issue is to give them in the manifest, and a traced
CPU rehearsal that prints them all under those entries."""

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
NEW = [
    "boot_pre_spawn_s", "boot_spawn_s", "boot_attach_s", "boot_enter_s", "boot_engine_init_s",
    "boot_unnamed_s", "warmup_requests_s", "compile_trace_lower_s", "compile_xla_s",
    "compile_cache_load_s", "compile_eager_s", "compile_cache_hit_pct",
]

# process start at 1000.0; the supervisor's Popen 0.8 s later; spawn 1.2,
# attach 4.5, enter 9.0 (engine_init 1.5 of it, kv_alloc 0.7 of that); ready at
# 1015.5 and /health answered at 1015.9. Warm-up 7.1 s of requests, then the
# mix's 20 s ramp after loadgen's 0.25
OPEN = """
mtpu_boot_mark_seconds{mark="spawned"} 1000.8
mtpu_boot_mark_seconds{mark="ready"} 1015.5
mtpu_boot_phase_seconds{phase="spawn"} 1.2
mtpu_boot_phase_seconds{phase="attach"} 4.5
mtpu_boot_phase_seconds{phase="enter"} 9.0
mtpu_boot_phase_seconds{phase="engine_init"} 1.5
mtpu_boot_phase_seconds{phase="kv_alloc"} 0.7
mtpu_boot_phase_seconds{phase="server_start"} 0.01
mtpu_compile_seconds_sum{program="block"} 3.0
mtpu_compile_seconds_sum{program="prefill_chunk"} 6.5
mtpu_compiles_total{program="prefill_chunk",cache="ahead"} 3
mtpu_compiles_total{program="prefill_chunk",cache="miss"} 2
mtpu_compile_phase_seconds_total{program="block",kind="trace"} 0.5
mtpu_compile_phase_seconds_total{program="block",kind="lower"} 1.0
mtpu_compile_phase_seconds_total{program="block",kind="cache_load"} 1.25
mtpu_compile_phase_seconds_total{program="prefill_chunk",kind="trace"} 1.5
mtpu_compile_phase_seconds_total{program="prefill_chunk",kind="lower"} 2.5
mtpu_compile_phase_seconds_total{program="prefill_chunk",kind="xla_compile"} 0.125
mtpu_compile_phase_seconds_total{program="prefill_chunk",kind="cache_load"} 2.0
mtpu_compile_phase_seconds_total{program="(eager)",kind="lower"} 0.25
mtpu_compile_phase_seconds_total{program="(eager)",kind="xla_compile"} 0.5
mtpu_compile_cache_total{result="hit"} 38
mtpu_compile_cache_total{result="miss"} 2
"""
BEFORE_THE_SERIES = 'mtpu_compile_seconds_sum{program="block"} 3.0\nmtpu_decode_steps_total 5\n'
TIMES = {"process_start": 1000.0, "health_ok": 1015.9, "warmup_start": 1016.0,
         "window_open": 1043.35, "window_close": 1094.35}


def _run(open_text):
    return RunData(
        cell={}, config={}, mix={"loop": "closed", "ramp_s": 20.0}, times=dict(TIMES),
        outcomes=[], scored=[], counters_open=parse_exposition(open_text),
        counters_close={}, kv_pages_peak=None, engine_log={}, device={}, trace=None,
    )


@pytest.mark.parametrize("name, want", [
    ("boot_pre_spawn_s", 0.8), ("boot_spawn_s", 1.2), ("boot_attach_s", 4.5),
    ("boot_enter_s", 9.0), ("boot_engine_init_s", 1.5),
    ("boot_unnamed_s", 0.4),  # 15.9 - 0.8 - (1.2 + 4.5 + 9.0): ready -> /health
    ("warmup_requests_s", 7.1), ("compile_trace_lower_s", 5.75), ("compile_xla_s", 0.625),
    ("compile_cache_load_s", 3.25), ("compile_eager_s", 0.75), ("compile_cache_hit_pct", 95.0),
])
def test_each_reader_on_a_hand_made_scrape(name, want):
    assert READERS[name](_run(OPEN)) == pytest.approx(want)


def test_the_named_parts_add_up_to_boot_s():
    run = _run(OPEN)
    parts = ("boot_pre_spawn_s", "boot_spawn_s", "boot_attach_s", "boot_enter_s", "boot_unnamed_s")
    assert sum(READERS[p](run) for p in parts) == pytest.approx(READERS["boot_s"](run))
    # a boot that restored a snapshot: the phase joins the named, not the unnamed
    restored = _run(OPEN + 'mtpu_boot_phase_seconds{phase="restore"} 0.3\n')
    assert READERS["boot_unnamed_s"](restored) == pytest.approx(0.1)
    # a phase never entered reads 0, not None: a CPU container attaches nothing
    cpu = _run(OPEN.replace('mtpu_boot_phase_seconds{phase="attach"} 4.5\n', ""))
    assert READERS["boot_attach_s"](cpu) == 0


@pytest.mark.parametrize("name", [n for n in NEW if n != "warmup_requests_s"]
                         + ["boot_by_phase_s", "compile_by_program_s"])
def test_none_where_the_program_exports_no_such_series(name):
    """The parent commit under these files runs to a result with them left out."""
    assert READERS[name](_run(BEFORE_THE_SERIES)) is None


def test_the_tables_for_the_dump():
    run = _run(OPEN)
    assert READERS["boot_by_phase_s"](run) == {
        "spawn": 1.2, "attach": 4.5, "enter": 9.0, "engine_init": 1.5, "kv_alloc": 0.7,
        "server_start": 0.01,
    }
    table = READERS["compile_by_program_s"](run)
    assert table["prefill_chunk"] == {
        "trace": 1.5, "lower": 2.5, "xla_compile": 0.125, "cache_load": 2.0,
        "built_s": 6.5, "ahead": 3.0,
    }
    assert table["(eager)"] == {"lower": 0.25, "xla_compile": 0.5}
    # inside the window: a helper program met for the first time, 0.125 s of lowering
    later = _run(OPEN)
    later.counters_close = parse_exposition(OPEN.replace(
        'program="(eager)",kind="lower"} 0.25', 'program="(eager)",kind="lower"} 0.375'))
    assert READERS["compile_by_program_s"](later)["(window)"] == {
        "trace": 0.0, "lower": 0.125, "xla_compile": 0.0, "cache_load": 0.0}
    # no cache answer yet: nothing to take a share of
    assert READERS["compile_cache_hit_pct"](_run(OPEN.split("mtpu_compile_cache_total")[0])) is None


def _entry(name):
    """The entry ISSUE 36 gives a reader: every cell, as ``compile_s``. The
    accepted manifest does not hold them yet: appended they break
    ``test_glm_dsa_cell.py``'s hold on the list's last five, and an entry put
    in the middle reads to the driver as a change (PERF.md, Open questions)."""
    hit = name == "compile_cache_hit_pct"
    return {
        "name": name, "unit": "%" if hit else "s", "better": "higher" if hit else "lower",
        # the warm-up's length is the harness's own clock around its own ramp
        "source": "host_clock" if name == "warmup_requests_s" else "program_span",
        "layer": "entry points and executor", "moves": "setup_s",
    }


def _with_entries(manifest):
    """``manifest`` with the entries it lacks put directly after ``compile_s``."""
    per_layer = list(manifest["per_layer"])
    named = {m["name"] for m in per_layer}
    at = [m["name"] for m in per_layer].index("compile_s") + 1
    per_layer[at:at] = [_entry(n) for n in NEW if n not in named]
    return dict(manifest, per_layer=per_layer)


def test_the_accepted_manifest_is_sound_with_the_readers_beside_it():
    """A reader no entry names is a dump table, as ``tick_by_phase_s`` is."""
    assert M.problems(MANIFEST, ROOT) == []
    assert set(NEW) | {"boot_by_phase_s", "compile_by_program_s"} <= set(READERS)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    compile_s = by_name["compile_s"]
    assert (compile_s["layer"], compile_s["moves"]) == ("entry points and executor", "setup_s")
    # found by name, wherever a later PR puts them
    for name in set(NEW) & set(by_name):
        assert by_name[name] == _entry(name)


def test_the_entries_join_the_manifest_without_a_problem():
    manifest = _with_entries(MANIFEST)
    assert M.problems(manifest, ROOT) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert set(NEW) <= set(names)
    # what was there keeps its order
    assert [n for n in names if n not in NEW] == \
        [m["name"] for m in MANIFEST["per_layer"] if m["name"] not in NEW]


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_would_report_them(cell):
    manifest = _with_entries(MANIFEST)
    reported = {m["name"] for m in M.resolve(manifest, cell, ROOT)["per_layer"]}
    assert set(NEW) <= reported


# -- the traced rehearsal on the CPU ---------------------------------------------------

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
print(json.dumps(run.run_cell("tiny-dense.tiny-closed", 2**31 + 41, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False,
                              dump=sys.argv[1] + "/dump.json")))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The benchmark copied beside a tiny configuration and mix, the
    manifest's metrics of set-up and the new entries pointed at the one cell,
    run once with ``--trace 1`` and ``--dump``."""
    root = tmp_path_factory.mktemp("bench-setup")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs/tiny-dense.json").write_text(json.dumps(TINY))
    (bench / "mixes/tiny-closed.json").write_text(json.dumps(CLOSED))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-dense", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-dense.json",
    }]
    manifest["workloads"] = [{
        "name": "tiny-dense.tiny-closed", "config": "tiny-dense",
        "traffic": "tiny-closed", "chips": 1, "why": "test",
    }]
    manifest["end_to_end"] = [
        dict(m, workloads=["tiny-dense.tiny-closed"]) if "workloads" in m else m
        for m in manifest["end_to_end"] if m["name"] != "out_tok_s"
    ]
    manifest["per_layer"] = [
        m for m in _with_entries(manifest)["per_layer"] if m["moves"] == "setup_s"
    ]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    spec = importlib.util.spec_from_file_location("bench_setup_manifest", bench / "manifest.py")
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
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    result["all_metrics"] = json.loads((root / "dump.json").read_text())["all_metrics"]
    return result


def test_the_dump_holds_them_with_no_entry_in_any_manifest(traced):
    """How they reach a reader until a ``benchmark`` issue names them:
    ``run.py --dump`` writes every reader's value, the tables too."""
    dumped = traced["all_metrics"]
    for name in NEW:
        assert dumped[name] == traced["metrics"][name]["value"]
    phases = dumped["boot_by_phase_s"]
    assert {"spawn", "enter", "engine_init", "kv_alloc", "server_start"} <= set(phases)
    assert phases["kv_alloc"] <= phases["engine_init"] <= phases["enter"]
    programs = dumped["compile_by_program_s"]
    assert "(window)" in programs and any("built_s" in row for row in programs.values())


def test_the_traced_cpu_run_prints_every_new_metric(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert set(NEW) <= set(value), sorted(set(NEW) - set(value))
    assert {"boot_s", "warmup_s", "compile_s"} <= set(value)
    assert -0.05 <= value["boot_unnamed_s"] <= 1.0
    named = sum(value[k] for k in ("boot_pre_spawn_s", "boot_spawn_s", "boot_attach_s",
                                   "boot_enter_s", "boot_unnamed_s"))
    assert named == pytest.approx(value["boot_s"], abs=0.05)
    assert value["boot_attach_s"] == 0  # the rehearsal's container attaches no chip
    assert 0 < value["boot_engine_init_s"] < value["boot_enter_s"]
    assert value["boot_pre_spawn_s"] > 0 and value["boot_spawn_s"] > 0
    assert value["warmup_requests_s"] == pytest.approx(value["warmup_s"] - 1.75)
    # counts and parts, not speeds: a CPU says nothing of the chip's
    assert value["compile_trace_lower_s"] > 0
    assert value["compile_xla_s"] >= 0 and value["compile_cache_load_s"] >= 0
    assert value["compile_eager_s"] >= 0 and 0 <= value["compile_cache_hit_pct"] <= 100
