"""The serving benchmark end to end on the CPU, at a tiny size.

A made-up configuration (dense and routed), two made-up mixes, a made-up
per-layer metric and a made-up *family* (a family file, a configuration that
names it, a cell) are added to a temporary copy of the benchmark as new
files, with manifest entries of their own and no edit to any file that was
there: the harness has to take them as data. Each run is a process of its
own, as the command's are (the load generator's process may not hold JAX).

What the runs show: the result line's keys; that the command refuses to
report without a TPU; that the int4 control comes out as not correct while
the program passes; that a token altered where the timed path produces it
turns ``correct`` false; and that the harness dispatches on the family a
configuration names: the made-up family's cell is ``correct``, and the same
served tokens held against the other family's reference are not.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SRC = ROOT / "benchmarks" / "serving"

TINY = {
    "name": "tiny-dense", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "vocab_size": 512, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "quantization": "int8",
    "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 64, "max_model_len": 256},
    # limits of the test's own configuration, from its own readings on the
    # CPU: sound runs 0.0 to 0.02, the int4 control 0.5 and more at its widest
    "check": {"served_gap_max": 0.15, "served_gap_mean": 0.03},
}
# The made-up family: the program's one layer under keys of its own, its
# weights and its reference from another stream of the seed than Llama's. The
# harness may read none of its keys but ``vocab_size``, ``engine``, ``check``.
ODD = {
    "name": "tiny-odd", "family": "made-up", "width": 128, "ffn_width": 256, "depth": 2,
    "q_heads": 4, "kv_groups": 2, "vocab_size": 512, "positions": 256, "eps": 1e-5,
    "theta": 10000.0, "quantization": "int8", "kv_dtype": "bfloat16", "reduced": [],
    "engine": TINY["engine"], "check": TINY["check"],
}
MADE_UP_FAMILY = '''
"""A made-up family: the Llama layer under other keys, another weight stream."""
import json
from pathlib import Path

import manifest

_llama = manifest.load_family({})
_STREAM = 1_000_003


def _as_llama(c):
    return {
        "hidden_size": c["width"], "intermediate_size": c["ffn_width"],
        "num_hidden_layers": c["depth"], "num_attention_heads": c["q_heads"],
        "num_key_value_heads": c["kv_groups"], "vocab_size": c["vocab_size"],
        "rms_norm_eps": c["eps"], "rope_theta": c["theta"],
        "quantization": c.get("quantization"), "kv_dtype": c.get("kv_dtype", "bfloat16"),
    }


def dims_of(config):
    return _llama.dims_of(_as_llama(config))


def make_tree(seed, dims):
    return _llama.make_tree(seed + _STREAM, dims)


def program_config(config_file):
    from modal_examples_tpu.models.llama import LlamaConfig

    c = json.loads(Path(config_file).read_text())
    return LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["width"], n_layers=c["depth"], n_heads=c["q_heads"],
        n_kv_heads=c["kv_groups"], ffn_dim=c["ffn_width"], rope_theta=c["theta"],
        norm_eps=c["eps"], max_seq_len=c["positions"],
    )


def logits_at(seed, dims, sequences, rows, bits=8):
    return _llama.logits_at(seed + _STREAM, dims, sequences, rows, bits)


def decode_step(config, batch, context_tokens):
    return _llama.decode_step(_as_llama(config), batch, context_tokens)


def prefill(config, prompt_lengths, calls):
    return _llama.prefill(_as_llama(config), prompt_lengths, calls)


SCOPE_WORK = {}
'''
# The same engine, the same weights, the same served tokens: held against the
# other family's reference (Llama's stream of the seed).
CROSSED_FAMILY = '''
"""The made-up family's program and weights, the Llama family's reference."""
import manifest

_made_up = manifest.load_family({"family": "made-up"})
dims_of, make_tree, program_config = _made_up.dims_of, _made_up.make_tree, _made_up.program_config
decode_step, prefill, SCOPE_WORK = _made_up.decode_step, _made_up.prefill, _made_up.SCOPE_WORK
logits_at = manifest.load_family({}).logits_at
'''
PACED = {
    "loop": "open", "rate_rps": 4,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8, "max": 200},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.4, "min": 4, "max": 12},
    "shared_prefix": {"tokens": 8, "variants": 2},
    "temperature": 0.7, "greedy_every": 2, "ramp_s": 1, "trace_s": 1, "check_samples": 3,
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
    "check_samples": 3,
}
DRIVER = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1] + "/benchmarks/serving/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
kw = json.loads(sys.argv[2])
print(json.dumps(run.run_cell(kw.pop("workload"), 2**31 + 17, kw.pop("seconds", 3.0), kw.pop("trace"),
                              root=run.Path(sys.argv[1]), require_tpu=False, **kw)))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with new files beside the old, none edited."""
    root = tmp_path_factory.mktemp("bench-copy")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-dense.json").write_text(json.dumps(TINY))
    (bench / "configs/tiny-moe.json").write_text(json.dumps(
        dict(TINY, name="tiny-moe", num_local_experts=4, num_experts_per_tok=2)
    ))
    (bench / "configs/tiny-odd.json").write_text(json.dumps(ODD))
    (bench / "configs/tiny-crossed.json").write_text(json.dumps(
        dict(ODD, name="tiny-crossed", family="made-up-crossed")
    ))
    (bench / "families/made-up.py").write_text(MADE_UP_FAMILY)
    (bench / "families/made-up-crossed.py").write_text(CROSSED_FAMILY)
    (bench / "mixes/tiny-paced.json").write_text(json.dumps(PACED))
    (bench / "mixes/tiny-closed.json").write_text(json.dumps(CLOSED))
    (bench / "layers/made_up.py").write_text(
        "METRICS = {'requests_scored': lambda run: float(len(run.scored))}\n"
    )
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    reason_cells = {w["name"] for w in manifest["workloads"] if w["traffic"] == "reason-closed"}
    manifest["configs"] = [
        {"name": n, "source": "made up for the test", "reduced": [], "why": "test",
         "file": f"benchmarks/serving/configs/{n}.json"}
        for n in ("tiny-dense", "tiny-moe", "tiny-odd", "tiny-crossed")
    ]
    manifest["workloads"] = [
        {"name": "tiny-dense.tiny-paced", "config": "tiny-dense", "traffic": "tiny-paced",
         "chips": 1, "why": "test"},
        {"name": "tiny-moe.tiny-closed", "config": "tiny-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny-odd.tiny-paced", "config": "tiny-odd", "traffic": "tiny-paced",
         "chips": 1, "why": "test"},
        {"name": "tiny-crossed.tiny-paced", "config": "tiny-crossed", "traffic": "tiny-paced",
         "chips": 1, "why": "test"},
    ]
    tiny_paced = [w["name"] for w in manifest["workloads"] if w["traffic"] == "tiny-paced"]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = (
                tiny_paced if set(metric["workloads"]) <= reason_cells
                else ["tiny-moe.tiny-closed"]
            )
    manifest["per_layer"].append({
        "name": "requests_scored", "unit": "reqs", "better": "higher",
        "source": "host_clock", "layer": "client view", "moves": "setup_s",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == data for p, data in before.items())
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_copy_manifest", bench / "manifest.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    assert copied.problems(manifest, root) == []
    return root


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), env.get("PYTHONPATH", "")])
    return env


def _run(root, **kw):
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(root), json.dumps(kw)],
        capture_output=True, text=True, timeout=600, env=_env(), cwd=str(root),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def paced(copy):
    return _run(copy, workload="tiny-dense.tiny-paced", trace=False)


@pytest.fixture(scope="module")
def closed_traced(copy):
    # a longer window than the paced run's: on a loaded machine a traced closed
    # loop of 3 clients has finished nothing to compare after 3 s
    return _run(copy, workload="tiny-moe.tiny-closed", trace=True, control=True, seconds=8.0)


def test_result_line_of_an_untraced_run(paced):
    result, stdout = paced
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == round(PACED["rate_rps"] * 3.0)
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result
    assert "compared served_gap_max:" in stdout and "(limit " in stdout


def test_traced_run_reports_the_layers_and_the_made_up_metric(closed_traced):
    result, _ = closed_traced
    names = set(result["metrics"])
    assert {"boot_s", "warmup_s", "closed.decode_batch_mean", "prefix_hit_pct",
            "kv_pages_peak_pct", "tpot_p50_obs_ms", "closed.ttft_p50_obs_ms",
            "closed.ttft_p90_obs_ms", "closed.tpot_p90_obs_ms", "closed.out_tok_obs_s",
            "requests_scored"} <= names
    # a traced line carries the per-layer metrics, and only this cell's variants
    assert "req_s" not in names and not any(n.startswith("reason.") for n in names)
    assert result["metrics"]["requests_scored"]["value"] == result["attempted"]
    assert 50 < result["metrics"]["prefix_hit_pct"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_int4_control_is_not_correct_where_the_program_is(closed_traced):
    result, _ = closed_traced
    compared = result["compared"]
    assert result["correct"] is True
    assert compared["served_gap_max"] <= TINY["check"]["served_gap_max"]
    assert compared["served_gap_mean"] <= TINY["check"]["served_gap_mean"]
    assert (
        compared["control_gap_max"] > TINY["check"]["served_gap_max"]
        or compared["control_gap_mean"] > TINY["check"]["served_gap_mean"]
    )
    assert compared["served_gap_p90"] <= compared["served_gap_max"] < compared["control_gap_max"]
    assert 0 <= compared["served_wide_decided_pct"] <= compared["control_wide_decided_pct"] <= 100


def test_a_token_altered_in_the_timed_path_is_not_correct(copy):
    result, _ = _run(
        copy, workload="tiny-dense.tiny-paced", trace=False,
        extra_env={"BENCH_BREAK_TIMED_PATH": "alter-token"},
    )
    assert result["correct"] is False
    assert result["compared"]["served_gap_max"] > TINY["check"]["served_gap_max"]


def test_a_family_added_as_files_is_served_and_checked_by_its_own_reference(copy, paced):
    result, stdout = _run(copy, workload="tiny-odd.tiny-paced", trace=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == round(PACED["rate_rps"] * 3.0)
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["compared"]["served_gap_max"] <= TINY["check"]["served_gap_max"]
    assert "compared served_gap_max:" in stdout
    # another stream of the seed: not the Llama family's run over again
    assert result["compared"]["served_gap_mean"] != paced[0]["compared"]["served_gap_mean"]


def test_the_other_familys_reference_finds_the_same_served_tokens_not_correct(copy):
    """The harness dispatches on the family the configuration names: the
    crossed family serves what the made-up family serves (its engine, its
    weights, the same seed and traffic) and checks it against Llama's
    reference."""
    result, _ = _run(copy, workload="tiny-crossed.tiny-paced", trace=False)
    assert result["failed"] == 0 and result["attempted"] == round(PACED["rate_rps"] * 3.0)
    assert result["correct"] is False
    assert result["compared"]["served_gap_max"] > TINY["check"]["served_gap_max"]
    assert result["compared"]["served_gap_mean"] > TINY["check"]["served_gap_mean"]


def test_the_command_refuses_to_report_without_a_tpu(copy):
    proc = subprocess.run(
        [sys.executable, "benchmarks/serving/run.py", "--workload", "tiny-dense.tiny-paced",
         "--seed", "5", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=_env(), cwd=str(copy),
    )
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
