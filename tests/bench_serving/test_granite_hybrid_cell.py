"""The Granite hybrid family of the serving harness: the manifest with its
configuration and cell, the family's reference against the program's own,
its work functions against hand values at the published widths, the new
readers on a recorded reduced trace with and without the scopes, and a CPU
rehearsal of the cell's path at a tiny size (a configuration of the family
and a small ``reason``-shaped mix added as files to a temporary copy of the
benchmark, none edited): ``App.run()`` -> ``@app.server`` -> ``LLMEngine``
behind ``serving/openai_api.py``, served, and compared with the family's own
reference and its control (the SSM state carried in bf16).
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
sys.path.insert(0, str(SRC))

import manifest as M  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG_FILE = SRC / "configs" / "granite-4.0-h-micro-bf16.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
CELL = "granite-4.0-h-micro-bf16.reason-wide-closed"
NEW_METRICS = {
    "reason.ssm_step_dev_pct", "reason.ssm_scan_dev_pct", "reason.ssm_proj_dev_pct",
    "reason.ssm_step_roofline", "reason.ssm_scan_roofline", "reason.state_rows_live_pct",
}


@pytest.fixture(scope="module")
def family():
    return M.load_family(CONFIG)


# -- the manifest ---------------------------------------------------------------------


def test_the_manifest_has_no_problems():
    assert M.problems(MANIFEST, ROOT) == []
    assert M.family_problems("granite_hybrid") == []
    assert M.family_name(CONFIG) == "granite_hybrid"


def test_the_cell_resolves_and_reports_its_metrics():
    info = M.resolve(MANIFEST, CELL, ROOT)
    assert {m["name"] for m in info["end_to_end"]} == {"out_tok_s", "setup_s"}
    names = {m["name"] for m in info["per_layer"]}
    readers = M.load_readers()
    assert all(M.quantity(n) in readers for n in names)
    assert NEW_METRICS <= names
    assert {"reason.decode_dev_ms", "reason.decode_roofline", "reason.hbm_peak_pct",
            "reason.dense_mlp_dev_pct", "reason.attention_dev_pct", "reason.req_obs_s",
            "reason.decode_kv_read_pct", "reason.kv_pages_peak_pct"} <= names
    assert all(m["moves"] in ("out_tok_s", "setup_s") for m in info["per_layer"])
    # no prefix cache (refused for per-slot state), no routed experts
    assert not {"reason.prefix_hit_pct", "reason.expert_scan_dev_pct"} & names
    # the traffic is ``reason-closed``'s by name (three of the benchmark's test files
    # know a cell's metrics by it: PERF.md section 7 (l)); the 64 clients and 768
    # sessions are the pair's own numbers, in the cell's file
    assert info["cell"]["traffic"] == "reason-closed"
    assert set(json.loads((SRC / "cells" / f"{CELL}.json").read_text())) == {"why", "clients", "session"}
    mix = info["mix"]
    assert (mix["clients"], mix["session"]["sessions"], info["cell"]["chips"]) == (64, 768, 1)
    # the length laws of reason-closed, from four times the clients
    narrow = json.loads((SRC / "mixes" / "reason-closed.json").read_text())
    assert mix["clients"] == 4 * narrow["clients"]
    assert mix["session"]["sessions"] == 4 * narrow["session"]["sessions"]
    for key in ("document", "question", "answer"):
        assert mix["session"][key] == narrow["session"][key]
    for key in ("temperature", "greedy_every", "balance_block", "stagger_s", "ramp_s",
                "trace_s", "check_samples"):
        assert mix[key] == narrow[key]


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every published number under its published key; only the vocabulary
    differs, and the file states the published count."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(
            r for r in map(json.loads, catalog.read_text().splitlines())
            if r["name"] == "granite-4.0-h-micro"
        )
        differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differing == set(CONFIG["reduced"]) == {"vocab_size"}
        assert CONFIG["source"] == row["source_url"]
        assert {k: row["config"][k] for k in differing} == CONFIG["published"]
    assert CONFIG["reduced"] == ["vocab_size"] and CONFIG["published"] == {"vocab_size": 100352}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert not any(M.reduced_problem(k) for k in CONFIG["reduced"])
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 40
    assert [i for i, t in enumerate(CONFIG["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert (CONFIG["hidden_size"], CONFIG["shared_intermediate_size"], CONFIG["mamba_n_heads"],
            CONFIG["mamba_d_head"], CONFIG["mamba_d_state"], CONFIG["mamba_d_conv"],
            CONFIG["mamba_expand"], CONFIG["mamba_chunk_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"]) == (2048, 8192, 64, 64, 128, 4, 2, 256, 32, 8)
    assert CONFIG["vocab_size"] * 8 >= 100352  # the floor: an eighth of the rows
    assert CONFIG["vocab_size"] <= 32 ** 3  # what tokenizer.py can spell
    assert CONFIG["engine"] == {"max_slots": 64, "page_size": 16, "n_pages": 6144,
                                "max_model_len": 2048, "enable_prefix_cache": False}


def test_the_program_config_is_the_published_model(family):
    cfg = family.program_config(str(CONFIG_FILE))
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.layer_types.count("mamba")) == (40, 4, 36)
    assert cfg.cache_leaf_shapes == ((4, 128), (4, 128))  # two K/V heads of 64 to a row
    assert cfg.state_leaves == (
        (36, (64, 64, 128), "float32"), (36, (3, 4352), "bfloat16"),
    )
    assert [s[2] for s in cfg.segments if s[0] == "mamba"] == [5, 9, 9, 9, 4]
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (0.015625, 12.0, 0.22, 8.0)
    assert 3.0e9 < cfg.param_count < 3.1e9  # 2.99 G in the layers + the 25088-row embedding
    engine = CONFIG["engine"]
    state = sum(n * engine["max_slots"] * _prod(shape) * (4 if dt == "float32" else 2)
                for n, shape, dt in cfg.state_leaves)
    assert state == 36 * 64 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 4_892_000_256
    pages = 2 * 4 * engine["n_pages"] * engine["page_size"] * 8 * 64 * 2
    assert pages == 805_306_368  # 8192 B a token over the 4 attention layers


def _prod(shape):
    out = 1
    for n in shape:
        out *= n
    return out


_NO_MODEL = """
import sys
import jax
jax.devices()  # a container has opened its backend by then
sys.path.insert(0, {src!r})
sys.modules["modal_examples_tpu.models.granite_hybrid"] = None  # a program from before the model
import manifest
family = manifest.load_family({{"family": "granite_hybrid"}})
try:
    family.program_config("unread.json")
except ImportError as e:
    print("raised", type(e).__name__)
"""


@pytest.mark.parametrize("in_container,code,said", [
    (True, 3, "cannot run the family's cells"), (False, 0, "raised ModuleNotFoundError"),
])
def test_a_program_without_the_model_fails_the_cell_and_leaves_no_container(
        in_container, code, said):
    """The check tries a new cell on the parent commit first: the failure
    has to leave nothing running (``families/deepseek_v2.py`` says why)."""
    env = {k: v for k, v in os.environ.items() if k != "MTPU_TASK_ID"}
    if in_container:
        env["MTPU_TASK_ID"] = "ta-test"
    proc = subprocess.run(
        [sys.executable, "-c", _NO_MODEL.format(src=str(SRC))],
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == code, proc.stderr[-2000:]
    assert said in proc.stdout + proc.stderr


# -- the work functions, by hand ----------------------------------------------------------

MIXER = 2048 * 8512 + 4096 * 2048  # in_proj [z 4096 | xBC 4352 | dt 64] and out_proj
ATTN = 2048 * (2048 + 2 * 512) + 2048 * 2048
MLP = 3 * 2048 * 8192
PER_TOKEN = 36 * MIXER + 4 * ATTN + 40 * MLP
HEAD = 2048 * 25088
STATE = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)  # a sequence's recurrent state, bytes


def test_sizes_by_hand(family):
    s = family.sizes(CONFIG)
    assert family.mixer_params(s) == MIXER == 25_821_184
    assert family.attn_params(s) == ATTN == 10_485_760
    assert family.mlp_params(s) == MLP == 50_331_648
    assert family.params_per_token(s) == PER_TOKEN == 2_984_771_584
    assert family.state_bytes_per_sequence(s) == STATE == 76_437_504  # 75.5 MB + the tails
    assert family.kv_bytes_per_token(s) == 4 * 2 * 8 * 64 * 2 == 8192
    assert family.weight_bytes(s) == (PER_TOKEN + HEAD) * 2 + 36 * 4352 * 5 * 2
    assert 6.0e9 < family.weight_bytes(s) < 6.2e9  # ISSUE 31: "6.1 GB of weights"


def test_decode_step_work_by_hand(family):
    """The weights once + per live sequence the state each way + live K/V."""
    s = family.sizes(CONFIG)
    step = family.decode_step(CONFIG, 63.0, 63 * 900.0)
    assert step["bytes"] == pytest.approx(
        family.weight_bytes(s) + 2 * STATE * 63 + 8192 * (63 * 900 + 63) + 63 * 2048 * 2
    )
    per_step = 5 * 64 * 64 * 128 + 2 * 4 * 4352
    assert step["flops"] == pytest.approx(
        2 * (PER_TOKEN + HEAD) * 63 + 36 * per_step * 63 + 4 * 4 * 32 * 64 * 63 * 900
    )
    # ISSUE 31: at 62-64 live slots ~16.5 GB a step, 9.7 of it the state
    assert 15.5e9 < step["bytes"] < 17.0e9
    assert 9.4e9 < 2 * STATE * 63 < 9.8e9
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # bytes-bound on a v5e


def test_prefill_work_by_hand(family):
    s = family.sizes(CONFIG)
    scan = 2 * 128.5 * (128 + 4096) + 2 * 4096 * 128 + 2 * 4096 * 128 + 2 * 4 * 4352
    assert family._scan_flops(s) == pytest.approx(scan)
    assert 3.0e6 < scan < 4.5e6  # ISSUE 31: "its scan about 4.2 MFLOP" a token and layer
    pre = family.prefill(CONFIG, [200, 100], 1.0)
    pairs = 200 * 201 / 2 + 100 * 101 / 2
    assert pre["flops"] == pytest.approx(
        2 * PER_TOKEN * 300 + 2 * HEAD * 2 + 36 * scan * 300 + 4 * 4 * 32 * 64 * pairs
    )
    assert pre["bytes"] == pytest.approx(family.weight_bytes(s) + 8192 * 300 + STATE * 2)


def test_scope_work_by_hand(family):
    work = family.SCOPE_WORK
    assert set(work) == {"mtpu.ssm_step", "mtpu.ssm_scan", "mtpu.attention", "mtpu.dense_mlp"}
    step = work["mtpu.ssm_step"](CONFIG, 63.0 * 100, 100.0)
    assert step["bytes"] == pytest.approx(2 * STATE * 6300)
    assert step["flops"] == pytest.approx(36 * (5 * 64 * 64 * 128 + 2 * 4 * 4352) * 6300)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12
    scan = work["mtpu.ssm_scan"](CONFIG, 640.0, 4.0)
    assert scan["flops"] == pytest.approx(36 * family._scan_flops(family.sizes(CONFIG)) * 640)
    assert scan["bytes"] == pytest.approx(36 * (2 * (2 * 4352 + 4096) + 4 * 64) * 640)
    mlp = work["mtpu.dense_mlp"](CONFIG, 63.0, 1.0)
    assert mlp["flops"] == pytest.approx(2 * 40 * MLP * 63)
    assert mlp["bytes"] == pytest.approx(40 * MLP * 2 + 40 * 63 * 2 * 2048 * 2)
    attention = work["mtpu.attention"]
    decode = attention(CONFIG, 63.0, 1.0, positions=63 * 900.0)
    assert decode["flops"] == pytest.approx(4 * 4 * 32 * 64 * 63 * 900)
    assert decode["bytes"] == pytest.approx(8192 * 63 * 900)
    prefill = attention(CONFIG, 300.0, 1.0, pairs=25150.0)
    assert prefill["flops"] == pytest.approx(4 * 4 * 32 * 64 * 25150)
    assert prefill["bytes"] == pytest.approx(4 * 300 * (32 + 16 + 32) * 64 * 2)
    assert attention(CONFIG, 63.0, 1.0) is None
    for name in ("mtpu.ssm_step", "mtpu.ssm_scan", "mtpu.dense_mlp"):
        assert work[name](CONFIG, 0.0, 1.0) is None


# -- the family's reference against the program's own -------------------------------------

TINY = {
    "name": "tiny-granite-hybrid", "family": "granite_hybrid", "model_type": "granitemoehybrid",
    "hidden_size": 64, "shared_intermediate_size": 128, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "attention", "mamba"],
    "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False, "position_embedding_type": "nope",
    "num_local_experts": 0, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.25, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 512, "max_position_embeddings": 512,
    "quantization": None, "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 96, "max_model_len": 256,
               "prefill_buckets": [32, 64], "enable_prefix_cache": False},
    # the test's own limits, from its own readings on the CPU (bf16 weights and
    # activations against the float32 reference of the same bf16 weights)
    "check": {"served_gap_max": 0.25, "served_gap_mean": 0.02},
}


def test_the_familys_reference_is_the_programs(family, tmp_path):
    """``logits_at`` (the family's own copy of the plain pass, on the
    family's seeded tree) against ``models/granite_hybrid_reference.forward``
    on the same tree: two writings of one forward pass, float32 ``highest``
    both, so they agree to float32 rounding through 6 layers (1e-4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import granite_hybrid_reference as ref

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    d = family.dims_of(TINY)
    tree = family.make_tree(7, d)
    assert set(tree) == {"embed", "final_norm", "mamba_layers", "attention_layers"}
    assert tree["mamba_layers"]["in_xbc"].shape == (4, 64, cfg.conv_dim)
    assert tree["attention_layers"]["wq"].shape == (2, 64, 64)
    assert tree["mamba_layers"]["gate"].dtype == jnp.bfloat16
    # layer 3 (a Mamba layer, the third of its kind) made alone is the tree's row
    alone = family._jitted().layer_weights(family.layer_key(7, d, 3), d=family._Frozen(d), kind="mamba")
    np.testing.assert_array_equal(
        np.asarray(alone["in_z"], np.float32), np.asarray(tree["mamba_layers"]["in_z"][2], np.float32)
    )
    ids = np.random.default_rng(0).integers(3, 512, size=40).astype(np.int32)
    rows = [5, 17, 39]
    (got,), (margins,), clock = family.logits_at(7, d, [ids], [rows])
    want = np.asarray(ref.forward(tree, jnp.asarray(ids), cfg))[rows]
    assert np.isinf(margins).all() and set(clock) == {"weights_s", "layers_s"}
    assert np.abs(want).max() > 1.0  # logits near N(0, 1), not near 0
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the control carries the state in bf16: other logits, by more than float32 rounding
    (low,), _, _ = family.logits_at(7, d, [ids], [rows], bits=4)
    assert 2e-4 < np.abs(low - want).max() < 0.5
    # the seeded decays: A a hundredth of the published initialisation's, dt the published
    A = np.exp(np.asarray(tree["mamba_layers"]["A_log"]))
    assert 0.01 <= A.min() and A.max() <= 0.16
    del jax


# -- the new readers on a recorded reduced trace -------------------------------------------


def _run(scopes: bool, counters: bool):
    import rundata

    import trace_reduce

    recording = json.loads((ROOT / "tests/bench_serving/recorded_trace_scoped.json").read_text())
    names, where = recording["names"], recording["scopes"]
    trace = trace_reduce.reduce_events({"lines": {}, "chips": {
        plane: {"modules": chip["modules"],
                "ops": [[names[n], start, dur, where[w]] for n, start, dur, w in chip["ops"]]}
        for plane, chip in recording["chips"].items()
    }})  # a chip recording of a program from before the scopes: none of them in it
    assert trace["scopes"] and not any(k.startswith("mtpu.ssm") for k in trace["scopes"])
    trace["window_s"] = 4.0
    base = dict(trace["scopes"])
    if scopes:
        base.update({
            "mtpu.ssm_step": {"time_s": 1.6, "ops": 900}, "mtpu.ssm_scan": {"time_s": 0.02, "ops": 60},
            "mtpu.ssm_proj": {"time_s": 0.4, "ops": 700},
        })
    trace["scopes"] = base
    trace["programs"] = {
        "jit__decode_block_fn": {"time_s": 3.0, "count": 15},
        "jit__prefill_and_sample": {"time_s": 0.3, "count": 6},
    }
    rows = "mtpu_state_rows_total"
    opened = {"mtpu_decode_steps_total": [({}, 0.0)], "mtpu_generated_tokens_total": [({}, 0.0)],
              "mtpu_ttft_seconds_count": [({}, 0.0)]}
    closed = {"mtpu_decode_steps_total": [({}, 1500.0)],
              "mtpu_generated_tokens_total": [({}, 94_600.0)],
              "mtpu_ttft_seconds_count": [({}, 100.0)]}
    if counters:
        opened[rows] = [({"kind": "stepped"}, 6400.0), ({"kind": "live"}, 6000.0)]
        closed[rows] = [({"kind": "stepped"}, 6400.0 + 96_000), ({"kind": "live"}, 6000.0 + 94_500)]
    return rundata.RunData(
        cell={"name": CELL}, config=CONFIG, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0}, outcomes=[], scored=[],
        counters_open=opened, counters_close=closed, kv_pages_peak=None,
        engine_log={i: {"n_prompt": 160, "first_token_at": 101.0 + i} for i in range(40)},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


def test_the_new_readers_on_a_recorded_trace():
    readers = M.load_readers()
    run = _run(scopes=True, counters=True)
    total = sum(row["time_s"] for row in run.trace["scopes"].values())
    assert readers["ssm_step_dev_pct"](run) == pytest.approx(100 * 1.6 / total)
    assert readers["ssm_scan_dev_pct"](run) == pytest.approx(100 * 0.02 / total)
    assert readers["ssm_proj_dev_pct"](run) == pytest.approx(100 * 0.4 / total)
    assert readers["state_rows_live_pct"](run) == pytest.approx(100 * 94_500 / 96_000)
    # 15 blocks x 8 steps in the traced 4 s, at a mean batch of 63: the state of 63
    # sequences each way a step at HBM's peak, against 1.6 s under the scope
    batch = (94_600 - 100) / 1500
    least = 2 * STATE * batch * 120 / 819e9
    assert readers["ssm_step_roofline"](run) == pytest.approx(100 * least / 1.6)
    assert 40 < readers["ssm_step_roofline"](run) < 100
    fam = M.load_family(CONFIG)
    work = fam.SCOPE_WORK["mtpu.ssm_scan"](CONFIG, 40 * 160.0, 6 * 51 / 4.0)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert readers["ssm_scan_roofline"](run) == pytest.approx(100 * least / (0.02 * 51 / 4.0))


def test_the_new_readers_read_null_never_zero_where_nothing_is_written():
    """A program from before the scopes and the counter (the parent), or a
    model without per-slot state: None, so the result line leaves them out."""
    readers = M.load_readers()
    run = _run(scopes=False, counters=False)
    for name in NEW_METRICS:
        assert readers[M.quantity(name)](run) is None, name
    untraced = _run(scopes=True, counters=True)
    untraced.trace = None
    for name in NEW_METRICS - {"reason.state_rows_live_pct"}:
        assert readers[M.quantity(name)](untraced) is None, name
    # a family without the scope's work function (Mistral's): no roofline
    other = _run(scopes=True, counters=True)
    other.config = json.loads((SRC / "configs" / "mistral-7b-int8.json").read_text())
    assert readers["ssm_step_roofline"](other) is None
    assert readers["ssm_scan_roofline"](other) is None


# -- a CPU rehearsal of the cell's path ----------------------------------------------------

REASON = {
    "loop": "closed", "clients": 4,
    "session": {
        "turns": 1, "sessions": 1200, "pool": 2,
        "document": {"dist": "uniform", "min": 8, "max": 8},
        "question": {"dist": "uniform", "min": 6, "max": 50},
        "answer": {"dist": "uniform", "min": 20, "max": 40},
    },
    "temperature": 0.7, "greedy_every": 2, "stagger_s": 0.5, "ramp_s": 1.5, "trace_s": 1,
    "check_samples": 3,
}
DRIVER = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1] + "/benchmarks/serving/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
print(json.dumps(run.run_cell("tiny-granite-hybrid.tiny-reason", 2**31 + 23, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False, control=True)))
"""


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-copy-granite")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-granite-hybrid.json").write_text(json.dumps(TINY))
    (bench / "mixes/tiny-reason.json").write_text(json.dumps(REASON))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-granite-hybrid", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-granite-hybrid.json",
    }]
    cell = "tiny-granite-hybrid.tiny-reason"
    manifest["workloads"] = [{
        "name": cell, "config": "tiny-granite-hybrid", "traffic": "tiny-reason", "chips": 1,
        "why": "test",
    }]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [cell] if CELL in metric["workloads"] else []
    manifest["end_to_end"] = [
        m for m in manifest["end_to_end"] if m.get("workloads", [cell])
    ]
    manifest["per_layer"] = [m for m in manifest["per_layer"] if m.get("workloads", [cell])]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == data for p, data in before.items())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(root)],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(root),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_the_rehearsed_cell_is_served_and_correct(rehearsal):
    result, stdout = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    compared = result["compared"]
    assert compared["served_gap_max"] <= TINY["check"]["served_gap_max"]
    assert compared["served_gap_mean"] <= TINY["check"]["served_gap_mean"]
    assert "compared served_gap_max:" in stdout
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result


def test_the_rehearsal_computes_the_bf16_state_control(rehearsal):
    """The control runs beside the comparison. At this size (6 layers, 512
    rows, ~90 served tokens of 20-40 a request) neither bf16 activations nor a
    bf16 state need move a first choice, so the gaps may read 0 both ways:
    that the control's logits differ by more than rounding is
    ``test_the_familys_reference_is_the_programs``, that a bf16 state breaks
    a tolerance ``tests/test_granite_hybrid.py``, and what the control reads
    at the published size is PERF.md section 6."""
    compared = rehearsal[0]["compared"]
    for key in ("control_gap_max", "control_gap_p90", "control_gap_mean"):
        assert compared[key] >= 0.0 and compared[key.replace("control", "served")] >= 0.0


def test_the_rehearsal_reads_the_new_counter_and_leaves_trace_metrics_out(rehearsal):
    """On the CPU there is no device trace, so the scope shares and the
    rooflines read nothing and the line leaves them out; the counter reads."""
    metrics = rehearsal[0]["metrics"]
    live = metrics["reason.state_rows_live_pct"]["value"]
    assert 30.0 < live <= 100.0  # 4 clients on 4 slots, less the gaps between requests
    assert metrics["reason.decode_kv_read_pct"]["value"] > 0
    assert metrics["reason.compiles_in_window"]["value"] == 0
    assert "reason.prefix_hit_pct" not in metrics
    for name in NEW_METRICS - {"reason.state_rows_live_pct"}:
        assert name not in metrics

