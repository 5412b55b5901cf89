"""The LFM2 family of the serving harness: the manifest with its configuration
and cell (every entry found by name, no position in a list asserted), the cut
as numbers, the family's reference against the program's own, its work
functions against hand values at the published widths, the new readers on a
recorded reduced trace with and without the scope and the counter, and a CPU
rehearsal of the cell's path at a tiny size (a configuration of the family
and a small ``reason``-shaped mix added as files to a temporary copy of the
benchmark, none edited): ``App.run()`` -> ``@app.server`` -> ``LLMEngine``
behind ``serving/openai_api.py``, served, and compared with the family's own
reference and both of its controls (int4 weights; the window not carried).
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
NAME = "lfm2-24b-a2b-int8-1chip"
CONFIG_FILE = SRC / "configs" / f"{NAME}.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
CELL = f"{NAME}.reason-wide-closed"
NEW_METRICS = {
    "reason.conv_mix_dev_pct", "reason.expert_dispatch_dev_pct",
    "reason.expert_scan_roofline", "reason.expert_tile_fill_pct",
}


@pytest.fixture(scope="module")
def family():
    return M.load_family(CONFIG)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# -- the manifest ---------------------------------------------------------------------


def test_the_manifest_has_no_problems():
    assert M.problems(MANIFEST, ROOT) == []
    assert M.family_problems("lfm2") == []
    assert M.family_name(CONFIG) == "lfm2"
    entry = _named(MANIFEST["configs"], NAME)
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmarks/serving/configs/{NAME}.json"
    cell = _named(MANIFEST["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-closed", 1)
    for name in NEW_METRICS:
        m = _named(MANIFEST["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s" and m["unit"] == "%"
    assert _named(MANIFEST["per_layer"], "reason.expert_tile_fill_pct")["source"] == "program_counter"


def test_the_cell_resolves_and_reports_its_metrics():
    info = M.resolve(MANIFEST, CELL, ROOT)
    assert {m["name"] for m in info["end_to_end"]} == {"out_tok_s", "setup_s"}
    names = {m["name"] for m in info["per_layer"]}
    readers = M.load_readers()
    assert all(M.quantity(n) in readers for n in names)
    assert NEW_METRICS <= names
    assert {"reason.decode_dev_ms", "reason.decode_roofline", "reason.prefill_roofline",
            "reason.hbm_peak_pct", "reason.dense_mlp_dev_pct", "reason.attention_dev_pct",
            "reason.page_gather_dev_pct", "reason.expert_scan_dev_pct", "reason.req_obs_s",
            "reason.decode_kv_read_pct", "reason.kv_pages_peak_pct",
            "reason.state_rows_live_pct", "boot_s", "warmup_s", "compile_s"} <= names
    assert all(m["moves"] in ("out_tok_s", "setup_s") for m in info["per_layer"])
    # no prefix cache (refused for per-slot state), no Mamba layers
    assert not {"reason.prefix_hit_pct", "reason.ssm_step_dev_pct"} & names
    # the traffic is ``reason-closed``'s by name (PERF.md section 7 (l)); the 64
    # clients and 768 sessions are the pair's own numbers, in the cell's file
    mix = info["mix"]
    assert (mix["clients"], mix["session"]["sessions"], info["cell"]["chips"]) == (64, 768, 1)


def test_the_cells_file_is_key_for_key_what_granites_lays():
    """ISSUE 39's cell: 64 closed-loop clients, one per slot; 768 sessions of
    one turn; prompt 65-256 tokens (a system prompt of 32, one of 4, then
    32-223, uniform); answer 512-1024 (uniform); every other key the mix's."""
    mine = json.loads((SRC / "cells" / f"{CELL}.json").read_text())
    granite = json.loads(
        (SRC / "cells" / "granite-4.0-h-micro-bf16.reason-wide-closed.json").read_text()
    )
    assert set(mine) == set(granite) == {"why", "clients", "session"}
    assert mine["clients"] == granite["clients"] == 64 == CONFIG["engine"]["max_slots"]
    assert mine["session"] == granite["session"] == {
        "turns": 1, "sessions": 768, "pool": 4,
        "document": {"dist": "uniform", "min": 32, "max": 32},
        "question": {"dist": "uniform", "min": 32, "max": 223},
        "answer": {"dist": "uniform", "min": 512, "max": 1024},
    }
    mix = M.resolve(MANIFEST, CELL, ROOT)["mix"]
    narrow = json.loads((SRC / "mixes" / "reason-closed.json").read_text())
    for key in ("loop", "temperature", "greedy_every", "balance_block", "stagger_s", "ramp_s",
                "trace_s", "check_samples"):
        assert mix[key] == narrow[key]
    assert (mix["stagger_s"], mix["ramp_s"], mix["temperature"], mix["greedy_every"],
            mix["balance_block"], mix["check_samples"]) == (16, 20, 0.7, 4, 8, 4)
    # every request of the mix keeps under 1280 positions of the 2048 the engine allows
    import traffic

    assert max(traffic.prompt_lengths(mix, 51.0)) <= 256
    assert 256 + 1024 <= 1280 < CONFIG["engine"]["max_model_len"]


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every published number under its published key; only the depth and
    the vocabulary differ, and the file states the published counts."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(
            r for r in map(json.loads, catalog.read_text().splitlines())
            if r["name"] == "LFM2-24B-A2B"
        )
        differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differing == set(CONFIG["reduced"]) == {"num_hidden_layers", "vocab_size"}
        assert CONFIG["source"] == row["source_url"]
        assert {k: row["config"][k] for k in differing} == CONFIG["published"]
    assert CONFIG["published"] == {"num_hidden_layers": 40, "vocab_size": 65536}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert not any(M.reduced_problem(k) for k in CONFIG["reduced"])
    kinds = CONFIG["layer_types"]
    assert len(kinds) == 40 and CONFIG["num_hidden_layers"] == 18  # the file keeps all 40
    assert [i for i, t in enumerate(kinds) if t == "full_attention"] == list(range(2, 40, 4))
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts"], CONFIG["num_experts_per_tok"], CONFIG["num_dense_layers"],
            CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"], CONFIG["conv_L_cache"],
            CONFIG["conv_bias"], CONFIG["use_expert_bias"], CONFIG["norm_topk_prob"],
            CONFIG["routed_scaling_factor"], CONFIG["norm_eps"]) == (
        2048, 11776, 1536, 64, 4, 2, 32, 8, 3, False, True, True, 1, 1e-5)
    assert CONFIG["rope_parameters"]["rope_theta"] == 1e6
    # the floors of model-configs section 4: four whole periods after the dense
    # layers, every expert, half the vocabulary (the floor is an eighth)
    assert (CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"]) % 4 == 0
    assert CONFIG["vocab_size"] * 8 >= 65536 and CONFIG["vocab_size"] <= 32 ** 3
    assert CONFIG["engine"] == {"max_slots": 64, "page_size": 16, "n_pages": 6144,
                                "max_model_len": 2048, "enable_prefix_cache": False}
    assert "check_control" not in CONFIG  # the benchmark's runs read the int4 control
    assert {"deployment", "assumed", "check", "check_why"} <= set(CONFIG)
    assert {"tie_word_embeddings", "weights", "router_bias", "embedding"} <= set(CONFIG["assumed"])


def test_the_program_config_is_the_published_model_and_the_cut_is_its_arithmetic(family):
    cfg = family.program_config(str(CONFIG_FILE))
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.n_moe_layers) == (18, 4, 16)
    assert cfg.cache_leaf_shapes == ((4, 128), (4, 128))  # two K/V heads of 64 to a row
    assert cfg.state_leaves == ((14, (2, 2048), "bfloat16"),)
    assert [s[4] for s in cfg.segments if s[0] == "conv"] == [2, 3, 3, 3, 3]
    engine = CONFIG["engine"]
    (n, shape, _dt), = cfg.state_leaves
    assert shape[0] * shape[1] * 2 == 8192  # 8 KB a window: a layer's and a slot's
    assert n * engine["max_slots"] * 8192 == 7_340_032  # 7 MB for all 64 slots
    pages = 2 * 4 * engine["n_pages"] * engine["page_size"] * 8 * 64 * 2
    assert pages == 805_306_368  # 8192 B a token over the 4 attention layers
    s = family.sizes(CONFIG)
    assert family.kv_bytes_per_token(s) == 8192
    assert family.window_bytes_per_sequence(s) == 14 * 8192
    assert round(family.held_weight_bytes(s) / 1e9, 1) == 10.2  # 60% of 15.75 GiB
    assert 0.59 < family.held_weight_bytes(s) / (15.75 * 2**30) < 0.62
    # the program's own count: the int8 matrices, the bf16 rest, the f32 bias
    assert abs(cfg.param_count - (family.held_weight_bytes(s) - 67_108_864 - 2_183_168)) < 2e6


_NO_MODEL = """
import sys
import jax
jax.devices()  # a container has opened its backend by then
sys.path.insert(0, {src!r})
sys.modules["modal_examples_tpu.models.lfm2"] = None  # a program from before the model
import manifest
family = manifest.load_family({{"family": "lfm2"}})
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

CONV = 2048 * 6144 + 2048 * 2048  # in_proj [B | C | u] and out_proj
ATTN = 2048 * (2048 + 2 * 512) + 2048 * 2048
DENSE = 3 * 2048 * 11776
EXPERT = 3 * 2048 * 1536
FIXED = 14 * CONV + 4 * ATTN + 2 * DENSE
ROUTERS = 16 * 2048 * 64
HEAD = 2048 * 32768
BF16 = ROUTERS + 14 * 3 * 2048 + HEAD  # routers, taps, the tied embedding
REACHED_62 = 64 * (1 - (60 / 64) ** 62)


def test_sizes_by_hand(family):
    s = family.sizes(CONFIG)
    assert family.conv_params(s) == CONV == 16_777_216
    assert family.attn_params(s) == ATTN == 10_485_760
    assert family.dense_params(s) == DENSE == 72_351_744
    assert family.expert_params(s) == EXPERT == 9_437_184  # 9.44 MB in int8
    assert family.active_params_per_token(s) == FIXED + ROUTERS + 16 * 4 * EXPERT == 1_027_604_480
    assert family.held_weight_bytes(s) == FIXED + 16 * 64 * EXPERT + 2 * BF16 == 10_223_788_032
    # ISSUE 39: at 62 live sequences 248 pairs land on 62.8 of a layer's 64 experts
    assert family.experts_reached(s, 62.0) == pytest.approx(REACHED_62)
    assert round(REACHED_62, 1) == 62.8
    assert family.experts_reached(s, 1.0) == pytest.approx(4.0)
    assert family.experts_reached(s, 0.0) == 0.0
    assert family.experts_reached(s, 4096.0) == pytest.approx(64.0)


def test_decode_step_work_by_hand(family):
    """The weights once (of the experts those the batch reaches) + live K/V +
    each live sequence's windows each way."""
    step = family.decode_step(CONFIG, 62.0, 62 * 900.0)
    weights = FIXED + 16 * EXPERT * REACHED_62 + 2 * BF16
    assert step["bytes"] == pytest.approx(
        weights + 8192 * (62 * 900 + 62) + 2 * 14 * 8192 * 62 + 62 * 2048 * 2
    )
    assert step["flops"] == pytest.approx(
        2 * (1_027_604_480 + HEAD) * 62 + 14 * 8 * 2048 * 62 + 4 * 4 * 32 * 64 * 62 * 900
    )
    # ISSUE 39: 9.5 of 9.9 GB of a step's bytes are the experts reached, a
    # roofline floor of 12.1 ms
    assert 9.4e9 < 16 * EXPERT * REACHED_62 < 9.6e9 and 10.0e9 < step["bytes"] < 10.6e9
    assert 12.0e-3 < step["bytes"] / 819e9 < 13.0e-3
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # bytes-bound on a v5e, at the needed flops


def test_prefill_work_by_hand(family):
    pre = family.prefill(CONFIG, [200, 100], 1.0)
    pairs = 200 * 201 / 2 + 100 * 101 / 2
    reached = 64 * (1 - (60 / 64) ** 300)
    assert pre["flops"] == pytest.approx(
        2 * 1_027_604_480 * 300 + 2 * HEAD * 2 + 14 * 8 * 2048 * 300 + 4 * 4 * 32 * 64 * pairs
    )
    assert pre["bytes"] == pytest.approx(
        FIXED + 16 * EXPERT * reached + 2 * BF16 + 8192 * 300 + 14 * 8192 * 2
    )


def test_scope_work_by_hand(family):
    work = family.SCOPE_WORK
    assert set(work) == {"mtpu.expert_scan", "mtpu.conv_mix", "mtpu.attention", "mtpu.dense_mlp"}
    # 100 decode steps at 62 live sequences: the real pairs' flops, the reached experts' bytes
    scan = work["mtpu.expert_scan"](CONFIG, 62.0 * 100, 100.0)
    assert scan["flops"] == pytest.approx(2 * EXPERT * 4 * 6200 * 16)
    assert scan["bytes"] == pytest.approx(
        100 * 16 * EXPERT * REACHED_62 + 16 * 4 * 6200 * 2 * 2048 * 2
    )
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12  # needed work: bandwidth-bound
    # ... where the padded tiles' products (62.8 x 64 rows a layer) are half the read's time
    padded = 2 * EXPERT * 16 * REACHED_62 * 64 * 100
    assert 0.45 < (padded / 197e12) / (scan["bytes"] / 819e9) < 0.6
    # a prefill call of 600 tokens reaches every expert
    chunk = work["mtpu.expert_scan"](CONFIG, 600.0, 1.0)
    assert chunk["bytes"] == pytest.approx(
        16 * EXPERT * 64 * (1 - (60 / 64) ** 600) + 16 * 4 * 600 * 2 * 2048 * 2
    )
    conv = work["mtpu.conv_mix"](CONFIG, 6200.0, 100.0)
    assert conv["flops"] == pytest.approx(14 * (2 * CONV + 8 * 2048) * 6200)
    assert conv["bytes"] == pytest.approx(
        100 * 14 * CONV + (2 * 14 * 8192 + 14 * 2 * 2048 * 2) * 6200
    )
    mlp = work["mtpu.dense_mlp"](CONFIG, 62.0, 1.0)
    assert mlp["flops"] == pytest.approx(2 * 2 * DENSE * 62)
    assert mlp["bytes"] == pytest.approx(2 * DENSE + 2 * 62 * 2 * 2048 * 2)
    attention = work["mtpu.attention"]
    decode = attention(CONFIG, 62.0, 1.0, positions=62 * 900.0)
    assert decode["flops"] == pytest.approx(4 * 4 * 32 * 64 * 62 * 900)
    assert decode["bytes"] == pytest.approx(8192 * 62 * 900)
    prefill = attention(CONFIG, 300.0, 1.0, pairs=25150.0)
    assert prefill["flops"] == pytest.approx(4 * 4 * 32 * 64 * 25150)
    assert prefill["bytes"] == pytest.approx(4 * 300 * (32 + 16 + 32) * 64 * 2)
    assert attention(CONFIG, 62.0, 1.0) is None
    for name in ("mtpu.expert_scan", "mtpu.conv_mix", "mtpu.dense_mlp"):
        assert work[name](CONFIG, 0.0, 1.0) is None


# -- the family's reference against the program's own -------------------------------------

TINY = {
    "name": "tiny-lfm2", "family": "lfm2", "model_type": "lfm2_moe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    # one layer more than run: a file keeps the published list whole
    "layer_types": ["conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "vocab_size": 512, "max_position_embeddings": 512,
    "quantization": "int8", "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 96, "max_model_len": 256,
               "prefill_buckets": [32, 64], "enable_prefix_cache": False},
    # the test's own limits, from its own readings on the CPU (int8 weights, bf16
    # activations and windows against the float32 reference of the same int8
    # weights): sound 0.0 / 0.018-0.028 / 0.0; int4 1.55 / 0.61 / 33.7; the window
    # not carried 3.80 / 2.26 / 70.4
    "check": {"served_gap_p90": 0.5, "served_gap_mean": 0.2, "served_wide_decided_pct": 10.0},
}


def _program_tree(tree):
    import jax

    from modal_examples_tpu.models.quantize import QuantizedWeight

    pair = lambda x: isinstance(x, dict) and set(x) == {"q", "scale"}  # noqa: E731
    return jax.tree.map(
        lambda leaf: QuantizedWeight(q=leaf["q"], scale=leaf["scale"]) if pair(leaf) else leaf,
        tree, is_leaf=pair,
    )


def test_the_familys_reference_is_the_programs(family, tmp_path):
    """``logits_at`` (the family's own copy of the plain pass, on the
    family's seeded tree, a layer's weights made again alone) against
    ``models/lfm2_reference.forward`` on the same tree dequantised: two
    writings of one forward pass, float32 ``highest`` both, so they agree to
    float32 rounding through 6 layers (1e-4). Both controls move the logits
    by far more; and the seeded tied head does not repeat its input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import lfm2_reference as ref
    from modal_examples_tpu.models.quantize import QuantizedWeight, dequantize_weight

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    d = family.dims_of(TINY)
    assert d["layer_types"] == tuple(TINY["layer_types"][:6]) and d["control"] == "int4"
    tree = family.make_tree(7, d)
    assert set(tree) == {"embed", "final_norm", "conv_layers", "attention_layers",
                         "dense_layers", "moe_layers"}
    assert tree["conv_layers"]["in_proj"]["q"].shape == (4, 64, 192)
    assert tree["moe_layers"]["moe_gate"]["q"].shape == (4, 8, 64, 32)
    assert tree["moe_layers"]["moe_gate"]["q"].dtype == jnp.int8
    assert tree["moe_layers"]["router_bias"].dtype == jnp.float32
    assert 0.01 < float(jnp.std(tree["moe_layers"]["router_bias"])) < 0.03  # 0.02: the family says why
    assert float(jnp.abs(tree["conv_layers"]["conv_w"].astype(jnp.float32)).max()) <= 0.5
    plain = jax.tree.map(
        lambda a: dequantize_weight(a, jnp.float32) if isinstance(a, QuantizedWeight) else a,
        _program_tree(tree), is_leaf=lambda a: isinstance(a, QuantizedWeight),
    )
    ids = np.random.default_rng(0).integers(3, 512, size=40).astype(np.int32)
    rows = [5, 17, 39]
    (got,), (margins,), clock = family.logits_at(7, d, [ids], [rows])
    want_all = np.asarray(ref.forward(plain, jnp.asarray(ids), cfg))
    want = want_all[rows]
    assert np.isfinite(margins).all() and (margins >= 0).all()
    assert set(clock) == {"weights_s", "layers_s"}
    assert np.abs(want).max() > 1.0  # logits near N(0, 1), not near 0
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the seeded embedding: a greedy choice is not the position's own token
    assert (want_all.argmax(-1) == ids).mean() < 0.1
    # the int4 control: other logits, by far more than float32 rounding
    (low,), _, _ = family.logits_at(7, d, [ids], [rows], bits=4)
    assert np.abs(low - want).max() > 0.05
    # the window not carried: rows after the first served one see an empty window,
    # the first served row (a prefill's last) the whole prompt
    d_nw = family.dims_of(dict(TINY, check_control="no-window"))
    (nw,), _, _ = family.logits_at(7, d_nw, [ids], [rows], bits=4)
    np.testing.assert_allclose(nw[0], want[0], atol=1e-4)
    assert np.abs(nw[1:] - want[1:]).max() > 0.05
    (same,), _, _ = family.logits_at(7, d_nw, [ids], [rows], bits=8)
    np.testing.assert_allclose(same, want, atol=1e-4)
    with pytest.raises(ValueError, match="check_control"):
        family.dims_of(dict(TINY, check_control="no-such"))


def test_the_program_serves_the_familys_tree_as_the_reference_reads_it(family, tmp_path):
    """The seeded int8 tree through the program's own full forward (bf16
    activations, the quantised tiles) against the family's reference at rows
    of one sequence: inside the rehearsal's limits by a wide margin."""
    import jax.numpy as jnp
    import numpy as np

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    d = family.dims_of(TINY)
    ids = np.random.default_rng(1).integers(3, 512, size=48).astype(np.int32)
    rows = list(range(8, 48))
    (want,), _, _ = family.logits_at(11, d, [ids], [rows])
    got = np.asarray(cfg.model.forward(
        _program_tree(family.make_tree(11, d)), jnp.asarray(ids)[None], cfg, attn_impl="xla",
    ), np.float32)[0][rows]
    gap = want.max(-1) - want[np.arange(len(rows)), got.argmax(-1)]
    assert gap.mean() < 0.1 and np.quantile(gap, 0.9) < 0.25


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
    }})  # a chip recording of a program from before the scope: it is not in it
    assert trace["scopes"] and "mtpu.conv_mix" not in trace["scopes"]
    trace["window_s"] = 4.0
    base = {k: v for k, v in trace["scopes"].items()
            if k not in ("mtpu.expert_scan", "mtpu.expert_dispatch")}
    if scopes:
        base.update({
            "mtpu.conv_mix": {"time_s": 0.12, "ops": 900},
            "mtpu.expert_scan": {"time_s": 2.4, "ops": 9000},
            "mtpu.expert_dispatch": {"time_s": 0.5, "ops": 9000},
        })
    trace["scopes"] = base
    trace["programs"] = {
        "jit__decode_block_fn": {"time_s": 3.6, "count": 18},
        "jit__prefill_and_sample": {"time_s": 0.3, "count": 6},
    }
    rows = "mtpu_expert_tile_rows_total"
    opened = {"mtpu_decode_steps_total": [({}, 0.0)], "mtpu_generated_tokens_total": [({}, 0.0)],
              "mtpu_ttft_seconds_count": [({}, 0.0)]}
    closed = {"mtpu_decode_steps_total": [({}, 1800.0)],
              "mtpu_generated_tokens_total": [({}, 111_700.0)],
              "mtpu_ttft_seconds_count": [({}, 100.0)]}
    if counters:
        opened[rows] = [({"kind": "pairs"}, 4000.0), ({"kind": "rows"}, 64_000.0)]
        closed[rows] = [({"kind": "pairs"}, 4000.0 + 7_142_400),
                        ({"kind": "rows"}, 64_000.0 + 115_800_000)]
    return rundata.RunData(
        cell={"name": CELL}, config=CONFIG, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0}, outcomes=[], scored=[],
        counters_open=opened, counters_close=closed, kv_pages_peak=None,
        engine_log={i: {"n_prompt": 160, "first_token_at": 101.0 + i} for i in range(40)},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


def test_the_new_readers_on_a_recorded_trace(family):
    readers = M.load_readers()
    run = _run(scopes=True, counters=True)
    total = sum(row["time_s"] for row in run.trace["scopes"].values())
    assert readers["conv_mix_dev_pct"](run) == pytest.approx(100 * 0.12 / total)
    assert readers["expert_dispatch_dev_pct"](run) == pytest.approx(100 * 0.5 / total)
    assert readers["expert_tile_fill_pct"](run) == pytest.approx(100 * 7_142_400 / 115_800_000)
    assert 6.0 < readers["expert_tile_fill_pct"](run) < 6.3
    # 18 blocks x 8 steps in the traced 4 s at a mean batch of 62, 6 prefill calls
    # of 40 prompts over the window: the least times of the two phases add,
    # against 2.4 s under the scope
    batch = (111_700 - 100) / 1800
    assert batch == pytest.approx(62.0)
    scale = 51.0 / 4.0
    works = [
        family.SCOPE_WORK["mtpu.expert_scan"](CONFIG, 40 * 160.0, 6 * scale),
        family.SCOPE_WORK["mtpu.expert_scan"](CONFIG, batch * 144 * scale, 144 * scale),
    ]
    least = sum(max(w["flops"] / 197e12, w["bytes"] / 819e9) for w in works)
    assert readers["expert_scan_roofline"](run) == pytest.approx(100 * least / (2.4 * scale))
    assert 60 < readers["expert_scan_roofline"](run) < 100


def test_the_new_readers_read_null_never_zero_where_nothing_is_written():
    """A program from before the scope and the counter (the parent), or a
    model that routes nothing: None, so the result line leaves them out."""
    readers = M.load_readers()
    run = _run(scopes=False, counters=False)
    for name in NEW_METRICS:
        assert readers[M.quantity(name)](run) is None, name
    untraced = _run(scopes=True, counters=True)
    untraced.trace = None
    for name in NEW_METRICS - {"reason.expert_tile_fill_pct"}:
        assert readers[M.quantity(name)](untraced) is None, name
    assert readers["expert_tile_fill_pct"](untraced) is not None
    # a family without the scope's work function (Granite's): no roofline
    other = _run(scopes=True, counters=True)
    other.config = json.loads((SRC / "configs" / "granite-4.0-h-micro-bf16.json").read_text())
    assert readers["expert_scan_roofline"](other) is None


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
print(json.dumps(run.run_cell("tiny-lfm2.tiny-reason", 2**31 + 23, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False, control=True,
                              extra_env=json.loads(sys.argv[2]))))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A temporary copy of the benchmark with the tiny configuration (and a
    copy of it that names the second control), a small mix and a manifest of
    the one cell added as files, none edited."""
    root = tmp_path_factory.mktemp("bench-copy-lfm2")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-lfm2.json").write_text(json.dumps(TINY))
    (bench / "configs/tiny-lfm2-no-window.json").write_text(
        json.dumps(dict(TINY, check_control="no-window"))
    )
    (bench / "mixes/tiny-reason.json").write_text(json.dumps(REASON))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-lfm2", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-lfm2.json",
    }]
    cell = "tiny-lfm2.tiny-reason"
    manifest["workloads"] = [{
        "name": cell, "config": "tiny-lfm2", "traffic": "tiny-reason", "chips": 1, "why": "test",
    }]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [cell] if CELL in metric["workloads"] else []
    manifest["end_to_end"] = [m for m in manifest["end_to_end"] if m.get("workloads", [cell])]
    manifest["per_layer"] = [m for m in manifest["per_layer"] if m.get("workloads", [cell])]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == data for p, data in before.items())
    return root


def _rehearse(root, extra_env: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(root), json.dumps(extra_env)],
        capture_output=True, text=True, timeout=900, env=env, cwd=str(root),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def rehearsal(copy):
    return _rehearse(copy, {})


@pytest.fixture(scope="module")
def rehearsal_no_window(copy):
    other = copy / "benchmarks/serving/configs/tiny-lfm2-no-window.json"
    return _rehearse(copy, {"BENCH_CONFIG_FILE": str(other)})


def test_the_rehearsed_cell_is_served_and_correct(rehearsal):
    result, stdout = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    compared = result["compared"]
    for name, limit in TINY["check"].items():
        assert compared[name] <= limit
    assert "compared served_gap_mean:" in stdout and "compared served_wide_decided_pct:" in stdout
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result


def _outside(compared: dict) -> list[str]:
    """The limits the control's reading lies outside."""
    return [n for n, limit in TINY["check"].items()
            if compared[n.replace("served", "control")] > limit]


def test_the_int4_control_reads_outside_the_limits(rehearsal):
    """The same requests read with every matmul weight requantised to int4:
    the token int4 puts first lies far below the reference's best."""
    compared = rehearsal[0]["compared"]
    assert set(_outside(compared)) == set(TINY["check"])
    assert compared["control_gap_mean"] > 5 * max(compared["served_gap_mean"], 0.02)


def test_the_window_not_carried_reads_outside_the_limits(rehearsal_no_window):
    """The same path with the configuration's copy that names the second
    control: the served tokens are sound (the program is the same) and the
    control, a decode step from an empty window at the stated precision,
    lies outside every limit."""
    result, _ = rehearsal_no_window
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    assert set(_outside(compared)) == set(TINY["check"])
    assert compared["control_gap_mean"] > 1.0


def test_the_rehearsal_reads_the_new_counter_and_leaves_trace_metrics_out(rehearsal):
    """On the CPU there is no device trace, so the scope shares and the
    rooflines read nothing and the line leaves them out; the counters read."""
    metrics = rehearsal[0]["metrics"]
    fill = metrics["reason.expert_tile_fill_pct"]["value"]
    # ~2 live tokens of 4 slots: 4 pairs a layer on 3-4 experts, a tile of 16 rows each
    assert 6.25 <= fill < 15.0
    assert 30.0 < metrics["reason.state_rows_live_pct"]["value"] <= 100.0
    assert metrics["reason.decode_kv_read_pct"]["value"] > 0
    assert metrics["reason.compiles_in_window"]["value"] == 0
    assert "reason.prefix_hit_pct" not in metrics
    for name in NEW_METRICS - {"reason.expert_tile_fill_pct"}:
        assert name not in metrics
