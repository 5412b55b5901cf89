"""The SmallThinker family of the serving harness: the manifest with its
configuration and cell (every entry found by name, no position in a list
asserted), the cut as numbers, the family's reference against the program's
own, its three controls, its work functions against hand values at the
published widths, the new readers on a recorded reduced trace with and
without the scope and the counters, and a CPU rehearsal of the cell's path at
a tiny size whose contexts pass the window (a configuration of the family and
a small ``reason``-shaped mix added as files to a temporary copy of the
benchmark, none edited): ``App.run()`` -> ``@app.server`` -> ``LLMEngine``
behind ``serving/openai_api.py``, served, and compared with the family's own
reference, its int4 control and its ``no-window`` control.
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
NAME = "smallthinker-21b-a3b-int8-1chip"
CONFIG_FILE = SRC / "configs" / f"{NAME}.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
CELL = f"{NAME}.reason-long-closed"
NEW_METRICS = {
    "reason.window_attention_dev_pct", "reason.window_attention_roofline",
    "reason.window_kv_read_pct", "reason.kv_window_pages_peak_pct",
}


@pytest.fixture(scope="module")
def family():
    return M.load_family(CONFIG)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# -- the manifest ---------------------------------------------------------------------


def test_the_manifest_has_no_problems():
    """... but the one PERF.md section 7 (dd) names, which is another test's."""
    assert M.problems(MANIFEST, ROOT) == []
    assert M.family_problems("smallthinker") == []
    assert M.family_name(CONFIG) == "smallthinker"
    entry = _named(MANIFEST["configs"], NAME)
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmarks/serving/configs/{NAME}.json"
    cell = _named(MANIFEST["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-closed", 1)
    assert CELL in _named(MANIFEST["end_to_end"], "out_tok_s")["workloads"]
    sources = {
        "reason.window_attention_dev_pct": ("device_trace", "lower", "kernels and XLA ops"),
        "reason.window_attention_roofline": ("device_trace", "higher", "kernels and XLA ops"),
        "reason.window_kv_read_pct": ("program_counter", "lower", "kernels and XLA ops"),
        "reason.kv_window_pages_peak_pct": ("program_counter", "lower", "cache manager"),
    }
    assert set(sources) == NEW_METRICS
    for name, (source, better, layer) in sources.items():
        m = _named(MANIFEST["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s" and m["unit"] == "%"
        assert (m["source"], m["better"], m["layer"]) == (source, better, layer)


def test_the_cell_resolves_and_reports_its_metrics():
    info = M.resolve(MANIFEST, CELL, ROOT)
    assert {m["name"] for m in info["end_to_end"]} == {"out_tok_s", "setup_s"}
    names = {m["name"] for m in info["per_layer"]}
    readers = M.load_readers()
    assert all(M.quantity(n) in readers for n in names)
    assert NEW_METRICS <= names
    assert {"reason.decode_dev_ms", "reason.decode_roofline", "reason.prefill_roofline",
            "reason.hbm_peak_pct", "reason.attention_dev_pct", "reason.page_gather_dev_pct",
            "reason.expert_scan_dev_pct", "reason.req_obs_s", "reason.decode_kv_read_pct",
            "reason.kv_pages_peak_pct", "boot_s", "warmup_s", "compile_s"} <= names
    assert all(m["moves"] in ("out_tok_s", "setup_s") for m in info["per_layer"])
    # no prefix cache (refused over window layers), no dense layer, no per-slot state
    assert not {"reason.prefix_hit_pct", "reason.dense_mlp_dev_pct",
                "reason.state_rows_live_pct"} & names
    mix = info["mix"]
    assert (mix["clients"], mix["session"]["sessions"], info["cell"]["chips"]) == (32, 192, 1)


def test_the_cells_file_is_issue_41s_step_5():
    """32 closed-loop clients, one per slot; 192 sessions of one turn; prompt
    4097-6144 tokens with BOS (a system prompt of 32, one of 4, then
    4064-6111, uniform); answer 768-1280 (uniform: the lengths the issue
    allows where a request of 1024-1536 outlasts the window, as the chip
    said: PERF.md section 6); every other key the mix's."""
    mine = json.loads((SRC / "cells" / f"{CELL}.json").read_text())
    assert set(mine) == {"why", "clients", "session"}
    assert mine["clients"] == 32 == CONFIG["engine"]["max_slots"]
    assert mine["session"] == {
        "turns": 1, "sessions": 192, "pool": 4,
        "document": {"dist": "uniform", "min": 32, "max": 32},
        "question": {"dist": "uniform", "min": 4064, "max": 6111},
        "answer": {"dist": "uniform", "min": 768, "max": 1280},
    }
    mix = M.resolve(MANIFEST, CELL, ROOT)["mix"]
    narrow = json.loads((SRC / "mixes" / "reason-closed.json").read_text())
    for key in ("loop", "temperature", "greedy_every", "balance_block", "stagger_s", "ramp_s",
                "trace_s", "check_samples"):
        assert mix[key] == narrow[key]
    assert (mix["stagger_s"], mix["ramp_s"], mix["temperature"], mix["greedy_every"],
            mix["balance_block"], mix["check_samples"]) == (16, 20, 0.7, 4, 8, 4)
    import traffic

    lengths = traffic.prompt_lengths(mix, 51.0)
    # every prompt is past the window and is three chunk calls of at most 2048 rows
    assert 4096 < min(lengths) and max(lengths) <= 6144
    assert 6144 + 1280 == 7424 < CONFIG["engine"]["max_model_len"] == 8192


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every published number under its published key; only the depth and
    the vocabulary differ, and the file states the published counts."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(
            r for r in map(json.loads, catalog.read_text().splitlines())
            if r["name"] == "SmallThinker-21BA3B-Instruct"
        )
        differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differing == set(CONFIG["reduced"]) == {"num_hidden_layers", "vocab_size"}
        assert CONFIG["source"] == row["source_url"]
        assert {k: row["config"][k] for k in differing} == CONFIG["published"]
    assert CONFIG["published"] == {"num_hidden_layers": 52, "vocab_size": 151936}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert not any(M.reduced_problem(k) for k in CONFIG["reduced"])
    assert CONFIG["sliding_window_layout"] == CONFIG["rope_layout"] == [0, 1, 1, 1] * 13
    assert CONFIG["num_hidden_layers"] == 16  # the file keeps all 52 entries of both layouts
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["head_dim"], CONFIG["moe_ffn_hidden_size"], CONFIG["moe_num_primary_experts"],
            CONFIG["moe_num_active_primary_experts"], CONFIG["sliding_window_size"],
            CONFIG["rope_theta"], CONFIG["rms_norm_eps"], CONFIG["tie_word_embeddings"],
            CONFIG["moe_primary_router_apply_softmax"], CONFIG["norm_topk_prob"]) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 1500000, 1e-6, False, True, True)
    # the floors of model-configs section 4: four whole periods, every expert,
    # over an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] % 4 == 0
    assert CONFIG["vocab_size"] * 8 >= 151936 and CONFIG["vocab_size"] <= 32 ** 3
    ring = 4096 // 16 + 1
    assert CONFIG["engine"] == {
        "max_slots": 32, "page_size": 16, "n_pages": 16384, "n_window_pages": 1 + 32 * ring,
        "max_model_len": 8192, "enable_prefix_cache": False,
    }
    assert "check_control" not in CONFIG  # the benchmark's runs read the int4 control
    assert {"deployment", "assumed", "check", "check_why", "engine_why"} <= set(CONFIG)
    assert {"router_input", "window_convention", "secondary_experts", "activation", "weights",
            "qk_gain", "max_model_len"} <= set(CONFIG["assumed"])
    assert set(CONFIG["check"]) == {"served_gap_mean", "served_gap_p90"} <= set(CONFIG["check_why"])


def test_the_program_config_is_the_published_model_and_the_cut_is_its_arithmetic(family):
    cfg = family.program_config(str(CONFIG_FILE))
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.window_group, cfg.period) == (16, 4, (12, 4096), 4)
    assert cfg.cache_leaf_shapes == ((4, 128), (4, 128)) and cfg.chunk_offset_runtime
    engine = CONFIG["engine"]
    per_page = engine["page_size"] * 2 * 4 * 128 * 2  # K and V of 16 positions of a layer
    assert 4 * engine["n_pages"] * per_page == 2_147_483_648  # 2.15 GB: whole contexts, 4 layers
    assert 12 * engine["n_window_pages"] * per_page == 3_234_201_600  # 3.23 GB: 12 layers' rings
    assert 12 * 32 * (7680 // 16) * per_page == 6_039_797_760  # 6.0 GB had they kept whole contexts
    s = family.sizes(CONFIG)
    assert (s["layers"], s["global_layers"], s["window_layers"]) == (16, 4, 12)
    assert family.kv_bytes_per_position(s) == 2048
    held = family.held_weight_bytes(s)
    assert round(held / 1e9, 1) == 6.7 and 0.39 < held / (15.75 * 2**30) < 0.41
    total = held + 2_147_483_648 + 3_234_201_600
    assert 0.70 < total / (15.75 * 2**30) < 0.73  # weights + both page groups: 71% of the chip
    # the program's own count of the 16 layers: the int8 matrices, the bf16 rest
    assert abs(cfg.param_count - (held - 2 * 2560 * 32768 - 16 * 2560 * 64)) < 1e6


_NO_MODEL = """
import sys
import jax
jax.devices()  # a container has opened its backend by then
sys.path.insert(0, {src!r})
sys.modules["modal_examples_tpu.models.smallthinker"] = None  # a program from before the model
import manifest
family = manifest.load_family({{"family": "smallthinker"}})
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

ATTN = 2560 * (3584 + 512 + 512) + 3584 * 2560
EXPERT = 3 * 2560 * 768
ROUTERS = 16 * 2560 * 64
HEAD = 2560 * 32768
REACHED_32 = 64 * (1 - (58 / 64) ** 32)
HEADS = 4 * 28 * 128  # flops a query-key pair: q . k and p . v over 28 heads of 128


def test_sizes_by_hand(family):
    s = family.sizes(CONFIG)
    assert family.attn_params(s) == ATTN == 20_971_520
    assert family.expert_params(s) == EXPERT == 5_898_240  # 5.9 MB in int8
    assert family.active_params_per_token(s) == 16 * (ATTN + 6 * EXPERT) + ROUTERS == 904_396_800
    assert family.held_weight_bytes(s) == 16 * (ATTN + 64 * EXPERT) + 2 * (ROUTERS + 2 * HEAD)
    # ISSUE 41: at 32 live sequences 192 pairs reach 60.9 of a layer's 64 experts
    assert family.experts_reached(s, 32.0) == pytest.approx(REACHED_32)
    assert round(REACHED_32, 1) == 61.3  # 64 (1 - (58/64)^32); the issue's 60.9 took 63/64 a pair
    assert family.experts_reached(s, 1.0) == pytest.approx(6.0)
    assert family.window_pairs(s, [10]) == 55.0
    assert family.window_pairs(s, [5000]) == 4096 * 4097 / 2 + 904 * 4096


def test_decode_step_work_by_hand(family):
    """The weights once (of the experts those the batch reaches), every
    position in 4 layers, the window's 4096 in 12."""
    step = family.decode_step(CONFIG, 32.0, 32 * 5800.0)
    weights = 16 * (ATTN + EXPERT * REACHED_32) + 2 * (ROUTERS + HEAD)
    attended = 4 * 32 * 5800 + 12 * 32 * 4096
    assert step["bytes"] == pytest.approx(weights + 2048 * (attended + 16 * 32) + 32 * 2560 * 2)
    assert step["flops"] == pytest.approx(2 * (904_396_800 + HEAD) * 32 + HEADS * attended)
    # ISSUE 41: 5.8 GB of experts, 3.2 GB of window K/V, 1.5 GB of global K/V:
    # K/V 43% of a step's 11.0 GB, 13.5 ms at HBM's peak
    assert 5.7e9 < 16 * EXPERT * REACHED_32 < 5.9e9
    assert 12 * 32 * 4096 * 2048 == 3_221_225_472 and 1.5e9 < 4 * 32 * 5800 * 2048 < 1.6e9
    assert 10.9e9 < step["bytes"] < 11.2e9 and 13.3e-3 < step["bytes"] / 819e9 < 13.6e-3
    assert 0.42 < 2048 * attended / step["bytes"] < 0.44
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # bytes-bound on a v5e
    # a context inside the window: both kinds of layer read all of it
    short = family.decode_step(CONFIG, 4.0, 4 * 1000.0)
    assert short["flops"] == pytest.approx(2 * (904_396_800 + HEAD) * 4 + HEADS * 16 * 4000)


def test_prefill_work_by_hand(family):
    pre = family.prefill(CONFIG, [5000, 100], 3.0)
    whole = 5000 * 5001 / 2 + 100 * 101 / 2
    windowed = 4096 * 4097 / 2 + 904 * 4096 + 100 * 101 / 2
    reached = 64 * (1 - (58 / 64) ** 1700)
    assert pre["flops"] == pytest.approx(
        2 * 904_396_800 * 5100 + 2 * HEAD * 2 + HEADS * (4 * whole + 12 * windowed)
    )
    assert pre["bytes"] == pytest.approx(
        3 * (16 * (ATTN + EXPERT * reached) + 2 * (ROUTERS + HEAD)) + 16 * 2048 * 5100
    )


def test_scope_work_by_hand(family):
    work = family.SCOPE_WORK
    assert set(work) == {"mtpu.expert_scan", "mtpu.attention", "mtpu.window_attention"}
    scan = work["mtpu.expert_scan"](CONFIG, 32.0 * 100, 100.0)
    assert scan["flops"] == pytest.approx(2 * EXPERT * 6 * 3200 * 16)
    assert scan["bytes"] == pytest.approx(100 * 16 * EXPERT * REACHED_32 + 16 * 6 * 3200 * 2 * 2560 * 2)
    assert work["mtpu.expert_scan"](CONFIG, 0.0, 1.0) is None
    attention, window = work["mtpu.attention"], work["mtpu.window_attention"]
    # decode: the global layers read every position, the window layers min(context, 4096)
    decode = attention(CONFIG, 32.0, 1.0, positions=32 * 5800.0)
    assert decode["flops"] == pytest.approx(4 * HEADS * 32 * 5800)
    assert decode["bytes"] == pytest.approx(4 * 2048 * 32 * 5800)
    ring = window(CONFIG, 64.0, 2.0, contexts=[5800.0, 3000.0], steps=32.0)
    assert ring["flops"] == pytest.approx(12 * HEADS * (4096 + 3000) * 32)
    assert ring["bytes"] == pytest.approx(12 * 2048 * (4096 + 3000) * 32)
    # prefill: n (n + 1) / 2 pairs in a global layer, min(t + 1, 4096) a query in a window layer
    wide = (28 + 4 + 4 + 28) * 128
    pre = attention(CONFIG, 5000.0, 3.0, pairs=5000 * 5001 / 2)
    assert pre["flops"] == pytest.approx(4 * HEADS * 5000 * 5001 / 2)
    assert pre["bytes"] == pytest.approx(4 * 5000 * wide * 2)
    pre = window(CONFIG, 5000.0, 3.0, lengths=[5000])
    assert pre["flops"] == pytest.approx(12 * HEADS * (4096 * 4097 / 2 + 904 * 4096))
    assert pre["bytes"] == pytest.approx(12 * 5000 * wide * 2)
    assert attention(CONFIG, 32.0, 1.0) is None and window(CONFIG, 32.0, 1.0) is None


# -- the family's reference against the program's own -------------------------------------

TINY = {
    "name": "tiny-smallthinker", "family": "smallthinker", "model_name": "smallthinker_tiny",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 6, "num_key_value_heads": 2,
    # one period more than run: a file keeps the published lists whole
    "sliding_window_layout": [0, 1, 1, 1] * 3, "rope_layout": [0, 1, 1, 1] * 3,
    "num_hidden_layers": 8, "sliding_window_size": 32, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": None, "tie_word_embeddings": False,
    "vocab_size": 512, "max_position_embeddings": 512,
    "quantization": "int8", "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 96, "n_window_pages": 13,
               "max_model_len": 128, "prefill_buckets": [32, 64], "enable_prefix_cache": False},
    # the test's own limits, from its own readings on the CPU (int8 weights, bf16
    # activations and K/V against the float32 reference of the same int8 weights):
    # sound 0.02-0.04 / 0.06-0.11; int4 1.2 / 2.6; no-window 1.5 / 3.4
    "check": {"served_gap_mean": 0.3, "served_gap_p90": 0.8},
}


def _program_tree(tree):
    import jax

    from modal_examples_tpu.models.quantize import QuantizedWeight

    pair = lambda x: isinstance(x, dict) and set(x) == {"q", "scale"}  # noqa: E731
    return jax.tree.map(
        lambda leaf: QuantizedWeight(q=leaf["q"], scale=leaf["scale"]) if pair(leaf) else leaf,
        tree, is_leaf=pair,
    )


def test_the_familys_reference_is_the_programs_and_its_controls_move_it(family, tmp_path):
    """``logits_at`` (the family's own copy of the plain pass, on the
    family's seeded tree, a layer's weights made again alone, the chosen
    experts' rows only) against ``models/smallthinker_reference.forward`` on
    the same tree dequantised: two writings of one forward pass, float32
    ``highest`` both, so they agree to float32 rounding through 8 layers
    (2e-4: the embedding is unit size here, the logits' sums ten times
    LFM2's). Each of the three controls moves the logits by far more, the
    ``no-window`` one only past the window."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import smallthinker_reference as ref
    from modal_examples_tpu.models.quantize import QuantizedWeight, dequantize_weight

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    assert cfg.window_layout == (0, 1, 1, 1) * 2 and cfg.sliding_window == 32
    d = family.dims_of(TINY)
    assert d["windows"] == d["ropes"] == (0, 1, 1, 1) * 2 and d["control"] == "int4"
    tree = family.make_tree(7, d)
    assert set(tree) == {"embed", "lm_head", "final_norm", "layers"}
    assert tree["layers"]["moe_gate"]["q"].shape == (8, 8, 64, 32)
    assert tree["layers"]["moe_gate"]["q"].dtype == jnp.int8
    assert tree["layers"]["router"].dtype == tree["lm_head"].dtype == jnp.bfloat16
    # W_q and W_k at sqrt(3) times the unit scale, W_v at it (the family says why)
    scale = lambda name: float(jnp.mean(tree["layers"][name]["scale"]))  # noqa: E731
    assert scale("wq") / scale("wv") == pytest.approx(3**0.5, rel=0.02)
    assert scale("wk") / scale("wv") == pytest.approx(3**0.5, rel=0.05)
    plain = jax.tree.map(
        lambda a: dequantize_weight(a, jnp.float32) if isinstance(a, QuantizedWeight) else a,
        _program_tree(tree), is_leaf=lambda a: isinstance(a, QuantizedWeight),
    )
    ids = np.random.default_rng(0).integers(3, 512, size=96).astype(np.int32)
    rows = [5, 30, 40, 70, 95]
    (got,), (margins,), clock = family.logits_at(7, d, [ids], [rows])
    want = np.asarray(ref.forward(plain, jnp.asarray(ids), cfg))[rows]
    assert np.isfinite(margins).all() and (margins >= 0).all() and (margins <= 1).all()
    assert set(clock) == {"weights_s", "layers_s"}
    assert np.abs(want).max() > 1.0  # logits near N(0, 1), not near 0
    np.testing.assert_allclose(got, want, atol=2e-4)
    # the int4 control: other logits, by far more than float32 rounding
    (low,), _, _ = family.logits_at(7, d, [ids], [rows], bits=4)
    assert np.abs(low - want).max(axis=-1).min() > 0.05
    for control in ("no-window", "rope-everywhere"):
        d_c = family.dims_of(dict(TINY, check_control=control))
        (moved,), _, _ = family.logits_at(7, d_c, [ids], [rows], bits=4)
        theirs = np.asarray(ref.forward(plain, jnp.asarray(ids), cfg, control=control))[rows]
        np.testing.assert_allclose(moved, theirs, atol=2e-4)  # the two writings agree on it too
        assert np.abs(moved[2:] - want[2:]).max(axis=-1).min() > 0.05  # rows past the window
        (same,), _, _ = family.logits_at(7, d_c, [ids], [rows], bits=8)
        np.testing.assert_allclose(same, want, atol=2e-4)
        if control == "no-window":  # rows 5 and 30 see their whole context either way
            np.testing.assert_allclose(moved[:2], want[:2], atol=2e-4)
    with pytest.raises(ValueError, match="check_control"):
        family.dims_of(dict(TINY, check_control="no-such"))


def test_the_program_serves_the_familys_tree_as_the_reference_reads_it(family, tmp_path):
    """The seeded int8 tree through the program's own full forward (bf16
    activations, the quantised tiles, the flash kernel under the window)
    against the family's reference at rows of one sequence, most of them past
    the window: inside the rehearsal's limits by a wide margin."""
    import jax.numpy as jnp
    import numpy as np

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    d = family.dims_of(TINY)
    ids = np.random.default_rng(1).integers(3, 512, size=96).astype(np.int32)
    rows = list(range(8, 96))
    (want,), _, _ = family.logits_at(11, d, [ids], [rows])
    got = np.asarray(cfg.model.forward(
        _program_tree(family.make_tree(11, d)), jnp.asarray(ids)[None], cfg,
    ), np.float32)[0][rows]
    gap = want.max(-1) - want[np.arange(len(rows)), got.argmax(-1)]
    assert gap.mean() < TINY["check"]["served_gap_mean"] / 2
    assert np.quantile(gap, 0.9) < TINY["check"]["served_gap_p90"] / 2


# -- the new readers on a recorded reduced trace -------------------------------------------

KV = "mtpu_decode_kv_positions_total"


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
    assert trace["scopes"] and "mtpu.window_attention" not in trace["scopes"]
    trace["window_s"] = 4.0
    if scopes:
        trace["scopes"]["mtpu.window_attention"] = {"time_s": 1.2, "ops": 9000}
    trace["programs"] = {
        "jit__decode_block_fn": {"time_s": 3.2, "count": 16},
        "jit_prefill_chunk_pre4096": {"time_s": 0.5, "count": 3},
    }
    opened = {"mtpu_decode_steps_total": [({}, 0.0)], "mtpu_generated_tokens_total": [({}, 0.0)],
              "mtpu_ttft_seconds_count": [({}, 0.0)]}
    closed = {"mtpu_decode_steps_total": [({}, 1600.0)],
              "mtpu_generated_tokens_total": [({}, 51_230.0)],
              "mtpu_ttft_seconds_count": [({}, 30.0)]}
    if counters:
        series = lambda scale: [  # noqa: E731
            ({"kind": "read", "layers": "global"}, 250_000.0 * scale),
            ({"kind": "live", "layers": "global"}, 185_600.0 * scale),
            ({"kind": "table", "layers": "global"}, 262_144.0 * scale),
            ({"kind": "read", "layers": "window"}, 139_264.0 * scale),
            ({"kind": "live", "layers": "window"}, 131_072.0 * scale),
            ({"kind": "table", "layers": "window"}, 131_584.0 * scale),
        ]
        opened[KV], closed[KV] = series(10.0), series(1610.0)
        closed["mtpu_kv_window_pages_peak"] = [({}, 8224.0)]
        closed["mtpu_kv_window_pages_total"] = [({}, 8224.0)]
        closed["mtpu_kv_window_pages_used"] = [({}, 8000.0)]
    return rundata.RunData(
        cell={"name": CELL}, config=CONFIG, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0}, outcomes=[],
        scored=[_Scored(5000, 1200), _Scored(4200, 1400)],
        counters_open=opened, counters_close=closed, kv_pages_peak=None,
        engine_log={i: {"n_prompt": 5000, "first_token_at": 101.0 + i} for i in range(10)},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


class _Scored:
    """What the readers take of a scored request."""

    def __init__(self, prompt_tokens, n_out):
        self.ok, self.prompt_tokens, self.n_out = True, prompt_tokens, n_out


def test_the_new_readers_on_a_recorded_trace(family):
    readers = M.load_readers()
    run = _run(scopes=True, counters=True)
    total = sum(row["time_s"] for row in run.trace["scopes"].values())
    assert readers["window_attention_dev_pct"](run) == pytest.approx(100 * 1.2 / total)
    # what the window layers' steps read over the contexts those sequences held
    assert readers["window_kv_read_pct"](run) == pytest.approx(100 * 139_264 / 185_600)
    assert readers["kv_window_pages_peak_pct"](run) == pytest.approx(100.0)
    # the existing reader sums both groups: read / table over all 16 layers' tables
    assert readers["decode_kv_read_pct"](run) == pytest.approx(
        100 * (250_000 + 139_264) / (262_144 + 131_584)
    )
    # 16 blocks x 8 steps in the traced 4 s at a mean batch of 32, 3 chunk calls of
    # 10 prompts of 5000 over the window: the least times of the two phases add,
    # against 1.2 s under the scope
    batch = (51_230 - 30) / 1600
    assert batch == pytest.approx(32.0)
    scale = 51.0 / 4.0
    steps = 128 * scale
    work = family.SCOPE_WORK["mtpu.window_attention"]
    works = [
        work(CONFIG, 50_000.0, 3 * scale, lengths=[5000] * 10),
        work(CONFIG, batch * steps, steps, contexts=[5600.0, 4900.0], steps=steps * batch / 2),
    ]
    least = sum(max(w["flops"] / 197e12, w["bytes"] / 819e9) for w in works)
    assert readers["window_attention_roofline"](run) == pytest.approx(100 * least / (1.2 * scale))
    assert 20 < readers["window_attention_roofline"](run) < 100


def test_the_new_readers_read_null_never_zero_where_nothing_is_written():
    """A program from before the scope and the counters (the parent), or a
    model with no window group: None, so the result line leaves them out."""
    readers = M.load_readers()
    run = _run(scopes=False, counters=False)
    for name in NEW_METRICS:
        assert readers[M.quantity(name)](run) is None, name
    untraced = _run(scopes=True, counters=True)
    untraced.trace = None
    for name in ("window_attention_dev_pct", "window_attention_roofline"):
        assert readers[name](untraced) is None, name
    assert readers["window_kv_read_pct"](untraced) is not None
    # a model whose counter has no ``layers`` label (every other family's): no share
    plain = _run(scopes=True, counters=False)
    plain.counters_open[KV] = [({"kind": "read"}, 0.0), ({"kind": "live"}, 0.0)]
    plain.counters_close[KV] = [({"kind": "read"}, 9.0), ({"kind": "live"}, 5.0)]
    assert readers["window_kv_read_pct"](plain) is None
    # a family without the scope's work function (LFM2's): no roofline
    other = _run(scopes=True, counters=True)
    other.config = json.loads((SRC / "configs" / "lfm2-24b-a2b-int8-1chip.json").read_text())
    assert readers["window_attention_roofline"](other) is None


# -- a CPU rehearsal of the cell's path ----------------------------------------------------

REASON = {
    "loop": "closed", "clients": 4,
    "session": {
        "turns": 1, "sessions": 1200, "pool": 2,
        "document": {"dist": "uniform", "min": 8, "max": 8},
        # prompts of 41-64 with BOS: every one past the window of 32 from its first
        # decode step on, and inside the 64-row bucket (a chunk program a helper
        # thread is still building on this CPU would be built in the window;
        # tests/test_smallthinker.py serves chunked prompts)
        "question": {"dist": "uniform", "min": 32, "max": 55},
        "answer": {"dist": "uniform", "min": 40, "max": 60},
    },
    "temperature": 0.7, "greedy_every": 2, "stagger_s": 0.5, "ramp_s": 1.5, "trace_s": 1,
    "check_samples": 3,
}
DRIVER = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1] + "/benchmarks/serving/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
print(json.dumps(run.run_cell("tiny-smallthinker.tiny-reason-long", 2**31 + 23, 10.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False, control=True,
                              extra_env=json.loads(sys.argv[2]))))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A temporary copy of the benchmark with the tiny configuration (and a
    copy of it that names the ``no-window`` control), a small mix and a
    manifest of the one cell added as files, none edited."""
    root = tmp_path_factory.mktemp("bench-copy-smallthinker")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-smallthinker.json").write_text(json.dumps(TINY))
    (bench / "configs/tiny-smallthinker-no-window.json").write_text(
        json.dumps(dict(TINY, check_control="no-window"))
    )
    (bench / "mixes/tiny-reason-long.json").write_text(json.dumps(REASON))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-smallthinker", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-smallthinker.json",
    }]
    cell = "tiny-smallthinker.tiny-reason-long"
    manifest["workloads"] = [{
        "name": cell, "config": "tiny-smallthinker", "traffic": "tiny-reason-long", "chips": 1,
        "why": "test",
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
    other = copy / "benchmarks/serving/configs/tiny-smallthinker-no-window.json"
    return _rehearse(copy, {"BENCH_CONFIG_FILE": str(other)})


def test_the_rehearsed_cell_is_served_and_correct(rehearsal):
    result, stdout = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    compared = result["compared"]
    for name, limit in TINY["check"].items():
        assert compared[name] <= limit
    assert "compared served_gap_mean:" in stdout
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result


def _outside(compared: dict) -> list[str]:
    """The limits the control's reading lies outside."""
    return [n for n, limit in TINY["check"].items()
            if compared[n.replace("served", "control")] > limit]


def test_the_int4_control_reads_outside_the_limits(rehearsal):
    compared = rehearsal[0]["compared"]
    assert set(_outside(compared)) == set(TINY["check"])
    assert compared["control_gap_mean"] > 5 * max(compared["served_gap_mean"], 0.02)


def test_the_window_forgotten_reads_outside_the_limits(rehearsal_no_window):
    """The same path with the configuration's copy that names the
    ``no-window`` control: the served tokens are sound (the program is the
    same) and the control, the window layers attending to everything at the
    stated precision, lies outside every limit."""
    result, _ = rehearsal_no_window
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    assert set(_outside(compared)) == set(TINY["check"])
    assert compared["control_gap_mean"] > 5 * max(compared["served_gap_mean"], 0.02)


def test_the_rehearsal_reads_the_new_counters_and_leaves_trace_metrics_out(rehearsal):
    """On the CPU there is no device trace, so the scope's share and its
    roofline read nothing and the line leaves them out; the counters read."""
    metrics = rehearsal[0]["metrics"]
    # every sequence is past the window: a ring of 3 pages of 16 for each of 4 slots
    assert metrics["reason.kv_window_pages_peak_pct"]["value"] == pytest.approx(100.0)
    assert 0 < metrics["reason.window_kv_read_pct"]["value"]
    assert metrics["reason.decode_kv_read_pct"]["value"] > 0
    assert metrics["reason.compiles_in_window"]["value"] == 0
    assert "reason.prefix_hit_pct" not in metrics
    for name in ("reason.window_attention_dev_pct", "reason.window_attention_roofline"):
        assert name not in metrics
