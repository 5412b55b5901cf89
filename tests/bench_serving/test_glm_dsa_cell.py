"""The GLM-5.2 family of the serving harness: the manifest with its
configuration and cell, the configuration's cut as asserted numbers, the
family's reference against the program's own, its work functions against hand
values at the published widths, the new readers on a recorded reduced trace
with and without the scopes, and a CPU rehearsal of the cell's path at a tiny
size (a configuration of the family and a small ``docqa``-shaped mix added as
files to a temporary copy of the benchmark, none edited): ``App.run()`` ->
``@app.server`` -> ``LLMEngine`` behind ``serving/openai_api.py``, served, and
compared with the family's own reference and its int4 control.
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
CONFIG_FILE = SRC / "configs" / "glm-5.2-int8-ep16.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
CELL = "glm-5.2-int8-ep16.docqa-long-closed"
NEW_METRICS = {
    "closed.indexer_dev_pct", "closed.topk_select_dev_pct", "closed.sparse_selected_pct",
    "closed.indexer_roofline", "closed.sparse_attention_roofline",
}


@pytest.fixture(scope="module")
def family():
    return M.load_family(CONFIG)


# -- (i) the manifest, the configuration, the cell ----------------------------------------


def test_the_manifest_has_no_problems():
    assert M.problems(MANIFEST, ROOT) == []
    assert M.family_problems("glm_dsa") == []
    assert M.family_name(CONFIG) == "glm_dsa"
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "glm-5.2-int8-ep16")
    assert entry == MANIFEST["configs"][-1] and MANIFEST["workloads"][-1]["name"] == CELL
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert [m["name"] for m in MANIFEST["per_layer"][-5:]] == [
        "closed.indexer_dev_pct", "closed.topk_select_dev_pct", "closed.sparse_selected_pct",
        "closed.indexer_roofline", "closed.sparse_attention_roofline",
    ]
    assert all(m["workloads"] == [CELL] and m["moves"] == "req_s" for m in MANIFEST["per_layer"][-5:])


def test_the_cell_resolves_and_reports_its_metrics():
    info = M.resolve(MANIFEST, CELL, ROOT)
    assert {m["name"] for m in info["end_to_end"]} == {"req_s", "setup_s"}
    names = {m["name"] for m in info["per_layer"]}
    readers = M.load_readers()
    assert all(M.quantity(n) in readers for n in names)
    assert NEW_METRICS <= names
    assert {"closed.decode_dev_ms", "closed.prefill_roofline", "closed.hbm_peak_pct",
            "closed.dense_mlp_dev_pct", "closed.expert_scan_dev_pct", "closed.expert_scan_roofline",
            "closed.latent_expand_dev_pct", "closed.expert_dispatch_dev_pct",
            "closed.expert_held_pct", "prefix_hit_pct", "closed.compiles_in_window"} <= names
    # the dense pairs of layers/latent.py's reader are not this model's attention
    assert "closed.attention_roofline" not in names
    assert all(m["moves"] in ("req_s", "setup_s") for m in info["per_layer"])
    # every list the three docqa cells share has the cell at its end
    docqa = [w["name"] for w in MANIFEST["workloads"] if w["traffic"] == "docqa-closed"]
    assert docqa[-1] == CELL and len(docqa) == 4
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if set(docqa[:3]) <= set(metric.get("workloads", ())):
            assert metric["workloads"][-1] == CELL, metric["name"]
    assert info["cell"] == {
        "name": CELL, "config": "glm-5.2-int8-ep16", "traffic": "docqa-closed", "chips": 1,
        "why": info["cell"]["why"],
    }


def test_the_cells_mix_is_key_for_key_what_issue_34_wrote():
    """The traffic is ``docqa-closed``'s by name (three of the benchmark's
    test files know ``req_s`` by it: PERF.md section 7 (l)); the pair's own
    numbers sit in the cell's file, every other key is the mix's. Two length
    laws are ISSUE 34's adjusted as its step 10 allows for ``req_s``'s spread
    (PERF.md section 6, PR 34): the documents three quarters as long, the
    answers' law narrower about the same median."""
    import traffic

    mix = M.resolve(MANIFEST, CELL, ROOT)["mix"]
    own = json.loads((SRC / "cells" / f"{CELL}.json").read_text())
    assert set(own) == {"why", "clients", "session", "stagger_s"}
    assert mix["loop"] == "closed" and mix["clients"] == 16
    assert mix["session"] == {
        "turns": 4, "sessions": 64, "pool": 16,
        # ISSUE 34: median 11264, sigma 0.2, 8192-16384
        "document": {"dist": "lognormal", "median": 8448, "sigma": 0.2, "min": 6144, "max": 12288},
        "question": {"dist": "uniform", "min": 24, "max": 64},
        # ISSUE 34: median 80, sigma 0.3, 48-128
        "answer": {"dist": "lognormal", "median": 80, "sigma": 0.1, "min": 64, "max": 100},
    }
    issue = {"median": 11264, "min": 8192, "max": 16384}
    assert all(mix["session"]["document"][k] * 4 == issue[k] * 3 for k in issue)
    assert (mix["stagger_s"], mix["ramp_s"], mix["temperature"], mix["greedy_every"],
            mix["balance_block"], mix["trace_s"], mix["check_samples"]) == (16, 20, 0.7, 4, 8, 6, 4)
    narrow = json.loads((SRC / "mixes" / "docqa-closed.json").read_text())
    for key in ("loop", "ramp_s", "temperature", "greedy_every", "balance_block", "trace_s",
                "check_samples"):
        assert mix[key] == narrow[key]
    # with 4 turns a session and every 4th request greedy, the greedy requests are the
    # sessions' first questions: the 16 the clients send in the ramp come back inside
    # the window (PERF.md section 6, PR 34), more than the 4 the reference reads
    sessions = traffic.closed_loop(mix, 5, 19360)
    greedy_turns = {r.turn for s in sessions for r in s if r.temperature == 0.0}
    assert greedy_turns == {0} and mix["clients"] >= 4 * mix["check_samples"]
    lengths = traffic.prompt_lengths(mix, 51)
    assert 6144 + 24 < min(lengths) and max(lengths) < CONFIG["engine"]["max_model_len"] - 128
    assert min(lengths) > CONFIG["index_topk"] * 3  # a query keeps at most a third


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every published number under its published key; the four cut keys
    differ, and the file states the published counts."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == "GLM-5.2")
        # every key, the two lists of layer kinds whole among them: only the
        # four cut keys differ from the published file
        differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differing == set(CONFIG["reduced"])
        assert CONFIG["source"] == row["source_url"]
        assert {k: row["config"][k] for k in differing} == CONFIG["published"]
        assert len(CONFIG["indexer_types"]) == len(CONFIG["mlp_layer_types"]) == 78
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                                 "num_nextn_predict_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 78, "n_routed_experts": 256,
                                   "vocab_size": 154880, "num_nextn_predict_layers": 1}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert not any(M.reduced_problem(k) for k in CONFIG["reduced"])
    # the eight layers run are published layers 2-9: the file names the stretch,
    # the model and the family take the kinds of it from the published lists
    assert CONFIG["layer_range"] == [2, 10] and CONFIG["first_k_dense_replace"] == 3
    sys.path.insert(0, str(SRC / "families"))
    import glm_dsa as family_module

    assert family_module.layer_pattern(CONFIG) == (
        ("full", "shared", "shared", "shared") * 2, ("dense",) + ("sparse",) * 7)
    ruled = {k: v for k, v in CONFIG.items() if k not in ("indexer_types", "mlp_layer_types")}
    assert family_module.layer_pattern(ruled) == family_module.layer_pattern(CONFIG)
    assert CONFIG["expert_share"] == {"of": 256, "offset": 0, "chips_per_layer": 16}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"], CONFIG["vocab_size"],
            CONFIG["num_nextn_predict_layers"]) == (8, 16, 19360, 0)
    # no width is cut
    assert (CONFIG["hidden_size"], CONFIG["q_lora_rank"], CONFIG["kv_lora_rank"],
            CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"],
            CONFIG["num_attention_heads"], CONFIG["index_n_heads"], CONFIG["index_head_dim"],
            CONFIG["index_topk"], CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["n_shared_experts"]) == (
        6144, 2048, 512, 192, 64, 256, 64, 32, 128, 2048, 12288, 2048, 8, 1)
    assert CONFIG["vocab_size"] * 8 >= 154880 and CONFIG["vocab_size"] <= 32 ** 3
    assert CONFIG["n_routed_experts"] >= 8  # the floor
    assert CONFIG["engine"] == {"max_slots": 16, "page_size": 16, "n_pages": 24576,
                                "max_model_len": 18432}
    assert set(CONFIG["assumed"]) >= {"indexer", "router_bias", "max_model_len", "weights"}
    assert "sixteen" in CONFIG["deployment"] and set(CONFIG["check"]) <= {
        "served_gap_p90", "served_gap_mean", "served_wide_decided_pct", "served_gap_max"}


def test_the_cuts_bytes_are_what_issue_34_reckoned(family):
    cfg = family.program_config(str(CONFIG_FILE))
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.full_layers) == (8, 1, (0, 4))
    assert cfg.cache_leaf_shapes == ((1, 512), (1, 64), (1, 128))
    assert cfg.cache_leaf_layers == (8, 8, 2)
    s = family.sizes(CONFIG)
    assert family.attn_params(s) == 165_019_648  # 12.6 + 33.6 + 3.5 + 14.7 + 100.7 M
    assert family.indexer_params(s) + 6144 * 32 == 9_371_648  # 9.4 M in a full layer
    assert family.expert_params(s) == 37_748_736  # each routed expert, and the shared one
    assert 3 * 6144 * 12288 == 226_492_416  # the dense layer's SwiGLU
    # weights: int8 matrices, bf16 router, embedding and head
    routed_layer = 165_019_648 + 37_748_736 * 17 + 6144 * 256
    assert 7 * routed_layer == 5_658_247_168  # 5.66 GB
    total = (165_019_648 + 226_492_416) + 7 * routed_layer + 2 * 9_371_648
    assert 6.0e9 < total + 19360 * 6144 * 3 < 6.5e9  # 6.4 GB with the embedding (bf16) and head
    # cache: 9728 B a token, 24576 pages of 16
    per_token = 8 * (512 + 64) * 2 + 2 * 128 * 2
    assert per_token == 9728 == family.kv_bytes_per_token(s) + family.index_key_bytes_per_token(s)
    engine = CONFIG["engine"]
    assert engine["n_pages"] * engine["page_size"] == 393_216
    assert per_token * 393_216 == 3_825_205_248  # 3.83 GB
    assert 16 * 15462 < 393_216  # sixteen of the longest prompts fit, and the pool's 16 documents


_NO_MODEL = """
import sys
import jax
jax.devices()  # a container has opened its backend by then
sys.path.insert(0, {src!r})
sys.modules["modal_examples_tpu.models.glm_dsa"] = None  # a program from before the model
import manifest
family = manifest.load_family({{"family": "glm_dsa"}})
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


# -- (j) the work functions, by hand -------------------------------------------------------

ATTN = 165_019_648
INDEX = 9_371_648  # W_Iq 2048 x 4096, W_Ik 6144 x 128, W_Iw 6144 x 32
EXPERT = 37_748_736
DENSE = 226_492_416
ROUTER = 6144 * 256
HEAD = 6144 * 19360
FIXED = 8 * ATTN + 2 * INDEX + DENSE + 7 * (EXPERT + ROUTER)  # every token's matmuls
ACTIVE = FIXED + 7 * EXPERT * 8 * 16 / 256  # ... with its routed pairs that land here
EXPANDED = 2 * 64 * (256 + 256)  # flops a query-key pair, prefill
ABSORBED = 2 * 64 * (576 + 512)  # flops a position, decode
SCORED = 2 * 32 * 128  # flops a scored pair, an indexer


def test_sizes_by_hand(family):
    s = family.sizes(CONFIG)
    assert (s["layers"], s["dense_layers"], s["moe_layers"], s["full_layers"]) == (8, 1, 7, 2)
    assert family._dense_params_per_token(s) == FIXED
    assert family.active_params_per_token(s) == ACTIVE
    assert family.held_share(s) == 1 / 16
    assert family.kv_bytes_per_token(s) == 8 * 576 * 2 == 9216
    assert family.index_key_bytes_per_token(s) == 2 * 128 * 2 == 512
    # 2048 tokens reach every held expert, 12 a third of them
    assert family.experts_reached(s, 2048) == pytest.approx(16.0)
    assert family.experts_reached(s, 12) == pytest.approx(16 * (1 - (1 - 8 / 256) ** 12))
    bf16 = 7 * ROUTER + 2 * 6144 * 32
    assert family.weight_bytes(s, 2048) == pytest.approx(
        FIXED - bf16 + 2 * bf16 + 7 * EXPERT * 16 + HEAD)
    assert 6.0e9 < family.weight_bytes(s, 2048) < 6.3e9
    assert family.selected_pairs(0, 5000, 2048) == 2048 * 2049 / 2 + (5000 - 2048) * 2048
    assert family.selected_pairs(3000, 5000, 2048) == 2000 * 2048
    assert family.selected_pairs(0, 100, 2048) == 100 * 101 / 2
    assert family.causal_pairs(3000, 5000) == sum(range(3001, 5001))


def test_decode_step_work_by_hand(family):
    """The weights once; every indexer scores each context whole (its keys
    read once), attention runs over 2048 selected latents a sequence."""
    s = family.sizes(CONFIG)
    step = family.decode_step(CONFIG, 12.0, 12 * 11500.0)
    assert step["flops"] == pytest.approx(
        2 * (ACTIVE + HEAD) * 12 + 8 * ABSORBED * 12 * 2048 + 2 * SCORED * 12 * 11500)
    assert step["bytes"] == pytest.approx(
        family.weight_bytes(s, 12) + 9216 * (12 * 2048 + 12) + 512 * (12 * 11500 + 12)
        + 12 * 6144 * 2)
    # ISSUE 34: 0.30 GB of selected latents a step where dense attention would read 1.8
    assert 9216 * 12 * 2048 == pytest.approx(0.23e9, rel=0.05)
    assert 9216 * 12 * 11500 == pytest.approx(1.27e9, rel=0.05)
    assert 512 * 12 * 11500 == pytest.approx(0.07e9, rel=0.05)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # bytes-bound on a v5e
    short = family.decode_step(CONFIG, 4.0, 4 * 500.0)  # under the top-k: every position
    assert short["flops"] == pytest.approx(
        2 * (ACTIVE + HEAD) * 4 + 8 * ABSORBED * 2000 + 2 * SCORED * 2000)


def test_prefill_work_by_hand(family):
    s = family.sizes(CONFIG)
    pre = family.prefill(CONFIG, [11400, 1000], 7.0)
    selected = 2048 * 2049 / 2 + (11400 - 2048) * 2048 + 1000 * 1001 / 2
    scored = 11400 * 11401 / 2 + 1000 * 1001 / 2
    assert pre["flops"] == pytest.approx(
        2 * ACTIVE * 12400 + 2 * HEAD * 2 + 8 * EXPANDED * selected + 2 * SCORED * scored)
    assert pre["bytes"] == pytest.approx(7 * family.weight_bytes(s, 12400 / 7) + 9728 * 12400)
    # ISSUE 34: a prompt of 11.4k tokens is ~44 TFLOP of matrix products, ~1 of index
    # scores and ~12 of attention over the selection (34 over every causal pair)
    assert 2 * ACTIVE * 11400 == pytest.approx(43e12, rel=0.1)
    assert 2 * SCORED * 11400 * 11401 / 2 == pytest.approx(1.06e12, rel=0.05)
    assert 8 * EXPANDED * (2048 * 2049 / 2 + 9352 * 2048) == pytest.approx(11.1e12, rel=0.05)
    assert 8 * EXPANDED * 11400 * 11401 / 2 == pytest.approx(34e12, rel=0.05)


def test_scope_work_by_hand(family):
    work = family.SCOPE_WORK
    assert set(work) == {"mtpu.expert_scan", "mtpu.attention", "mtpu.indexer"}
    scan = work["mtpu.expert_scan"](CONFIG, 2048.0 * 6, 6.0)
    pairs = 8 / 16 * 2048 * 6
    assert scan["flops"] == pytest.approx(2 * EXPERT * pairs * 7)
    assert scan["bytes"] == pytest.approx(6 * 7 * EXPERT * 16 + 7 * pairs * 2 * 6144 * 2)
    attention = work["mtpu.attention"]
    pre = attention(CONFIG, 11400.0, 6.0, phase="prefill", selected=2.0e7)
    assert pre["flops"] == pytest.approx(8 * EXPANDED * 2.0e7)
    assert pre["bytes"] == pytest.approx(8 * 11400 * 64 * (2 * 256 + 2 * 256) * 2)
    dec = attention(CONFIG, 12.0 * 100, 100.0, phase="decode", selected=12 * 100 * 2048.0)
    assert dec["flops"] == pytest.approx(8 * ABSORBED * 12 * 100 * 2048)
    assert dec["bytes"] == pytest.approx(9216 * 12 * 100 * 2048)
    # the dense counts of a full attention's reader are not this model's
    assert attention(CONFIG, 100.0, 1.0, pairs=5050.0) is None
    assert attention(CONFIG, 100.0, 1.0, positions=5050.0) is None
    index = work["mtpu.indexer"](CONFIG, 11400.0, 6.0, scored=6.5e7, keys=11400.0)
    assert index["flops"] == pytest.approx(2 * (2 * INDEX * 11400 + SCORED * 6.5e7))
    assert index["bytes"] == pytest.approx(
        6 * 2 * (INDEX - 6144 * 32 + 6144 * 32 * 2) + 512 * 11400)
    assert work["mtpu.indexer"](CONFIG, 100.0, 1.0) is None


# -- the family's reference against the program's own -------------------------------------

TINY = {
    "name": "tiny-glm-dsa", "family": "glm_dsa", "model_type": "glm_moe_dsa",
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
    "indexer_types": ["full", "shared", "shared", "full", "shared"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "intermediate_size": 128, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "expert_share": {"of": 16, "offset": 4}, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 0,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"}, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "quantization": "int8", "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 8, "n_pages": 256, "max_model_len": 256,
               "prefill_buckets": [16, 32]},
    # the test's own limits, from its own readings on the CPU (bf16 activations and int8
    # weights against the float32 reference of the same int8 weights)
    "check": {"served_gap_p90": 2.0, "served_gap_mean": 1.0},
}


def test_the_familys_reference_is_the_programs(family, tmp_path):
    """``logits_at`` (the family's own copy of the plain pass, on the
    family's seeded tree, queries and heads in blocks) against
    ``models/glm_dsa_reference.forward`` on the same tree dequantised: two
    writings of one forward pass, float32 ``highest`` both, the selection 16 of
    up to 90 positions: they agree to float32 rounding (1e-4)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    from modal_examples_tpu.models import glm_dsa_reference as ref
    from modal_examples_tpu.models.quantize import QuantizedWeight, dequantize_weight

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = dataclasses.replace(family.program_config(str(path)), dtype="float32")
    assert (cfg.n_held_experts, cfg.expert_offset, cfg.n_routed_experts) == (8, 4, 16)
    d = family.dims_of(TINY)
    tree = family.make_tree(7, d)
    assert set(tree) == {"embed", "final_norm", "lm_head", "dense_layers", "moe_layers",
                         "indexer_layers"}
    assert tree["indexer_layers"]["wq_idx"]["q"].shape == (2, 32, 64)  # two full layers
    assert tree["moe_layers"]["router_bias"].shape == (4, 16)
    assert float(jnp.abs(tree["moe_layers"]["router_bias"]).mean()) > 0.05  # not zero
    is_pair = lambda x: isinstance(x, dict) and set(x) == {"q", "scale"}  # noqa: E731
    plain = jax.tree.map(
        lambda leaf: dequantize_weight(QuantizedWeight(q=leaf["q"], scale=leaf["scale"]), jnp.float32)
        if is_pair(leaf) else leaf.astype(jnp.float32), tree, is_leaf=is_pair,
    )
    rng = np.random.default_rng(0)
    seqs = [rng.integers(3, 512, size=n).tolist() for n in (90, 40)]
    rows = [list(range(60, 90)), list(range(30, 40))]
    got, margins, clock = reference.logits_at(family, 7, d, seqs, rows)
    assert set(clock) == {"weights_s", "layers_s"}
    for seq, r, lg, mg in zip(seqs, rows, got, margins):
        want, _margin, used = ref.forward(plain, np.asarray(seq), cfg)
        assert np.abs(np.asarray(want)).max() > 1.0
        np.testing.assert_allclose(lg, np.asarray(want)[r], atol=1e-4)
        assert int(np.asarray(used).sum(-1).max()) == 16 < len(seq)  # not the identity
        assert (mg >= 0).all() and np.isfinite(mg).all()
    # int4 weights, and selection off: other logits, by far more than rounding
    low, _, _ = reference.logits_at(family, 7, d, seqs, rows, bits=4)
    assert np.abs(low[0] - got[0]).max() > 0.5
    # the second control is a key of the configuration's file, absent in the benchmark's
    assert d["control"] == "int4" and "check_control" not in CONFIG
    off = family.dims_of(TINY | {"check_control": "select-all"})
    every, _, _ = reference.logits_at(family, 7, off, seqs, rows, bits=4)
    same, _, _ = reference.logits_at(family, 7, off, seqs, rows, bits=8)
    with pytest.raises(ValueError, match="check_control"):
        family.dims_of(TINY | {"check_control": "none"})
    want_all, _, _ = ref.forward(plain, np.asarray(seqs[0]), cfg, select_all=True)
    np.testing.assert_allclose(every[0], np.asarray(want_all)[rows[0]], atol=1e-4)
    assert np.abs(every[0] - got[0]).max() > 0.5
    np.testing.assert_array_equal(same[0], got[0])  # the switch moves the control alone


def test_the_margin_is_the_held_experts_gap_to_changing_sides(family):
    import jax.numpy as jnp
    import numpy as np

    family._load()
    d = {"top_k": 2, "norm_topk": True, "route_scale": 2.5, "expert_offset": 2, "experts": 2}
    p = jnp.asarray([[0.9, 0.8, 0.5, 0.1, 0.79, 0.3],   # held 2, 3 outside: 0.8 - 0.5
                     [0.2, 0.3, 0.9, 0.6, 0.55, 0.1]])  # held 2, 3 inside: 0.6 - 0.55
    weights, ids, margin = family.route(p, jnp.zeros((6,)), d)
    assert np.asarray(ids).tolist() == [[0, 1], [2, 3]]
    np.testing.assert_allclose(np.asarray(margin), [0.3, 0.05], atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    _, biased_ids, _ = family.route(p, jnp.asarray([0, 0, 0.35, 0, 0, 0.0]), d)
    assert np.asarray(biased_ids).tolist() == [[0, 2], [2, 3]]  # chosen by p + b


# -- the new readers on a recorded reduced trace -------------------------------------------


def _run(scopes: bool, counters: bool, *, attention_s: float = 2.0, indexer_s: float = 0.3):
    import rundata
    import trace_reduce

    recording = json.loads((ROOT / "tests/bench_serving/recorded_trace_scoped.json").read_text())
    names, where = recording["names"], recording["scopes"]
    trace = trace_reduce.reduce_events({"lines": {}, "chips": {
        plane: {"modules": chip["modules"],
                "ops": [[names[n], start, dur, where[w]] for n, start, dur, w in chip["ops"]]}
        for plane, chip in recording["chips"].items()
    }})  # a chip recording of a program from before the scopes: none of them in it
    assert trace["scopes"] and "mtpu.indexer" not in trace["scopes"]
    trace["window_s"] = 6.0
    base = {k: v for k, v in trace["scopes"].items() if k != "mtpu.attention"}
    if scopes:
        base.update({
            "mtpu.attention": {"time_s": attention_s, "ops": 900},
            "mtpu.indexer": {"time_s": indexer_s, "ops": 300},
            "mtpu.topk_select": {"time_s": 0.2, "ops": 200},
        })
    trace["scopes"] = base
    trace["programs"] = {
        "jit__decode_block_fn": {"time_s": 1.2, "count": 10},
        "jit_prefill_chunk_pre6144": {"time_s": 1.5, "count": 18},
        "jit_prefill_chunk_pre12288": {"time_s": 2.4, "count": 14},
        "jit_prefill_chunk_pre0": {"time_s": 0.3, "count": 6},
    }
    name = "mtpu_sparse_positions_total"
    opened = {"mtpu_decode_steps_total": [({}, 0.0)], "mtpu_generated_tokens_total": [({}, 0.0)],
              "mtpu_ttft_seconds_count": [({}, 0.0)]}
    closed = {"mtpu_decode_steps_total": [({}, 680.0)],
              "mtpu_generated_tokens_total": [({}, 8200.0)],
              "mtpu_ttft_seconds_count": [({}, 40.0)]}
    if counters:
        opened[name] = [({"kind": k}, 5.0e9) for k in ("scored", "selected", "attended")]
        closed[name] = [({"kind": "scored"}, 5.0e9 + 6.2e9), ({"kind": "selected"}, 5.0e9 + 8.0e9),
                        ({"kind": "attended"}, 5.0e9 + 2.5e10)]

    class Done:
        ok, prompt_tokens, n_out = True, 11400, 80

    return rundata.RunData(
        cell={"name": CELL}, config=CONFIG, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0}, outcomes=[], scored=[Done()] * 40,
        counters_open=opened, counters_close=closed, kv_pages_peak=None,
        engine_log={i: {"n_prompt": 11400, "first_token_at": 101.0 + i} for i in range(40)},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


def test_the_new_readers_on_a_recorded_trace(family):
    readers = M.load_readers()
    run = _run(scopes=True, counters=True)
    total = sum(row["time_s"] for row in run.trace["scopes"].values())
    assert readers["indexer_dev_pct"](run) == pytest.approx(100 * 0.3 / total)
    assert readers["topk_select_dev_pct"](run) == pytest.approx(100 * 0.2 / total)
    assert readers["sparse_selected_pct"](run) == pytest.approx(100 * 8.0e9 / 2.5e10)  # masked-dense
    # the window's 40 prompts of 11400 tokens and its decode steps (10 blocks x 8 steps in
    # the traced 6 s of 51, a mean batch of 12) against the scope's seconds over the window
    scale = 51 / 6
    selected = 40 * (2048 * 2049 / 2 + (11400 - 2048) * 2048)
    steps, batch = 80 * scale, (8200 - 40) / 680
    pre = family.attention(CONFIG, 40 * 11400.0, 38 * scale, phase="prefill", selected=selected)
    dec = family.attention(CONFIG, batch * steps, steps, phase="decode",
                           selected=batch * steps * 2048)
    least = max(pre["flops"] / 197e12, pre["bytes"] / 819e9) + max(
        dec["flops"] / 197e12, dec["bytes"] / 819e9)
    assert readers["sparse_attention_roofline"](run) == pytest.approx(100 * least / (2.0 * scale))
    assert 5 < readers["sparse_attention_roofline"](run) < 100
    scored = 40 * 11400 * 11401 / 2
    pre = family.indexer(CONFIG, 40 * 11400.0, 38 * scale, scored=scored, keys=40 * 11400.0)
    dec = family.indexer(CONFIG, batch * steps, steps, scored=batch * steps * 11440.0,
                         keys=batch * steps * 11440.0)
    least = max(pre["flops"] / 197e12, pre["bytes"] / 819e9) + max(
        dec["flops"] / 197e12, dec["bytes"] / 819e9)
    assert readers["indexer_roofline"](run) == pytest.approx(100 * least / (0.3 * scale))


def test_a_program_that_did_exactly_the_needed_work_reads_under_100(family):
    """The scope's seconds set to what the chip's peaks give for the needed
    work (every selected pair at the MXU's peak, nothing else): the share
    reads 100 and no more, so a gathered form that computed only the
    selection could not read over it; the dense pairs of a full attention,
    handed to ``closed.attention_roofline``'s reader, would (2.9x)."""
    readers = M.load_readers()
    scale = 51 / 6
    selected = 40 * (2048 * 2049 / 2 + (11400 - 2048) * 2048)
    steps, batch = 80 * scale, (8200 - 40) / 680
    pre = family.attention(CONFIG, 40 * 11400.0, 38 * scale, phase="prefill", selected=selected)
    dec = family.attention(CONFIG, batch * steps, steps, phase="decode", selected=batch * steps * 2048)
    exact = (max(pre["flops"] / 197e12, pre["bytes"] / 819e9)
             + max(dec["flops"] / 197e12, dec["bytes"] / 819e9)) / scale
    run = _run(scopes=True, counters=True, attention_s=exact)
    assert readers["sparse_attention_roofline"](run) == pytest.approx(100.0)
    assert readers["sparse_attention_roofline"](_run(True, True, attention_s=exact * 1.01)) < 100
    dense = 40 * 11400 * 11401 / 2
    assert dense / selected > 2.5
    assert readers["attention_roofline"](run) is None  # not this family's count


def test_the_new_readers_read_null_never_zero_where_nothing_is_written():
    """A program from before the scopes and the counter (the parent), or a
    model without an indexer: None, so the result line leaves them out."""
    readers = M.load_readers()
    run = _run(scopes=False, counters=False)
    for name in NEW_METRICS:
        assert readers[M.quantity(name)](run) is None, name
    untraced = _run(scopes=True, counters=True)
    untraced.trace = None
    for name in NEW_METRICS - {"closed.sparse_selected_pct"}:
        assert readers[M.quantity(name)](untraced) is None, name
    # a family without an indexer (DeepSeek-V2's): no roofline, whatever the trace shows
    other = _run(scopes=True, counters=True)
    other.config = json.loads((SRC / "configs" / "deepseek-v2-int8-ep4.json").read_text())
    assert readers["indexer_roofline"](other) is None
    assert readers["sparse_attention_roofline"](other) is None


# -- a CPU rehearsal of the cell's path ----------------------------------------------------

DOCQA = {
    "loop": "closed", "clients": 4,
    "session": {
        "turns": 2, "sessions": 600, "pool": 4,
        "document": {"dist": "uniform", "min": 70, "max": 150},
        "question": {"dist": "uniform", "min": 6, "max": 20},
        "answer": {"dist": "uniform", "min": 10, "max": 20},
    },
    "temperature": 0.7, "greedy_every": 2, "stagger_s": 0.5, "ramp_s": 1.5, "trace_s": 1,
    "check_samples": 3,
}
DRIVER = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1] + "/benchmarks/serving/run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
print(json.dumps(run.run_cell("tiny-glm-dsa.tiny-docqa", 2**31 + 23, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False, control=True)))
"""


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench-copy-glm")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-glm-dsa.json").write_text(json.dumps(TINY))
    (bench / "mixes/tiny-docqa.json").write_text(json.dumps(DOCQA))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-glm-dsa", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-glm-dsa.json",
    }]
    cell = "tiny-glm-dsa.tiny-docqa"
    manifest["workloads"] = [{
        "name": cell, "config": "tiny-glm-dsa", "traffic": "tiny-docqa", "chips": 1, "why": "test",
    }]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [cell] if CELL in metric["workloads"] else []
    manifest["end_to_end"] = [m for m in manifest["end_to_end"] if m.get("workloads", [cell])]
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
    """Documents of 70-150 tokens in chunks of 32 at run-time offsets, the
    selection 16 positions, 8 of the router's 16 experts held: served through
    the normal path and inside the test's own limits."""
    result, stdout = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    compared = result["compared"]
    assert compared["served_gap_p90"] <= TINY["check"]["served_gap_p90"]
    assert compared["served_gap_mean"] <= TINY["check"]["served_gap_mean"]
    assert "compared served_gap_p90:" in stdout
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result


def test_the_rehearsal_computes_the_int4_control(rehearsal):
    compared = rehearsal[0]["compared"]
    assert compared["control_gap_mean"] > compared["served_gap_mean"]
    assert compared["control_gap_p90"] > compared["served_gap_p90"]


def test_the_rehearsal_reads_the_new_counter_and_leaves_trace_metrics_out(rehearsal):
    """On the CPU there is no device trace, so the scope shares and the
    rooflines read nothing and the line leaves them out; the counters read."""
    metrics = rehearsal[0]["metrics"]
    selected = metrics["closed.sparse_selected_pct"]["value"]
    assert 10.0 < selected < 60.0  # 16 of up to 170 positions under prefill's mask, all of decode's
    held = metrics["closed.expert_held_pct"]["value"]
    assert 30.0 < held < 70.0  # experts 4..11 of 16
    assert metrics["prefix_hit_pct"]["value"] > 30.0  # the second question of a document
    assert metrics["closed.compiles_in_window"]["value"] == 0
    for name in NEW_METRICS - {"closed.sparse_selected_pct"}:
        assert name not in metrics
