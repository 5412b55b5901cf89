"""The routed Granite hybrid family of the serving harness (Granite-4.0-H-Small
on one chip of an EP2 layer): the manifest with its configuration and cell
(every entry found by name, no position in a list asserted), the cut as
numbers, the configuration against the catalog's row, the family's reference
against the program's own, its three controls, its work functions against
hand values at the published widths, the readers on a recorded reduced trace
with and without the scope and the counters, and a CPU rehearsal of the
cell's path at a tiny size (a configuration of the family and a small
``reason``-shaped mix added as files to a temporary copy of the benchmark,
none edited): ``App.run()`` -> ``@app.server`` -> ``LLMEngine`` behind
``serving/openai_api.py``, served, and compared with the family's own
reference and each of its controls.
"""

import json
import math
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
NAME = "granite-4.0-h-small-bf16-ep2"
CONFIG_FILE = SRC / "configs" / f"{NAME}.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
CELL = f"{NAME}.reason-wide-closed"
SIBLING = "granite-4.0-h-micro-bf16.reason-wide-closed"
NEW_METRICS = {"reason.expert_held_pct", "reason.router_dev_pct"}
#: the entries PR 39 pinned to LFM2's cell alone (tests/bench_serving/test_lfm2_cell.py)
LFM2_PINNED = {"reason.expert_scan_roofline", "reason.expert_dispatch_dev_pct",
               "reason.expert_tile_fill_pct"}


@pytest.fixture(scope="module")
def family():
    return M.load_family(CONFIG)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# -- the manifest ---------------------------------------------------------------------


def test_the_manifest_names_the_configuration_the_cell_and_the_two_new_metrics():
    assert M.problems(MANIFEST, ROOT) == []
    assert M.family_problems("granite_hybrid_moe") == []
    assert M.family_name(CONFIG) == "granite_hybrid_moe"
    entry = _named(MANIFEST["configs"], NAME)
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmarks/serving/configs/{NAME}.json"
    cell = _named(MANIFEST["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-closed", 1)
    assert CELL in _named(MANIFEST["end_to_end"], "out_tok_s")["workloads"]
    sources = {
        "reason.expert_held_pct": ("program_counter", "higher", "model programs"),
        "reason.router_dev_pct": ("device_trace", "lower", "kernels and XLA ops"),
    }
    assert set(sources) == NEW_METRICS
    for name, (source, better, layer) in sources.items():
        m = _named(MANIFEST["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s" and m["unit"] == "%"
        assert (m["source"], m["better"], m["layer"]) == (source, better, layer)
    # the three entries pinned to LFM2's cell stay its alone (PERF.md section 7 (ii))
    for name in LFM2_PINNED:
        assert CELL not in _named(MANIFEST["per_layer"], name)["workloads"]


def test_the_cell_resolves_and_reports_both_halves_metrics():
    """Everything the dense sibling's cell reports (the six ``ssm_*`` /
    ``state_rows`` entries among them) and the routed half's shares."""
    info = M.resolve(MANIFEST, CELL, ROOT)
    assert {m["name"] for m in info["end_to_end"]} == {"out_tok_s", "setup_s"}
    names = {m["name"] for m in info["per_layer"]}
    readers = M.load_readers()
    assert all(M.quantity(n) in readers for n in names)
    sibling = {m["name"] for m in M.resolve(MANIFEST, SIBLING, ROOT)["per_layer"]}
    assert names == sibling | NEW_METRICS | {"reason.expert_scan_dev_pct"}
    assert {"reason.ssm_step_dev_pct", "reason.ssm_scan_dev_pct", "reason.ssm_proj_dev_pct",
            "reason.ssm_step_roofline", "reason.ssm_scan_roofline", "reason.state_rows_live_pct",
            "reason.dense_mlp_dev_pct", "reason.page_gather_dev_pct", "reason.hbm_peak_pct",
            "reason.decode_roofline"} <= names
    assert all(m["moves"] in ("out_tok_s", "setup_s") for m in info["per_layer"])
    assert not (LFM2_PINNED | {"reason.prefix_hit_pct"}) & names
    mix = info["mix"]
    assert (mix["clients"], mix["session"]["sessions"], info["cell"]["chips"]) == (64, 768, 1)


def test_the_cells_file_is_the_dense_siblings_key_for_key():
    """64 closed-loop clients, one per slot; 768 sessions of one turn; a
    32-token system prompt of 4, a question of 32-223 (65-256 with BOS);
    answers uniform 512-1024; every other key the mix's."""
    mine = json.loads((SRC / "cells" / f"{CELL}.json").read_text())
    theirs = json.loads((SRC / "cells" / f"{SIBLING}.json").read_text())
    assert set(mine) == set(theirs) == {"why", "clients", "session"}
    assert mine["clients"] == theirs["clients"] == 64 == CONFIG["engine"]["max_slots"]
    assert mine["session"] == theirs["session"] == {
        "turns": 1, "sessions": 768, "pool": 4,
        "document": {"dist": "uniform", "min": 32, "max": 32},
        "question": {"dist": "uniform", "min": 32, "max": 223},
        "answer": {"dist": "uniform", "min": 512, "max": 1024},
    }
    mix = M.resolve(MANIFEST, CELL, ROOT)["mix"]
    assert (mix["temperature"], mix["greedy_every"], mix["check_samples"]) == (0.7, 4, 4)
    import traffic

    lengths = traffic.prompt_lengths(mix, 51.0)
    assert 65 <= min(lengths) and max(lengths) <= 256  # bucket calls, no chunk
    assert 256 + 1024 == 1280 < CONFIG["engine"]["max_model_len"] == 2048


def test_the_configuration_is_the_published_one_cut_as_it_says():
    """Every published number under its published key; only the depth, the
    experts held and the vocabulary differ, and the file states the published
    counts, the share and the deployment."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        row = next(
            r for r in map(json.loads, catalog.read_text().splitlines())
            if r["name"] == "granite-4.0-h-small"
        )
        differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differing == set(CONFIG["reduced"])
        assert CONFIG["source"] == row["source_url"]
        assert {k: row["config"][k] for k in differing} == CONFIG["published"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert not any(M.reduced_problem(k) for k in CONFIG["reduced"])
    # the file keeps all 40 entries of the pattern and runs the first period of it
    assert len(CONFIG["layer_types"]) == 40 and CONFIG["num_hidden_layers"] == 10
    assert [i for i, t in enumerate(CONFIG["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert CONFIG["expert_share"] == {"of": 72, "offset": 0, "chips_per_layer": 2}
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["shared_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["mamba_n_heads"], CONFIG["mamba_d_head"],
            CONFIG["mamba_d_state"], CONFIG["mamba_expand"], CONFIG["mamba_d_conv"],
            CONFIG["mamba_chunk_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["attention_multiplier"],
            CONFIG["logits_scaling"]) == (
        4096, 768, 1536, 10, 128, 64, 128, 2, 4, 256, 32, 8, 0.0078125, 16)
    # the floors of model-configs section 4: a whole period and ten layers, 36 >= 8
    # experts, over an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] % 10 == 0 and CONFIG["num_local_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= 100352 and CONFIG["vocab_size"] <= 32 ** 3
    assert CONFIG["engine"] == {"max_slots": 64, "page_size": 16, "n_pages": 6144,
                                "max_model_len": 2048, "enable_prefix_cache": False}
    assert "check_control" not in CONFIG  # the benchmark's runs read the bf16-state control
    assert {"deployment", "assumed", "check", "check_why"} <= set(CONFIG)
    # the seeded router and experts are the family's constants and no key of the file
    assert "seeded" not in CONFIG
    assert {"expert_width", "weights", "router", "ssm_init", "max_model_len", "state"} <= set(
        CONFIG["assumed"])
    assert set(CONFIG["check"]) <= {"served_gap_mean", "served_gap_p90"}
    assert all(name in CONFIG["check_why"] for name in CONFIG["check"])


def test_the_program_config_is_the_published_model_and_the_cut_is_its_arithmetic(family):
    """ISSUE 45's cut: 4.55 B parameters = 9.10 GB of layers, 0.21 GB of
    embedding, 2.45 GB of state for 64 slots, 0.40 GB of pages: 12.2 GB of a
    chip's 17.2 (71%)."""
    cfg = family.program_config(str(CONFIG_FILE))
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.layer_types.count("mamba")) == (10, 1, 9)
    assert cfg.segments == (("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4))
    assert (cfg.n_experts, cfg.held_experts, cfg.expert_offset, cfg.top_k, cfg.expert_dim,
            cfg.ffn_dim) == (72, 36, 0, 10, 768, 1536)
    assert cfg.cache_leaf_shapes == ((8, 128), (8, 128)) and cfg.kv_fold == 1
    assert cfg.state_leaves == ((9, (128, 64, 128), "float32"), (9, (3, 8448), "bfloat16"))
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (0.0078125, 12.0, 0.22, 16.0)
    s = family.sizes(CONFIG)
    assert (s["layers"], s["mamba_layers"], s["attn_layers"], s["experts"], s["router"]) == (
        10, 9, 1, 36, 72)
    held = family.held_weight_bytes(s)
    assert round(held / 1e9, 2) == 9.31  # layers 9.10 + the tied embedding 0.21
    assert abs(2 * cfg.param_count - held) < 2e6  # the program's own count of the same tree
    engine = CONFIG["engine"]
    per_slot = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(per_slot / 1e6, 1) == 38.2
    state = engine["max_slots"] * per_slot
    pages = 2 * engine["n_pages"] * engine["page_size"] * 8 * 128 * 2
    assert round(state / 1e9, 2) == 2.45 and pages == 402_653_184  # 4 KB a token, one layer
    total = held + state + pages
    assert round(total / 1e9, 1) == 12.2 and 0.70 < total / (16 * 2**30) < 0.72
    # 96 slots would leave the chunk programs no room
    assert (held + 96 * per_slot + pages) / 1e9 > 13.3


_OLD_PROGRAM = """
import sys
import jax
jax.devices()  # a container has opened its backend by then
sys.path.insert(0, {src!r})
{fault}
import manifest
family = manifest.load_family({{"family": "granite_hybrid_moe"}})
try:
    family.program_config({config!r})
except (ImportError, NotImplementedError) as e:
    print("raised", type(e).__name__)
"""
#: a program from before the family's model, and the parent of PR 45, which has
#: the module and refuses the file's experts by name
_FAULTS = {
    "no-module": 'sys.modules["modal_examples_tpu.models.granite_hybrid"] = None',
    "refuses-experts": (
        "from modal_examples_tpu.models.granite_hybrid import GraniteHybridConfig as C\n"
        "def old(path):\n"
        "    raise NotImplementedError('GraniteHybridConfig: num_local_experts=36 is not modelled')\n"
        "C.from_hf_config = staticmethod(old)"
    ),
}


@pytest.mark.parametrize("fault", list(_FAULTS))
@pytest.mark.parametrize("in_container", [True, False])
def test_a_program_without_the_routed_model_fails_the_cell_and_leaves_no_container(
        fault, in_container):
    """The check tries a new cell on the parent commit first: the failure
    has to leave nothing running (``families/deepseek_v2.py`` says why).
    Inside a container the process hands the chip back and leaves with code
    3; anywhere else the error is the answer."""
    env = {k: v for k, v in os.environ.items() if k != "MTPU_TASK_ID"}
    if in_container:
        env["MTPU_TASK_ID"] = "ta-test"
    proc = subprocess.run(
        [sys.executable, "-c", _OLD_PROGRAM.format(
            src=str(SRC), fault=_FAULTS[fault], config=str(CONFIG_FILE))],
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)},
        capture_output=True, text=True, timeout=120,
    )
    said = "cannot run the family's cells" if in_container else "raised "
    assert proc.returncode == (3 if in_container else 0), proc.stderr[-2000:]
    assert said in proc.stdout + proc.stderr


# -- the work functions, by hand ----------------------------------------------------------

MIXER = 4096 * (8192 + 8448 + 128) + 8192 * 4096  # in_proj [z | xBC | dt] and out_proj
ATTN = 4096 * (4096 + 2 * 1024) + 4096 * 4096
SHARED = 3 * 4096 * 1536
EXPERT = 3 * 4096 * 768
ROUTERS = 10 * 4096 * 72
FIXED = 9 * MIXER + ATTN + 10 * SHARED + ROUTERS
HEAD = 4096 * 25088
SMALL = 9 * 8448 * 5 * 2.0  # the convolutions' taps and biases, bf16
STATE = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)  # a sequence's recurrent state, bytes
SKEW = 1.1  # the family's ROUTE_SKEW: the held experts' loads log-normal, their logarithms 1.1 wide


def _reached(tokens: float) -> float:
    """The held experts ``tokens`` independent tokens reach, each expert
    chosen with 10 / 72 times its load: 36 mid-quantiles of the log-normal,
    mean 1."""
    from statistics import NormalDist

    loads = [math.exp(SKEW * NormalDist().inv_cdf((i + 0.5) / 36)) for i in range(36)]
    return sum(1 - (1 - 10 / 72 * load * 36 / sum(loads)) ** tokens for load in loads)


REACHED_64 = _reached(64.0)


def test_sizes_by_hand(family):
    s = family.sizes(CONFIG)
    assert family.expert_params(s) == EXPERT == 9_437_184  # 18.9 MB in bf16
    assert 102.2e6 < MIXER < 102.4e6 and round(ATTN / 1e6, 1) == 41.9 and SHARED == 18_874_368
    assert family.held_share(s) == 0.5
    assert family.params_per_token(s) == FIXED + 10 * EXPERT * 10 * 0.5
    assert family.held_weight_bytes(s) == (FIXED + 10 * 36 * EXPERT + HEAD) * 2 + SMALL
    # ISSUE 45 took routing as even: 64 x 10 pairs over 72 experts reach every held one
    # ((62/72)^64 is 7e-5). The chip's tiles cover 33.2-33.6 of 36 at 62.1 live tokens
    assert (62 / 72) ** 64 == pytest.approx(7e-5, rel=0.02)
    # (three served seeds 32.5-33.6; the reference's routes of 64 random sequences 32.0)
    assert family.ROUTE_SKEW == SKEW
    assert family.experts_reached(s, 64.0) == pytest.approx(REACHED_64)
    assert 33.0 < REACHED_64 < 33.3 and 32.9 < family.experts_reached(s, 62.1) < 33.1
    # the reference's routes on the chip: 8.2, 18.7, 29.2 at 2, 8, 32 tokens, 34.2-34.5 at 128
    for n, read in ((2, 8.2), (8, 18.7), (32, 29.2), (128, 34.5)):
        assert abs(family.experts_reached(s, float(n)) - read) < 0.6
    assert family.experts_reached(s, 1.0) == pytest.approx(5.0)  # half of a token's ten
    assert family.experts_reached(s, 0.0) == 0.0
    assert max(family._loads(36)) * 10 / 72 < 1.0 and sum(family._loads(36)) == pytest.approx(36)
    # a token chooses no expert more than once: the heaviest experts' shares are capped at 1
    narrow = dict(s, router=36, top_k=12)
    assert max(family._loads(36)) * 12 / 36 > 1.0 and 10.0 < family.experts_reached(narrow, 1.0) < 12.0
    assert family.experts_reached(narrow, 0.5) < family.experts_reached(narrow, 1.0)
    assert family.experts_reached(s, float("inf")) == 36.0  # and many reach every one
    reached = [family.experts_reached(s, float(n)) for n in (1, 2, 8, 64, 256, 2048)]
    assert reached == sorted(reached) and reached[-1] > 35.999


def test_decode_step_work_by_hand(family):
    """The weights once (the held experts 64 live sequences reach), each
    sequence's state read and written, the one attention layer's K/V."""
    step = family.decode_step(CONFIG, 64.0, 64 * 700.0)
    weights = (FIXED + 10 * EXPERT * REACHED_64 + HEAD) * 2 + SMALL
    assert step["bytes"] == pytest.approx(
        weights + 2 * STATE * 64 + 4096 * (64 * 700 + 64) + 64 * 4096 * 2)
    step_flops = 9 * (5 * 128 * 64 * 128 + 2 * 4 * 8448) * 64
    assert step["flops"] == pytest.approx(
        2 * (FIXED + 10 * EXPERT * 5 + HEAD) * 64 + step_flops + 4 * 32 * 128 * 64 * 700)
    # ISSUE 45, all 36 experts reached: experts 6.79 GB (48%), state 4.89 GB (34%), the
    # dense rest 17%; 14.1-14.4 GB a step. With the 33.1 a step reaches: 6.25 GB (45%),
    # the state 35%; 13.8 GB a step, 16.9 ms at HBM's 819 GB/s
    experts, state = 10 * EXPERT * REACHED_64 * 2, 2 * STATE * 64
    assert round(10 * EXPERT * 36 * 2 / 1e9, 2) == 6.79 and round(state / 1e9, 2) == 4.89
    assert round(experts / 1e9, 2) == 6.25
    assert 0.44 < experts / step["bytes"] < 0.46 and 0.35 < state / step["bytes"] < 0.36
    assert 13.8e9 < step["bytes"] < 13.9e9 and 16.8e-3 < step["bytes"] / 819e9 < 17.0e-3
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # bytes-bound on a v5e


def test_prefill_work_by_hand(family):
    pre = family.prefill(CONFIG, [200, 100], 1.0)
    scan = 2 * 128.5 * (128 + 8192) + 4 * 8192 * 128 + 2 * 4 * 8448
    reached = _reached(300.0)
    assert pre["flops"] == pytest.approx(
        2 * (FIXED + 10 * EXPERT * 5) * 300 + 2 * HEAD * 2 + 9 * scan * 300
        + 4 * 32 * 128 * (200 * 201 / 2 + 100 * 101 / 2))
    assert pre["bytes"] == pytest.approx(
        (FIXED + 10 * EXPERT * reached + HEAD) * 2 + SMALL + 4096 * 300 + 2 * STATE)


def test_scope_work_by_hand(family):
    work = family.SCOPE_WORK
    assert set(work) == {"mtpu.expert_scan", "mtpu.ssm_step", "mtpu.ssm_scan",
                         "mtpu.dense_mlp", "mtpu.attention"}
    # 100 decode steps of 64: the held experts' bytes once a layer a step
    scan = work["mtpu.expert_scan"](CONFIG, 6400.0, 100.0)
    assert scan["flops"] == pytest.approx(2 * EXPERT * 5 * 6400 * 10)
    assert scan["bytes"] == pytest.approx(
        100 * 10 * EXPERT * REACHED_64 * 2 + 10 * 5 * 6400 * 2 * 4096 * 2)
    assert work["mtpu.expert_scan"](CONFIG, 0.0, 1.0) is None
    step = work["mtpu.ssm_step"](CONFIG, 6400.0, 100.0)  # the state read and written
    assert step["bytes"] == 2 * STATE * 6400
    assert step["flops"] == 9 * (5 * 128 * 64 * 128 + 2 * 4 * 8448) * 6400
    pre = work["mtpu.ssm_scan"](CONFIG, 1000.0, 4.0)
    assert pre["bytes"] == 9 * (2 * (2 * 8448 + 8192) + 4 * 128) * 1000
    mlp = work["mtpu.dense_mlp"](CONFIG, 6400.0, 100.0)  # the shared expert
    assert mlp["bytes"] == 100 * 10 * SHARED * 2 + 10 * 6400 * 2 * 4096 * 2
    assert mlp["flops"] == 2 * 10 * SHARED * 6400
    attn = work["mtpu.attention"](CONFIG, 64.0, 1.0, positions=64 * 700.0)
    assert attn["bytes"] == 4096 * 64 * 700 and attn["flops"] == 4 * 32 * 128 * 64 * 700
    assert work["mtpu.attention"](CONFIG, 64.0, 1.0) is None


def test_reading_the_work_functions_does_not_import_jax():
    """The load generator's process reads them and may not hold JAX (nor may
    loading the dense sibling bring it)."""
    code = (
        "import sys, json; sys.path.insert(0, sys.argv[1]); import manifest as M\n"
        f"c = json.load(open(sys.argv[1] + '/configs/{NAME}.json'))\n"
        "f = M.load_family(c); M.load_readers()\n"
        "assert f.decode_step(c, 64, 45000)['bytes'] > 13.5e9 and f.prefill(c, [256], 1)\n"
        "assert f.SCOPE_WORK['mtpu.expert_scan'](c, 64.0, 1.0)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code, str(SRC)], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- the family's reference against the program's own -------------------------------------

TINY = {
    "name": "tiny-granite-moe", "family": "granite_hybrid_moe", "model_type": "granitemoehybrid",
    "hidden_size": 64, "shared_intermediate_size": 64, "intermediate_size": 32,
    # one period more than run: a file keeps the published list whole
    "layer_types": ["mamba", "mamba", "attention", "mamba", "attention", "mamba"] * 2,
    "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False, "position_embedding_type": "nope",
    "num_local_experts": 6, "num_experts_per_tok": 4,
    "expert_share": {"of": 12, "offset": 6, "chips_per_layer": 2},
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.25, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 512, "max_position_embeddings": 512,
    "quantization": None, "kv_dtype": "bfloat16", "reduced": [],
    "engine": {"max_slots": 4, "page_size": 16, "n_pages": 96, "max_model_len": 256,
               "prefill_buckets": [32, 64], "enable_prefix_cache": False},
    # the test's own limits, from its own readings on the CPU (bf16 weights and
    # activations against the float32 reference of the same bf16 weights; mean / p90):
    # sound 0.003 / 0.0; top9 0.063-0.068 / 0.26-0.27; no-shared 1.46 / 2.9
    "check": {"served_gap_mean": 0.02, "served_gap_p90": 0.06},
}


def test_the_familys_reference_is_the_programs_and_its_controls_move_it(family, tmp_path):
    """``logits_at`` (the family's own copy of the plain pass, on the
    family's seeded tree, a layer's weights made again alone, the held
    experts of a share that starts at expert 6) against
    ``models/granite_hybrid_reference.forward`` on the same tree: two
    writings of one forward pass, float32 ``highest`` both, so they agree to
    float32 rounding through 6 layers (1e-4). Each of the three controls
    moves the logits by far more, and the two routed ones as the program's
    reference computes them."""
    import jax.numpy as jnp
    import numpy as np

    from modal_examples_tpu.models import granite_hybrid_reference as ref

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    cfg = family.program_config(str(path))
    assert (cfg.n_layers, cfg.n_experts, cfg.held_experts, cfg.expert_offset, cfg.top_k) == (
        6, 12, 6, 6, 4)
    d = family.dims_of(TINY)
    assert d["layer_types"] == ("mamba", "mamba", "attention", "mamba", "attention", "mamba")
    assert (d["control"], d["experts"], d["router"], d["expert_offset"], d["ffn"], d["moe_ffn"]) == (
        "bf16-state", 6, 12, 6, 64, 32)
    tree = family.make_tree(7, d)
    assert set(tree) == {"embed", "final_norm", "mamba_layers", "attention_layers", "moe_layers"}
    assert tree["mamba_layers"]["in_z"].shape[0] == 4 and tree["attention_layers"]["wq"].shape[0] == 2
    assert tree["moe_layers"]["moe_gate"].shape == (6, 6, 64, 32)
    assert tree["moe_layers"]["router"].shape == (6, 64, 12)
    assert {a.dtype for a in tree["moe_layers"].values()} == {jnp.dtype(jnp.bfloat16)}
    # layer 3 (a Mamba layer, the third of its kind) made alone is the tree's rows
    alone = family._jitted().layer_weights(
        family._sibling().layer_key(7, d, 3), d=family._sibling()._Frozen(d), kind="mamba")
    for leaf, row in (("in_z", tree["mamba_layers"]["in_z"][2]),
                      ("moe_down", tree["moe_layers"]["moe_down"][3]),
                      ("router", tree["moe_layers"]["router"][3])):
        np.testing.assert_array_equal(np.asarray(alone[leaf], np.float32), np.asarray(row, np.float32))
    # the share is a slice of the whole model's tree: the chip that holds every
    # expert has these six as its experts 6..11
    whole = family.make_tree(7, family.dims_of(
        dict(TINY, num_local_experts=12, expert_share={"of": 12, "offset": 0})))
    np.testing.assert_array_equal(
        np.asarray(whole["moe_layers"]["moe_up"][:, 6:], np.float32),
        np.asarray(tree["moe_layers"]["moe_up"], np.float32))
    # the experts' output projections at EXPERT_GAIN times the unit scale
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32)))))  # noqa: E731
    ratio = rms(tree["moe_layers"]["moe_down"]) * 32**0.5 / (rms(tree["moe_layers"]["moe_gate"]) * 8)
    assert ratio == pytest.approx(family.EXPERT_GAIN, rel=0.05)
    ids = np.random.default_rng(0).integers(3, 512, size=40).astype(np.int32)
    rows = [5, 17, 39]
    (got,), (margins,), clock = family.logits_at(7, d, [ids], [rows])
    want = np.asarray(ref.forward(tree, jnp.asarray(ids), cfg))[rows]
    assert np.isfinite(margins).all() and (margins >= 0).all() and (margins <= 1).all()
    assert set(clock) == {"weights_s", "layers_s"}
    assert np.abs(want).max() > 1.0  # logits near N(0, 1), not near 0
    np.testing.assert_allclose(got, want, atol=1e-4)
    for control, theirs in (("bf16-state", None), ("top9", dict(top_k=3)), ("drop-4", dict(top_k=3)),
                            ("no-shared", dict(shared=False))):
        d_c = family.dims_of(dict(TINY, check_control=control))
        (moved,), _, _ = family.logits_at(7, d_c, [ids], [rows], bits=4)
        assert np.abs(moved - want).max(axis=-1).min() > (5e-4 if theirs is None else 0.02)
        if theirs is not None:  # the two writings agree on the control too
            other = np.asarray(ref.forward(tree, jnp.asarray(ids), cfg, **theirs))[rows]
            np.testing.assert_allclose(moved, other, atol=1e-4)
        (same,), _, _ = family.logits_at(7, d_c, [ids], [rows], bits=8)
        np.testing.assert_allclose(same, want, atol=1e-4)
    for bad in ("no-such", "drop-0", "drop-5", "drop-x"):  # a token's chosen four: 1..4
        with pytest.raises(ValueError, match="check_control"):
            family.dims_of(dict(TINY, check_control=bad))
    with pytest.raises(ValueError, match="routed model"):
        family.dims_of(dict(TINY, num_local_experts=0))


@pytest.mark.parametrize("control, k, drop", [
    ("drop-1", 10, 0), ("drop-4", 10, 3), ("drop-10", 10, 9), ("top9", 10, 9), ("top9", 4, 3),
    ("no-shared", 10, None), ("bf16-state", 10, None), (None, 10, None),
])
def test_a_drop_control_leaves_out_one_of_the_chosen_before_the_softmax(family, control, k, drop):
    """``drop-<n>`` names the n-th of a token's chosen experts, ``top9`` the
    last; the route without it is the softmax over the logits of the others,
    and the margin stays the whole route's (the sound pass reads it)."""
    import jax.numpy as jnp
    import numpy as np

    family._load()
    assert family.dropped(control, k) == drop
    logits = jnp.asarray(np.random.default_rng(3).normal(0, 2, size=(5, 24)), jnp.float32)
    logits = logits.at[0, 7].set(logits[0, 2])  # a tie: the lower id ranks first
    whole_ids, whole_p, margin = family.route(logits, k)
    ids, p, margin_dropped = family.route(logits, k, drop)
    order = np.argsort(-np.asarray(logits), axis=-1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.asarray(whole_ids), order)
    np.testing.assert_allclose(np.asarray(whole_p).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(margin), np.asarray(margin_dropped))
    kept = order if drop is None else np.delete(order, drop, axis=1)
    np.testing.assert_array_equal(np.asarray(ids), kept)
    chosen = np.take_along_axis(np.asarray(logits), kept, axis=-1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(p), want / want.sum(-1, keepdims=True), rtol=1e-5)


def test_the_program_serves_the_familys_tree_as_the_reference_reads_it(family, tmp_path):
    """The seeded bf16 tree through the program's own full forward (bf16
    activations, the tile loop over the held experts, the chunked scan)
    against the family's reference at rows of one sequence: inside the
    rehearsal's limits by a wide margin."""
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
        family.make_tree(11, d), jnp.asarray(ids)[None], cfg), np.float32)[0][rows]
    gap = want.max(-1) - want[np.arange(len(rows)), got.argmax(-1)]
    assert gap.mean() < TINY["check"]["served_gap_mean"] / 2
    assert np.quantile(gap, 0.9) < TINY["check"]["served_gap_p90"] / 2


# -- the readers on a recorded reduced trace -----------------------------------------------

PAIRS = "mtpu_routed_pairs_total"


def _run(scopes: bool, counters: bool):
    import rundata

    import trace_reduce

    recording = json.loads((ROOT / "tests/bench_serving/recorded_trace_scoped.json").read_text())
    names, where = recording["names"], recording["scopes"]
    trace = trace_reduce.reduce_events({"lines": {}, "chips": {
        plane: {"modules": chip["modules"],
                "ops": [[names[n], start, dur, where[w]] for n, start, dur, w in chip["ops"]]}
        for plane, chip in recording["chips"].items()
    }})  # a chip recording of a dense model's program: no router in it
    assert trace["scopes"] and "mtpu.router" not in trace["scopes"]
    trace["window_s"] = 4.0
    if scopes:
        trace["scopes"].update({
            "mtpu.router": {"time_s": 0.08, "ops": 4000},
            "mtpu.expert_scan": {"time_s": 1.5, "ops": 1500},
            "mtpu.ssm_step": {"time_s": 1.1, "ops": 1400},
        })
    trace["programs"] = {
        "jit__decode_block_fn": {"time_s": 3.4, "count": 18},
        "jit__prefill_and_sample": {"time_s": 0.3, "count": 8},
    }
    opened = {"mtpu_decode_steps_total": [({}, 0.0)], "mtpu_generated_tokens_total": [({}, 0.0)],
              "mtpu_ttft_seconds_count": [({}, 0.0)]}
    closed = {"mtpu_decode_steps_total": [({}, 1800.0)],
              "mtpu_generated_tokens_total": [({}, 113_500.0)],
              "mtpu_ttft_seconds_count": [({}, 100.0)]}
    if counters:
        opened[PAIRS] = [({"where": "held"}, 3000.0), ({"where": "elsewhere"}, 3400.0)]
        closed[PAIRS] = [({"where": "held"}, 3000.0 + 5_650_000),
                         ({"where": "elsewhere"}, 3400.0 + 5_690_000)]
    return rundata.RunData(
        cell={"name": CELL}, config=CONFIG, mix={"loop": "closed"},
        times={"window_open": 100.0, "window_close": 151.0}, outcomes=[], scored=[],
        counters_open=opened, counters_close=closed, kv_pages_peak=None,
        engine_log={i: {"n_prompt": 160, "first_token_at": 101.0 + i} for i in range(40)},
        device={"kind": "TPU v5 lite", "decode_block": 8}, trace=trace,
    )


def test_the_readers_on_a_recorded_trace(family):
    readers = M.load_readers()
    run = _run(scopes=True, counters=True)
    total = sum(row["time_s"] for row in run.trace["scopes"].values())
    assert readers["router_dev_pct"](run) == pytest.approx(100 * 0.08 / total)
    assert readers["expert_held_pct"](run) == pytest.approx(100 * 5.65 / (5.65 + 5.69))
    assert readers["expert_scan_dev_pct"](run) == pytest.approx(100 * 1.5 / total)
    # 18 blocks x 8 steps in the traced 4 s at a mean batch of 63: the state of 63
    # sequences each way a step at HBM's peak, against 1.1 s under the scope
    batch = (113_500 - 100) / 1800
    assert batch == pytest.approx(63.0)
    least = 2 * STATE * batch * 144 / 819e9
    assert readers["ssm_step_roofline"](run) == pytest.approx(100 * least / 1.1)
    assert 40 < readers["ssm_step_roofline"](run) < 100
    # the family's expert_scan work against the scope (an entry pinned to LFM2's
    # cell reads it there; here the reader has what it needs and stays under 100)
    assert 50 < readers["expert_scan_roofline"](run) < 100


def test_the_new_readers_read_null_never_zero_where_nothing_is_written():
    """A program from before the scope and the counter (the parent), or a
    model that routes nothing: None, so the result line leaves them out."""
    readers = M.load_readers()
    run = _run(scopes=False, counters=False)
    for name in NEW_METRICS:
        assert readers[M.quantity(name)](run) is None, name
    untraced = _run(scopes=True, counters=True)
    untraced.trace = None
    assert readers["router_dev_pct"](untraced) is None
    assert readers["expert_held_pct"](untraced) is not None
    empty = _run(scopes=True, counters=True)
    empty.trace["scopes"] = {}
    assert readers["router_dev_pct"](empty) is None


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
print(json.dumps(run.run_cell("tiny-granite-moe.tiny-reason", 2**31 + 23, 8.0, True,
                              root=run.Path(sys.argv[1]), require_tpu=False, control=True,
                              extra_env=json.loads(sys.argv[2]))))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A temporary copy of the benchmark with the tiny configuration (and a
    copy of it for each of the two routed controls), a small mix and a
    manifest of the one cell added as files, none edited."""
    root = tmp_path_factory.mktemp("bench-copy-granite-moe")
    bench = root / "benchmarks" / "serving"
    shutil.copytree(SRC, bench, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs/tiny-granite-moe.json").write_text(json.dumps(TINY))
    for control in ("top9", "no-shared"):
        (bench / f"configs/tiny-granite-moe-{control}.json").write_text(
            json.dumps(dict(TINY, check_control=control)))
    (bench / "mixes/tiny-reason.json").write_text(json.dumps(REASON))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] = [{
        "name": "tiny-granite-moe", "source": "made up for the test", "reduced": [],
        "why": "test", "file": "benchmarks/serving/configs/tiny-granite-moe.json",
    }]
    cell = "tiny-granite-moe.tiny-reason"
    manifest["workloads"] = [{
        "name": cell, "config": "tiny-granite-moe", "traffic": "tiny-reason", "chips": 1,
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


def _outside(compared: dict) -> list[str]:
    """The limits the control's reading lies outside."""
    return [n for n, limit in TINY["check"].items()
            if compared[n.replace("served", "control")] > limit]


def test_the_rehearsed_cell_is_served_and_correct(rehearsal):
    result, stdout = rehearsal
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    compared = result["compared"]
    for name, limit in TINY["check"].items():
        assert compared[name] <= limit
    assert "compared served_gap_mean:" in stdout
    assert result["device"]["platform"] == "cpu"  # a rehearsal, never a chip result


def test_the_rehearsal_computes_the_bf16_state_control(rehearsal):
    """The configuration's own control (no ``check_control``) runs beside the
    comparison. At this size (4 Mamba layers, 512 rows, answers of 20-40) a
    bf16 state need not move a first choice: what it reads at the published
    size is PERF.md section 6 (PR 45); that its logits differ by more than
    rounding is ``test_the_familys_reference_is_the_programs...``."""
    compared = rehearsal[0]["compared"]
    for key in ("control_gap_max", "control_gap_p90", "control_gap_mean"):
        assert compared[key] >= 0.0 and compared[key.replace("control", "served")] >= 0.0


@pytest.mark.parametrize("control", ["top9", "no-shared"])
def test_a_routed_control_reads_outside_the_limits(copy, control):
    """The same path with the configuration's copy that names the control:
    the served tokens are sound (the program is the same) and the control,
    the route one expert short or the shared expert left out, lies outside
    the rehearsal's limits."""
    other = copy / f"benchmarks/serving/configs/tiny-granite-moe-{control}.json"
    result, _ = _rehearse(copy, {"BENCH_CONFIG_FILE": str(other)})
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    assert set(_outside(compared)) == set(TINY["check"])
    assert compared["control_gap_mean"] > 5 * max(compared["served_gap_mean"], 0.004)


def test_the_rehearsal_reads_the_counters_and_leaves_trace_metrics_out(rehearsal):
    """On the CPU there is no device trace, so the scopes' shares and the
    rooflines read nothing and the line leaves them out; the counters read:
    half the experts held, about half the pairs."""
    metrics = rehearsal[0]["metrics"]
    assert 35.0 < metrics["reason.expert_held_pct"]["value"] < 65.0
    assert 30.0 < metrics["reason.state_rows_live_pct"]["value"] <= 100.0
    assert metrics["reason.decode_kv_read_pct"]["value"] > 0
    assert metrics["reason.compiles_in_window"]["value"] == 0
    assert "reason.prefix_hit_pct" not in metrics
    for name in ("reason.router_dev_pct", "reason.expert_scan_dev_pct", "reason.ssm_step_dev_pct",
                 "reason.ssm_step_roofline"):
        assert name not in metrics
