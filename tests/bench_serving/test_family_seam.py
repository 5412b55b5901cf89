"""The family seam of the serving harness: a configuration names its family,
``families/<family>.py`` holds everything that knows a layer's shape, and
``manifest.problems()`` checks both. The Llama family's file is the code
that ``weights.py``, ``reference.py`` and ``work_model.py`` held before,
moved: the seeded tree and the reference's logits are pinned to hashes taken
on the parent commit, bit for bit.
"""

import copy
import hashlib
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "serving"
sys.path.insert(0, str(BENCH))

import manifest as M  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 17
TINY = {
    "name": "tiny-dense", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "vocab_size": 512, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "quantization": "int8",
}
TINY_MOE = dict(TINY, name="tiny-moe", num_local_experts=4, num_experts_per_tok=2)
#: computed on the parent commit (8dc43c7: weights.make_tree, reference.logits_at)
#: with the functions below; the first served-side argmax tokens stand beside
#: the hashes so that a failure says whether the numbers moved or only bits
PINNED = {
    "tiny-dense": {
        "tree": "972b3cb754e86e8680d58b92c585f8ed9e46e9d6912d01a7388ba31a2a31accf",
        "logits_int8": "1f262db3466dfc04d4c56e142ad32e8f3b3456cd773195f18296088c18baf7b5",
        "logits_int4": "cc57b815a6d1f3077b81ec94c70f859f992cb6ec84ce0f945d212b7acdaeeeb1",
        "logit_sum": 6972.3017578125,
        "row_max_int8": [3.44917, 3.33777, 3.1076, 2.63556, 2.66635, 3.65099, 3.19785, 3.54188,
            3.50539, 3.81214, 3.29705, 2.38049, 2.99685, 3.13355, 2.58842, 3.11724, 2.65978],
        "row_max_int4": [3.35166, 3.50405, 3.32225, 2.86315, 2.70823, 3.63231, 3.54489,
            3.39957, 3.25375, 3.10571, 3.4842, 2.47542, 2.91931, 3.33668, 3.02557, 3.99632,
            3.29307],
        "argmax": [250, 250, 247, 42, 289, 204, 250, 321, 120, 211, 249, 505, 269, 12, 77,
                   189, 442],
    },
    "tiny-moe": {
        "tree": "bf390a4ef5c8a88adecdd7df420c60c1b6daa9e60ab56dc8f122bb7a05be1865",
        "logits_int8": "9b4b6dd14813f01925165d121266ce83bf5e4c376f2515fdb4ac42c91450fadb",
        "logits_int4": "3fdf7acda6e1fc1bbe08eda3524b8d5f17f808126317bba60637f772acd865e1",
        "logit_sum": 6972.87744140625,
        "row_max_int8": [2.56586, 2.57166, 3.14859, 2.90346, 3.12438, 2.99319, 3.3764, 3.67358,
            3.92579, 2.95339, 3.17029, 2.6722, 3.43117, 3.55135, 3.36788, 3.1851, 3.20478],
        "row_max_int4": [2.59159, 3.24028, 3.07378, 3.1763, 3.19244, 3.23361, 3.36397, 3.48673,
            3.31322, 2.63878, 3.76542, 3.08114, 2.84575, 3.55549, 3.78554, 3.44096, 3.24735],
        "argmax": [240, 311, 240, 480, 437, 149, 281, 350, 443, 500, 343, 328, 204, 229, 343,
                   242, 194],
    },
}


def _tree_hash(tree):
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _arrays_hash(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _inputs():
    rng = np.random.default_rng(7)
    sequences = [rng.integers(3, 512, size=n).tolist() for n in (37, 90)]
    return sequences, [list(range(30, 37)), list(range(80, 90))]


# -- the move is exact -------------------------------------------------------------


@pytest.mark.parametrize("config", [TINY, TINY_MOE], ids=lambda c: c["name"])
def test_the_seeded_tree_is_the_parents_bit_for_bit(config):
    family = M.load_family(config)
    tree = family.make_tree(SEED, family.dims_of(config))
    assert _tree_hash(tree) == PINNED[config["name"]]["tree"]


@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4-control"])
@pytest.mark.parametrize("config", [TINY, TINY_MOE], ids=lambda c: c["name"])
def test_the_reference_logits_are_the_parents_bit_for_bit(config, bits):
    import reference

    family = M.load_family(config)
    sequences, rows = _inputs()
    logits, margins, clock = reference.logits_at(
        family, SEED, family.dims_of(config), sequences, rows, bits=bits
    )
    pinned = PINNED[config["name"]]
    assert [lg.shape for lg in logits] == [(7, 512), (10, 512)]
    assert [m.shape for m in margins] == [(7,), (10,)]
    assert set(clock) == {"weights_s", "layers_s"}
    if bits == 8:
        assert [int(x) for lg in logits for x in lg.argmax(-1)] == pinned["argmax"]
        assert float(sum(np.abs(lg).sum() for lg in logits)) == pytest.approx(
            pinned["logit_sum"], rel=1e-5
        )
    row_max = [float(x) for lg in logits for x in lg.max(-1)]
    assert row_max == pytest.approx(pinned[f"row_max_int{bits}"], abs=2e-4)
    if _arrays_hash(logits + margins) != pinned[f"logits_int{bits}"]:
        # bit for bit on the CPU the hashes were taken on; another CPU's float32
        # matmul may round its sums in another order, and then the numbers above decide
        import platform
        import warnings

        warnings.warn(f"reference logits differ in bits from the parent's ({platform.machine()})")


def test_a_routed_layers_margins_are_finite_and_a_dense_layers_are_not():
    import reference

    sequences, rows = _inputs()
    for config, finite in ((TINY, False), (TINY_MOE, True)):
        family = M.load_family(config)
        _lg, margins, _c = reference.logits_at(
            family, SEED, family.dims_of(config), sequences, rows
        )
        assert all(np.isfinite(m).all() == finite for m in margins)


# -- what a family file has to hold ---------------------------------------------------


def test_the_family_of_a_configuration():
    assert M.family_name({}) == "llama"  # the two files that are there name none
    assert M.family_name({"family": "latent"}) == "latent"
    llama = M.load_family({})
    assert llama is M.load_family({"family": "llama"})
    for name in M.FAMILY_INTERFACE:
        assert hasattr(llama, name)
    assert set(llama.SCOPE_WORK) == {"mtpu.expert_scan"}
    with pytest.raises(KeyError, match="no family file"):
        M.load_family({"family": "no-such-family"})
    with pytest.raises(KeyError, match="no family file"):
        M.load_family({"family": "../manifest"})


def test_reading_the_work_functions_does_not_import_jax():
    """The load generator's process reads them and may not hold JAX."""
    import subprocess

    code = (
        "import sys, json; sys.path.insert(0, sys.argv[1]); import manifest as M\n"
        "c = json.load(open(sys.argv[1] + '/configs/mistral-7b-int8.json'))\n"
        "f = M.load_family(c); M.load_readers()\n"
        "assert f.decode_step(c, 8, 4000)['bytes'] > 7e9 and f.prefill(c, [2048], 1)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code, str(BENCH)], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def _with_config(tmp_path, **keys):
    """The manifest with its first configuration's file replaced by a copy
    that carries ``keys``, under a root of its own."""
    manifest = copy.deepcopy(MANIFEST)
    entry = manifest["configs"][0]
    config = dict(json.loads((ROOT / entry["file"]).read_text()), **keys)
    for c in manifest["configs"]:
        target = tmp_path / c["file"]
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(
            json.dumps(config) if c is entry else (ROOT / c["file"]).read_text()
        )
    return manifest


def test_problems_names_a_missing_family_file(tmp_path):
    manifest = _with_config(tmp_path, family="latent-moe")
    assert M.problems(manifest, tmp_path) == [
        "config mistral-7b-int8: family latent-moe: no file families/latent-moe.py"
    ]
    assert M.problems(_with_config(tmp_path, family="bad name"), tmp_path)


def test_problems_names_a_family_file_short_of_the_interface(monkeypatch, tmp_path):
    families = tmp_path / "families"
    families.mkdir()
    (families / "half.py").write_text(
        "def dims_of(config): ...\ndef make_tree(seed, dims): ...\n"
        "def logits_at(seed, dims, sequences, rows, bits=8): ...\n"
        "if True:\n    def prefill(config, prompt_lengths, calls): ...\n"  # not top level
    )
    (families / "llama.py").write_text((BENCH / "families/llama.py").read_text())
    monkeypatch.setattr(M, "HERE", tmp_path)
    assert M.family_problems("llama") == []
    assert M.family_problems("half") == [
        "family half: families/half.py lacks program_config, decode_step, prefill, SCOPE_WORK"
    ]


@pytest.mark.parametrize("keys, refused", [
    (["vocab_size", "n_routed_experts", "num_hidden_layers"], []),
    (["num_local_experts", "num_attention_heads", "n_group", "first_k_dense_replace"], []),
    (["hidden_size"], ["hidden_size"]),
    (["head_dim"], ["head_dim"]),
    (["kv_lora_rank"], ["kv_lora_rank"]),
    (["vocab_size", "moe_intermediate_size", "num_experts_per_tok", "qk_rope_head_dim"],
     ["moe_intermediate_size", "num_experts_per_tok", "qk_rope_head_dim"]),
    (["ssm_state_size", "d_model", "expansion_factor", "a b"],
     ["ssm_state_size", "d_model", "expansion_factor", "a b"]),
])
def test_reduced_takes_counts_of_rows_experts_heads_and_layers_and_no_width(keys, refused):
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"][0]["reduced"] = keys
    assert M.problems(manifest, ROOT) == [
        f"config mistral-7b-int8: reduced names a width: {key}" for key in refused
    ]


# -- nothing outside the family's file knows a layer's shape --------------------------


def test_no_other_file_of_the_harness_names_the_familys_keys():
    named = re.compile(
        r"\b(k_pages|v_pages|LlamaConfig|num_local_experts|num_key_value_heads)\b"
    )
    hits = [
        f"{path.relative_to(BENCH)}: {m.group(0)}"
        for path in BENCH.rglob("*.py") if path.name != "llama.py"
        for m in named.finditer(path.read_text())
    ]
    assert hits == []
