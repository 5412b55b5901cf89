"""``BENCHMARK.json`` and the data files it names.

Whatever belongs to one configuration, one traffic mix, one cell or one
metric sits in a file of its own, found by name:

- ``configs/<configuration>.json``: the published sizes as run, ``reduced``,
  ``assumed``, the deployment, the engine's settings;
- ``mixes/<traffic>.json``: the mix's parameters, read by ``traffic.py``;
- ``cells/<workload>.json`` (optional): what belongs to the pair, such as the
  paced rate that a sweep found for this configuration under this mix or its
  number of closed-loop clients; its keys overlay the mix's;
- ``families/<family>.py``: everything that knows a layer's shape (below);
- ``layers/*.py``: readers, each a ``METRICS`` table from a quantity's name
  to a function of the run's data. A metric's name in ``BENCHMARK.json`` is
  ``[<variant>.]<quantity>``: one quantity read in cells that report
  different end-to-end metrics has an entry for each (``reason.decode_dev_ms``
  moves ``out_tok_s``, ``closed.decode_dev_ms`` moves ``req_s``), and both
  find the reader ``decode_dev_ms``.

A configuration's file names its ``family`` (absent: ``llama``); nothing
dispatches on a model's or a cell's name. To add a family, add one file
``families/<family>.py`` with (:data:`FAMILY_INTERFACE`): ``dims_of(config)``,
the sizes its generator and reference need; ``make_tree(seed, dims)``, the
whole seeded tree for the engine in one jitted call (from ``weights.py``'s
primitives; what its reference needs to make one layer again alone is its
own affair); ``program_config(config_file)``, what ``LLMEngine`` is given
(``server_app.py`` builds the engine itself: the normal path is one path);
``logits_at(seed, dims, sequences, rows, bits) -> (logits, margins, clock)``,
its plain float32 ``highest`` forward pass over padded token ids, layers
outermost, ``bits=4`` the control (``reference.py`` pads, takes the gaps and
decides); ``decode_step(config, batch, context_tokens)`` and ``prefill(config,
prompt_lengths, calls)`` returning ``{"flops", "bytes"}`` for the rooflines;
and ``SCOPE_WORK``, a table from ``mtpu.*`` scope to ``fn(config, tokens,
calls)`` returning the same for the tokens of one kind of program call, the
window's prefill calls or its decode steps (``layers/scopes.py``). The
file imports JAX only inside the functions that need it: the load
generator's process reads the work functions and may not hold JAX.

So a later PR adds cells, mixes, configurations, families and metrics by
adding files and manifest entries, and edits none.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
FAMILY_INTERFACE = ("dims_of", "make_tree", "program_config", "logits_at",
                    "decode_step", "prefill", "SCOPE_WORK")
#: ``reduced`` may name a key that counts rows, experts, heads or layers
#: (``model-configs`` section 4: the chip's share; ``vocab_size`` counts
#: rows), never a width: a hidden, intermediate, latent, state, projection or
#: head size, an expansion factor, the experts a token is routed to
WIDTHS = re.compile(r"(_dim|_rank|_size|_width|_factor|_per_tok)$|^d_\w+$")
ROW_COUNTS = ("vocab_size",)


def load_manifest(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def resolve(manifest: dict, workload: str, root: Path) -> dict:
    """The cell, its configuration and its mix (overlaid by the cell's own
    file), and the metrics the cell reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config_file = Path(root) / entry["file"]
    config = json.loads(config_file.read_text())
    mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())
    cell_file = HERE / "cells" / f"{workload}.json"
    if cell_file.is_file():
        mix.update(json.loads(cell_file.read_text()))

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell, "config": config, "config_file": str(config_file), "mix": mix,
        "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
        "per_layer": [m for m in manifest["per_layer"] if reported(m)],
    }


def quantity(metric_name: str) -> str:
    """The reader's name: the metric's, less its variant."""
    return metric_name.rpartition(".")[2]


def family_name(config: dict) -> str:
    return config.get("family", "llama")


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_families: dict = {}


def _family_file(name: str) -> Path | None:
    path = HERE / "families" / f"{name}.py"
    return path if NAME.match(name) and path.is_file() else None


def load_family(config: dict):
    """The module ``families/<family>.py`` of a configuration, loaded once."""
    name = family_name(config)
    if name not in _families:
        path = _family_file(name)
        if path is None:
            raise KeyError(f"no family file families/{name}.py")
        _families[name] = _load(path, f"bench_family_{name}")
    return _families[name]


def family_problems(name: str) -> list[str]:
    """A family file that is missing, or short of the interface. Read, not
    run: the file may import the program."""
    path = _family_file(name)
    if path is None:
        return [f"family {name}: no file families/{name}.py"]
    defined: set = set()
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            defined.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            defined.update(
                n.id for t in targets for n in ast.walk(t) if isinstance(n, ast.Name)
            )
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            defined.update((a.asname or a.name).split(".")[0] for a in node.names)
    missing = [n for n in FAMILY_INTERFACE if n not in defined]
    return [f"family {name}: families/{name}.py lacks {', '.join(missing)}"] if missing else []


def reduced_problem(key: str) -> bool:
    """Whether ``reduced`` may not name this key: a width, or no name."""
    return not NAME.match(key) or (key not in ROW_COUNTS and bool(WIDTHS.search(key)))


def load_readers() -> dict:
    """quantity -> reader, from every file under ``layers/``. Two files that
    claim one name are an error."""
    readers: dict = {}
    for path in sorted((HERE / "layers").glob("*.py")):
        module = _load(path, f"bench_layer_{path.stem}")
        for name, fn in module.METRICS.items():
            if name in readers:
                raise ValueError(f"metric {name!r} has two readers ({path.name})")
            readers[name] = fn
    return readers


def problems(manifest: dict, root: Path) -> list[str]:
    """What the builder's contract would refuse, as far as a file check can
    tell: names, units, references between entries, files that exist."""
    out: list[str] = []
    wanted = {"command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end", "per_layer"}
    if set(manifest) != wanted:
        out.append(f"keys {sorted(manifest)} != {sorted(wanted)}")
        return out
    if not (isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51):
        out.append("run_seconds must be a whole number from 1 to 51")
    paths = [str(p) for p in manifest["paths"]]
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [*configs, *cells]
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    for name in names + metric_names:
        if not NAME.match(name):
            out.append(f"bad name {name!r}")
    for group in (list(configs), list(cells), metric_names):
        if len(set(group)) != len(group):
            out.append(f"duplicate names in {sorted(group)}")
    for c in configs.values():
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c['name']}: keys {sorted(c)}")
        if not any(c["file"].startswith(p + "/") for p in paths):
            out.append(f"config {c['name']}: file outside paths")
        if not (Path(root) / c["file"]).is_file():
            out.append(f"config {c['name']}: {c['file']} missing")
        else:
            family = family_name(json.loads((Path(root) / c["file"]).read_text()))
            out.extend(f"config {c['name']}: {p}" for p in family_problems(family))
        for key in c["reduced"]:
            if reduced_problem(key):
                out.append(f"config {c['name']}: reduced names a width: {key}")
        if not any(w["config"] == c["name"] for w in cells.values()):
            out.append(f"config {c['name']} is used by no cell")
    seen_pairs = set()
    for w in cells.values():
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w['name']}: keys {sorted(w)}")
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]):
            out.append(f"workload {w['name']}: bad traffic name")
        if not (HERE / "mixes" / f"{w['traffic']}.json").is_file():
            out.append(f"workload {w['name']}: no mix file for {w['traffic']}")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            out.append(f"workload {w['name']}: why must be one line of 1..200")
        if (w["config"], w["traffic"]) in seen_pairs:
            out.append(f"workload {w['name']}: pair appears twice")
        seen_pairs.add((w["config"], w["traffic"]))
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            out.append(f"metric {m['name']}: keys {sorted(m)}")
        if not 0 < m.get("bound", 0) <= 0.1:
            out.append(f"metric {m['name']}: bound {m.get('bound')}")
        if m.get("source") not in ("host_clock", "device_trace"):
            out.append(f"metric {m['name']}: an end-to-end source is host_clock or device_trace")
    for m in manifest["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            out.append(f"metric {m['name']}: keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            out.append(f"metric {m['name']}: source {m.get('source')}")
        if m.get("moves") not in e2e:
            out.append(f"metric {m['name']}: moves {m.get('moves')!r}, no such end-to-end metric")
            continue
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            if w not in cells:
                out.append(f"metric {m['name']}: unknown workload {w}")
            elif "workloads" in moved and w not in moved["workloads"]:
                out.append(f"metric {m['name']}: {w} does not report {m['moves']}")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m.get("unit", "")):
            out.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better {m.get('better')!r}")
    for w in cells:
        mine = [m for m in manifest["end_to_end"]
                if "workloads" not in m or w in m["workloads"]]
        if len(mine) < 2:
            out.append(f"workload {w}: reports no end-to-end metric besides setup_s")
        if not any("workloads" not in m or w in m["workloads"] for m in manifest["per_layer"]):
            out.append(f"workload {w}: reports no per-layer metric")
    return out
