"""Kernel probes (ops/probes.py): every Pallas-kernel module has a registered
probe, and the probes run green in-process — in interpret mode here, through
Mosaic when ``chip_smoke.py`` runs the same callables on the TPU.
"""

from __future__ import annotations

import re
from pathlib import Path

import pytest

from modal_examples_tpu.ops.probes import (
    CELL_FLASH_PROBES,
    KERNEL_PROBES,
    PROBED_MODULES,
    model_geometry_probes,
)

OPS_DIR = Path(__file__).resolve().parent.parent / "modal_examples_tpu" / "ops"


class TestRegistryCoverage:
    def test_every_pallas_module_has_a_probe(self):
        """Any module calling pl.pallas_call needs an entry in
        PROBED_MODULES (mapping module -> its probe names) and those probes
        registered, so the chip smoke compiles every kernel."""
        pkg_root = OPS_DIR.parent
        pallas_modules = set()
        for f in pkg_root.rglob("*.py"):
            if f.name == "probes.py":
                continue
            code = "\n".join(
                line.split("#")[0] for line in f.read_text().splitlines()
            )
            if re.search(r"\bpl\.pallas_call\s*\(", code):
                pallas_modules.add(
                    str(f.relative_to(pkg_root.parent))
                    .removesuffix(".py").replace("/", ".")
                )
        assert pallas_modules == set(PROBED_MODULES), (
            "pallas_call callers and PROBED_MODULES disagree — a new kernel "
            "module must register probes in ops/probes.py: "
            f"{pallas_modules ^ set(PROBED_MODULES)}"
        )
        registered = {p for probes in PROBED_MODULES.values() for p in probes}
        assert registered == set(KERNEL_PROBES)


class TestProbesRun:
    def test_ragged_decode_probe_green(self):
        assert KERNEL_PROBES["ragged_decode"]()["max_err"] < 0.06

    @pytest.mark.slow
    def test_full_registry_green(self):
        for name, probe in KERNEL_PROBES.items():
            assert probe(), name

    def test_cell_flash_probes_at_a_small_geometry(self):
        """The docqa chunk probes' own function at the two head layouts
        (grouped at width 128; ungrouped, 192-wide q/k over 128-wide values,
        the reference held to some heads), small enough for the interpreter
        and still several tiles long."""
        from modal_examples_tpu.ops.probes import probe_flash_chunked

        assert set(CELL_FLASH_PROBES) == {
            "docqa_flash_chunk_gqa", "docqa_flash_chunk_mla",
        }
        for probe in CELL_FLASH_PROBES.values():
            assert probe.func is probe_flash_chunked
        assert probe_flash_chunked(1, 8, 2, 768, 128, 384)["max_err"] < 0.06
        assert probe_flash_chunked(
            1, 4, 4, 768, 192, 384, Dv=128, ref_kv_heads=2
        )["max_err"] < 0.06

    def test_model_geometry_probes_at_a_small_geometry(self):
        """The cases chip_smoke adds at the smoke model's shapes, at a
        geometry small enough for the interpreter: GQA picks the grouped
        variant, and 12 pages do not divide into its 8-page chunks."""
        probes = model_geometry_probes(
            n_heads=8, n_kv_heads=2, head_dim=128, n_layers=2, page_size=16,
            pages_per_seq=12, slots=3, prefill_batch=2, prefill_bucket=128,
        )
        assert set(probes) == {
            "model_ragged_bf16kv", "model_ragged_int8kv",
            "model_scatter_bf16kv", "model_scatter_int8kv",
            "model_flash_prefill", "model_flash_chunked",
        }
        for name, probe in probes.items():
            assert probe(), name
