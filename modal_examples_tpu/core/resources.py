"""TPU resource specs — the TPU-native replacement for ``gpu=``.

The reference requests accelerators with typed strings and fallback lists:
``gpu="H200:8"`` (vllm_inference.py:133), ``gpu=["h100", "a100", "any"]``
(gpu_fallbacks.py:20-23). Our equivalent is topology-aware: ``tpu="v5e-8"``
names a generation *and* a slice size, from which chips-per-host, host count,
and the default device mesh all derive. This module is pure parsing — no jax
import — so the client SDK stays light; mesh construction from a spec lives in
``modal_examples_tpu.parallel.mesh``.
"""

from __future__ import annotations

import dataclasses
import re

# generation -> (chips per host, HBM GiB per chip, bf16 peak TFLOP/s per chip)
# Peaks are the published per-chip figures (Google Cloud TPU documentation,
# "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system architecture pages).
TPU_GENERATIONS: dict[str, tuple[int, int, float]] = {
    "v4": (4, 32, 275.0),
    "v5e": (8, 16, 197.0),  # v5 lite
    "v5p": (4, 95, 459.0),
    "v6e": (8, 32, 918.0),
}

# generation -> HBM bandwidth GB/s per chip (same source): the MBU
# denominator the roofline meter (observability/usage.py) normalizes decode
# byte traffic against.
TPU_HBM_GBPS: dict[str, float] = {
    "v4": 1228.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6e": 1640.0,
}

# jax ``device_kind`` -> generation, so a process on a TPU reads its peaks
# from the device it is on (observability.usage.resolve_peaks)
DEVICE_KIND_GENERATION: dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}

_SPEC_RE = re.compile(r"^(?P<gen>v\d+[a-z]*)(?:-(?P<chips>\d+))?$", re.IGNORECASE)


class InvalidTPUSpec(ValueError):
    pass


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """A parsed TPU slice request.

    ``tpu="v5e-8"`` -> generation v5e, 8 chips, 1 host.
    ``tpu="v5p-128"`` -> 128 chips, 32 hosts (4 chips/host).
    A bare generation (``tpu="v5e"``) means one chip.
    """

    generation: str
    chips: int

    @property
    def chips_per_host(self) -> int:
        return TPU_GENERATIONS[self.generation][0]

    @property
    def hosts(self) -> int:
        cph = self.chips_per_host
        return max(1, (self.chips + cph - 1) // cph)

    @property
    def hbm_gib_per_chip(self) -> int:
        return TPU_GENERATIONS[self.generation][1]

    @property
    def bf16_tflops_per_chip(self) -> float:
        return TPU_GENERATIONS[self.generation][2]

    @property
    def hbm_gbps_per_chip(self) -> float:
        return TPU_HBM_GBPS[self.generation]

    @property
    def multi_host(self) -> bool:
        return self.hosts > 1

    def __str__(self) -> str:
        return f"{self.generation}-{self.chips}"


def parse_tpu_spec(spec: str) -> TPUSpec:
    m = _SPEC_RE.match(spec.strip())
    if not m:
        raise InvalidTPUSpec(
            f"invalid tpu spec {spec!r}; expected e.g. 'v5e-8', 'v4-16', 'v5e'"
        )
    gen = m.group("gen").lower()
    if gen not in TPU_GENERATIONS:
        raise InvalidTPUSpec(
            f"unknown TPU generation {gen!r}; known: {sorted(TPU_GENERATIONS)}"
        )
    chips = int(m.group("chips") or 1)
    if chips < 1:
        raise InvalidTPUSpec("chip count must be >= 1")
    return TPUSpec(generation=gen, chips=chips)


def parse_tpu_request(
    tpu: str | list[str] | tuple[str, ...] | None,
) -> list[TPUSpec]:
    """Parse a ``tpu=`` argument into an ordered preference list.

    Mirrors the reference's ordered GPU fallback lists
    (gpu_fallbacks.py:20-23): the scheduler tries each spec in order until
    capacity is found.
    """
    if tpu is None:
        return []
    if isinstance(tpu, str):
        return [parse_tpu_spec(tpu)]
    specs = [parse_tpu_spec(s) for s in tpu]
    if not specs:
        raise InvalidTPUSpec("empty tpu fallback list")
    return specs
