"""Fused adaptive speculative decoding (docs/speculative.md).

The engine-scheduler-integrated speculation runtime: one jitted
propose+verify+accept round per dispatch (:mod:`.runtime`, built on
``ops.scan_loop.masked_scan`` and emitting the harvest plane), the
detokenization worker its harvests feed (:mod:`.detok`),
plus the acceptance-driven per-request γ policy (:mod:`.controller`).
The standalone ``serving.speculative`` loop is NOT part of the serving
path anymore — it survives only as the reference oracle for parity tests
(enforced statically in tests/test_static.py)."""

from .controller import AdaptiveGammaController
from .runtime import (
    SPEC_ADAPTIVE_ENV,
    accept_reject,
    build_ngram_round_fn,
    build_spec_round_fn,
    resolve_spec_adaptive,
)

__all__ = [
    "AdaptiveGammaController",
    "SPEC_ADAPTIVE_ENV",
    "accept_reject",
    "build_ngram_round_fn",
    "build_spec_round_fn",
    "resolve_spec_adaptive",
]
