"""Framework-wide configuration: state dir, backend selection, env knobs.

The reference platform keeps all durable state (volumes, deployed apps,
dicts/queues) in a closed-source control plane reached over gRPC. Our local
control plane is a state directory on disk (cheap, inspectable, works in CI);
the layout is designed so a networked metadata service can replace it later
without changing any caller. (Spec: reference examples treat these objects as
named, durable, cross-process — e.g. ``modal.Volume.from_name`` in
``06_gpu_and_ml/llm-serving/vllm_inference.py:77-81``.)
"""

from __future__ import annotations

import os
from pathlib import Path

#: Execution backend for ``.remote``-family calls.
#:   "process" — containers are supervised worker processes (default; the
#:               local analog of Modal's per-container runners).
#:   "inline"  — run in the caller's process with a serialization round-trip
#:               (used for single-chip benches so the TPU stays owned by the
#:               caller, and for debugging).
BACKEND_ENV = "MTPU_BACKEND"

#: Root of the local control plane (volumes, deployments, dicts, queues).
STATE_DIR_ENV = "MTPU_STATE_DIR"

#: Set inside containers so user code can detect remote execution
#: (reference analog: ``MODAL_TASK_ID``, simple_torch_cluster.py:111).
TASK_ID_ENV = "MTPU_TASK_ID"


def backend() -> str:
    return os.environ.get(BACKEND_ENV, "process")


def state_dir() -> Path:
    root = os.environ.get(STATE_DIR_ENV)
    if root:
        p = Path(root)
    else:
        p = Path.home() / ".mtpu"
    p.mkdir(parents=True, exist_ok=True)
    return p


def in_container() -> bool:
    return TASK_ID_ENV in os.environ


def task_id() -> str | None:
    return os.environ.get(TASK_ID_ENV)
