"""Test harness configuration.

Forces the CPU backend with an 8-device virtual mesh (the reference has no
fake backend — SURVEY.md §4 calls out that we add one so multi-chip SPMD
paths are testable without TPUs: ``xla_force_host_platform_device_count``),
and isolates the framework's state dir per test session.
"""

import os
import sys
import tempfile
from pathlib import Path

_REPO_ROOT = str(Path(__file__).resolve().parent.parent)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# Must happen before any jax import anywhere in the test process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Isolate the local control plane (volumes/dicts/queues/apps) per test
# session.
_state_tmp = tempfile.mkdtemp(prefix="mtpu-test-state-")
os.environ.setdefault("MTPU_STATE_DIR", _state_tmp)

# Engine strict mode: a scheduler-loop exception stops the engine and
# releases callers with finish_reason="error" instead of being swallowed
# (the round-2 flake postmortem — NOTES.md "engine flake closeout").
os.environ.setdefault("MTPU_ENGINE_STRICT", "1")

# The suite's XLA compile cache is private to the session: a fresh temporary
# directory, inherited by every container and CLI child the tests spawn, so
# engines built by different tests reuse each other's programs (about a
# third of the suite's time) without sharing anything on disk with another
# session or host (tier-1 used to die with rc 139 inside XLA:CPU's cache
# read when every test engine read one directory under ~/.cache; full runs
# against a fresh directory, cold and warm, do not), and without filling
# the in-checkout directory the entry points use. A directory the
# environment already names is JAX's to read; nothing here overrides it.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import atexit
    import shutil

    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="mtpu-test-xla-cache-"
    )
    atexit.register(
        shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True
    )
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


@pytest.fixture()
def state_dir():
    return Path(os.environ["MTPU_STATE_DIR"])


@pytest.fixture(scope="session")
def jax_cpu():
    """The jax module, on the CPU backend the environment above selects."""
    import jax

    return jax


@pytest.fixture(scope="session", autouse=True)
def _engine_error_sentinel():
    """Assert that NO engine anywhere in the suite recorded a scheduler
    exception — the regression net for the round-2 intermittent
    output-mismatch flake (NOTES.md). Reads the eagerly-recorded class-level
    report list, so engines garbage-collected mid-session are still
    covered."""
    yield
    try:
        from modal_examples_tpu.serving.engine import LLMEngine
    except Exception:
        return
    reports = list(LLMEngine._error_reports)
    assert not reports, f"engines recorded scheduler errors: {reports}"
