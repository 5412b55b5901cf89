"""The compile-cache placement rule (utils/compile_cache.py): one place
decides. ``JAX_COMPILATION_CACHE_DIR`` set -> JAX keeps its cache there and
nothing is written anywhere else; unset -> the entry points export one fixed
path inside the checkout, with no fingerprint, pid, time or temporary name.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

from modal_examples_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent

# a child that goes through an entry point's placement and then compiles:
# prints the helper's answer and how many entries JAX wrote there
_CHILD = """
import os, sys
from modal_examples_tpu.utils.compile_cache import place_compile_cache
placed = place_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
print(placed)
print(len(os.listdir(placed)) if os.path.isdir(placed) else 0)
"""


def _run_child(tmp_path, **env) -> list[str]:
    child_env = {
        **os.environ,
        "PYTHONPATH": str(REPO),
        "JAX_PLATFORMS": "cpu",
        # every entry cacheable, whatever the suite's own threshold is
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        **env,
    }
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=child_env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.strip().splitlines()[-2:]


class TestPlacement:
    def test_env_set_is_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path / "mine"))
        assert compile_cache.place_compile_cache() == str(tmp_path / "mine")
        assert os.environ[compile_cache.CACHE_DIR_ENV] == str(tmp_path / "mine")
        assert not (tmp_path / "mine").exists()  # JAX creates it, on use

    def test_unset_exports_the_fixed_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        placed = compile_cache.place_compile_cache()
        assert placed == str(REPO / ".xla_cache")
        assert os.environ[compile_cache.CACHE_DIR_ENV] == placed
        # git ignores it (the driver's checkout holds only committed files)
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", ".xla_cache/entry"], cwd=str(REPO)
        )
        assert ignored.returncode == 0

    def test_empty_value_counts_as_unset(self, monkeypatch):
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "")
        assert compile_cache.place_compile_cache() == str(REPO / ".xla_cache")

    def test_the_default_is_the_same_in_every_process(self, monkeypatch):
        """No CPU fingerprint, pid, time or temporary name: a fresh process
        — another host, another day — computes the identical path."""
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        code = (
            "from modal_examples_tpu.utils.compile_cache import "
            "place_compile_cache as p; print(p())"
        )
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.CACHE_DIR_ENV}
        outs = {
            subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                env={**env, "PYTHONPATH": str(REPO), "HOME": home},
                check=True,
            ).stdout.strip()
            for home in ("/tmp", "/root")
        }
        assert outs == {str(REPO / ".xla_cache")}

    def test_the_package_stays_jax_free(self):
        code = (
            "import sys; import modal_examples_tpu.utils.compile_cache as c; "
            "c.place_compile_cache(); print('jax' in sys.modules)"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": str(REPO)}, check=True,
        )
        assert out.stdout.strip() == "False"


class TestJaxKeepsItsCacheWhereTheEnvironmentSays:
    def test_entries_land_in_the_named_directory_only(self, tmp_path):
        mine = tmp_path / "some" / "dir"
        before = set((REPO / ".xla_cache").glob("*"))
        placed, n_entries = _run_child(
            tmp_path, HOME=str(tmp_path),
            **{compile_cache.CACHE_DIR_ENV: str(mine)},
        )
        assert placed == str(mine)
        assert int(n_entries) > 0  # JAX read the variable itself
        # nothing written elsewhere: not the in-checkout default, not HOME
        assert set((REPO / ".xla_cache").glob("*")) == before
        assert not (tmp_path / ".cache").exists()


class TestNothingElseSetsADirectory:
    def test_one_helper_owns_the_cache_directory(self):
        """The acceptance grep: outside utils/compile_cache.py nothing in
        the package, bench.py, examples/ or tests/ names the jax config key,
        the old override variable or the old fingerprint."""
        import re

        banned = re.compile(  # spelled in pieces: this file is searched too
            "jax_compilation" "_cache_dir|MTPU_COMPILE" "_CACHE|_machine" "_tag"
        )
        offenders = []
        for root in ("modal_examples_tpu", "examples", "tests", "benchmarks"):
            for f in (REPO / root).rglob("*.py"):
                if banned.search(f.read_text()):
                    offenders.append(str(f.relative_to(REPO)))
        if banned.search((REPO / "bench.py").read_text()):
            offenders.append("bench.py")
        assert offenders == []
