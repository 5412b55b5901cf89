"""chip_smoke.py off the chip: it must fail here, fast and without a result;
its plumbing (legs, HTTP client, pass conditions) is rehearsed at tiny size
behind ``--rehearse-cpu``; and its judgement of a leg is checked on fakes."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

import chip_smoke

REPO = Path(__file__).resolve().parent.parent


def _run(args: list[str], cwd: Path = REPO, timeout: float = 600):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(cwd / "chip_smoke.py"), *args],
        cwd=str(cwd), capture_output=True, text=True, timeout=timeout,
    )
    return proc, time.monotonic() - t0


def _result_lines(stdout: str) -> list[dict]:
    return [
        json.loads(l) for l in stdout.splitlines()
        if l.startswith("{") and '"ok"' in l
    ]


class TestOffTheChip:
    def test_plain_invocation_fails_before_compiling_anything(self):
        proc, elapsed = _run([])
        assert proc.returncode != 0
        assert "no TPU" in proc.stderr
        assert _result_lines(proc.stdout) == []
        # the device leg is the only one that started
        assert "leg device" in proc.stdout
        assert "leg kernels" not in proc.stdout
        assert elapsed < 60

    def test_alone_in_a_directory_it_fails_without_a_result(self, tmp_path):
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        proc, _ = _run([], cwd=tmp_path)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""


class TestRehearsal:
    def test_one_server_leg_end_to_end(self, tmp_path):
        """The leg the parent would spawn for ``server-pallas``, run directly:
        App.run() -> LLMServer.serve() -> HTTP client -> pass conditions, on
        the tiny model with the Pallas decode path in interpret mode."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py"), "--leg",
             "server-pallas", "--paged-impl", "pallas", "--scatter-impl",
             "pallas", "--rehearse-cpu"],
            cwd=str(REPO), capture_output=True, text=True, timeout=600,
            env={**os.environ, "PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
        lines = proc.stdout.strip().splitlines()
        assert json.loads(lines[-1]) == {"leg": "server-pallas", "ok": True}
        plan = json.loads(
            next(l for l in lines if l.startswith("impl_plan: "))[11:]
        )
        assert (plan["attention"], plan["variant"], plan["scatter"]) == (
            "ragged", "grouped", "pallas"
        )
        assert plan["downgraded"] == "0"
        assert plan["allocator"] in ("native", "python")
        assert plan["state_step"] == "-"  # a llama model keeps no per-slot state
        summary = json.loads(lines[-2])
        assert summary["requests_sent"] == summary["succeeded"] == 9
        assert summary["failed"] == summary["error_count"] == 0
        assert summary["mean_decode_batch"] > 1.5

    @pytest.mark.slow
    def test_all_legs_pass_at_tiny_size_on_the_cpu(self):
        proc, _ = _run(["--rehearse-cpu"])
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
        final = json.loads(proc.stdout.strip().splitlines()[-1])
        assert final["ok"] is True
        assert final["device"]["platform"] == "cpu"
        assert "not a chip result" in final["rehearsal"]
        for leg in ("device", "kernels", "server-default", "server-pallas",
                    "server-pallas-int8kv"):
            assert f"=== leg {leg} passed" in proc.stdout, leg


@pytest.fixture()
def fake_leg(tmp_path, monkeypatch):
    """Point run_leg at a stand-in chip_smoke.py whose body the test writes."""
    monkeypatch.setattr(chip_smoke, "HERE", tmp_path)
    monkeypatch.setattr(chip_smoke, "LOG_DIR", tmp_path / "logs")

    def write(body: str) -> None:
        (tmp_path / "chip_smoke.py").write_text(textwrap.dedent(body))

    return write


class TestLegJudgement:
    def test_a_passing_leg_returns_its_result(self, fake_leg):
        fake_leg("""
            print("some output")
            print('{"leg": "x", "ok": true, "device": {"platform": "tpu"}}')
        """)
        out = chip_smoke.run_leg("x", [], dict(os.environ), 30)
        assert out["device"] == {"platform": "tpu"}

    def test_exit_0_after_catching_its_own_failure_fails(self, fake_leg):
        fake_leg("""
            try:
                raise RuntimeError("a phase failed")
            except RuntimeError:
                print('{"leg": "x", "ok": false}')
        """)
        with pytest.raises(chip_smoke.LegFailed, match="without reporting ok"):
            chip_smoke.run_leg("x", [], dict(os.environ), 30)

    def test_nonzero_exit_fails_even_if_it_printed_ok(self, fake_leg):
        fake_leg("""
            print('{"leg": "x", "ok": true}')
            raise SystemExit(3)
        """)
        with pytest.raises(chip_smoke.LegFailed, match="exited with code 3"):
            chip_smoke.run_leg("x", [], dict(os.environ), 30)

    def test_a_hung_leg_is_killed_at_its_limit(self, fake_leg):
        fake_leg("""
            import time
            time.sleep(600)
        """)
        t0 = time.monotonic()
        with pytest.raises(chip_smoke.LegFailed, match="ran past its 1s limit"):
            chip_smoke.run_leg("x", [], dict(os.environ), 1)
        assert time.monotonic() - t0 < 30

    def test_a_leg_that_leaves_a_process_behind_fails_and_is_cleaned_up(
        self, fake_leg, tmp_path
    ):
        fake_leg(f"""
            import subprocess, sys
            p = subprocess.Popen(
                [sys.executable, "-c", "import time; time.sleep(600)"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
            open({str(tmp_path / "straggler.pid")!r}, "w").write(str(p.pid))
            print('{{"leg": "x", "ok": true}}')
        """)
        with pytest.raises(chip_smoke.LegFailed, match="left processes behind"):
            chip_smoke.run_leg("x", [], dict(os.environ), 30)
        pid = int((tmp_path / "straggler.pid").read_text())
        # killed (at most a zombie nobody reaps): the next leg finds the chip free
        assert pid not in chip_smoke._processes()
