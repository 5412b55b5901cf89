"""One process per chip (core/tpu_lease.py + the executor): a second ``tpu=``
container requested while the chip is held is refused at once with a message
naming the holder; a ``tpu=`` container never serves from the CPU silently;
a ``tpu=`` pool holds one container. All of it on the CPU — the rule is
about processes, not devices. And a boot that fails is reported when it
fails, by ``serve()`` and by ``tpurun serve``."""

import subprocess
import sys
import textwrap
import time

import pytest

import modal_examples_tpu as mtpu
from modal_examples_tpu.core import tpu_lease

app = mtpu.App("tpu-lease-test")


@app.function(tpu="v5e-1", max_containers=4, timeout=60)
def on_the_chip() -> str:
    return "ran"


@app.function(timeout=60)
def on_the_cpu() -> str:
    import os

    return os.environ["JAX_PLATFORMS"]


class TestLease:
    def test_second_holder_is_refused_and_told_who_holds_it(self, tmp_path):
        path = tmp_path / "lease"
        held = tpu_lease.acquire("first-function", path)
        with pytest.raises(tpu_lease.TPULeaseHeld) as e:
            tpu_lease.acquire("second-function", path)
        assert "first-function" in str(e.value)
        assert "second-function" in str(e.value)
        assert "one process at a time" in str(e.value)
        held.close()  # released: closing the file (or exiting) is enough
        tpu_lease.acquire("second-function", path).close()


@pytest.fixture()
def lease_dir(tmp_path, monkeypatch):
    """Containers compute the lease path from the temp dir they inherit."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    return tmp_path


class TestTpuContainers:
    def test_refused_at_once_while_the_chip_is_held(self, lease_dir):
        held = tpu_lease.acquire("the-other-server", lease_dir / "mtpu-tpu.lease")
        try:
            with app.run():
                t0 = time.monotonic()
                with pytest.raises(tpu_lease.TPULeaseHeld) as e:
                    on_the_chip.remote()
                assert time.monotonic() - t0 < 30  # a refusal, not a hang
                assert "the-other-server" in str(e.value)
        finally:
            held.close()

    def test_no_silent_cpu_fallback(self, lease_dir):
        """The lease is free, but this sandbox has no TPU: the container
        says so at boot instead of running the function on the CPU."""
        with app.run():
            with pytest.raises(RuntimeError, match="asks for tpu='v5e-1'"):
                on_the_chip.remote()

    def test_tpu_pool_holds_one_container(self):
        with app.run():
            assert on_the_chip._pool().max_containers == 1
            assert on_the_cpu._pool().max_containers == on_the_cpu.spec.max_containers

    def test_cpu_containers_stay_off_the_chip(self):
        with app.run():
            assert on_the_cpu.remote() == "cpu"


class TestServeSurfacesBootFailures:
    """A server that never comes up ends ``tpurun serve`` non-zero, when
    the boot fails — not at the end of ``startup_timeout``."""

    @staticmethod
    def _serve(tmp_path, script: str):
        path = tmp_path / "broken_server.py"
        path.write_text(textwrap.dedent(script))
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, "-m", "modal_examples_tpu", "serve", str(path),
             "--timeout", "1"],
            capture_output=True, text=True, timeout=120,
        )
        return proc, time.monotonic() - t0

    def test_failed_enter_exits_nonzero_at_once(self, tmp_path):
        proc, elapsed = self._serve(tmp_path, """
            import modal_examples_tpu as mtpu

            app = mtpu.App("broken-server")

            @app.server(port=18971, startup_timeout=600)
            class Broken:
                @mtpu.enter()
                def start(self):
                    raise RuntimeError("boot failed on purpose")
        """)
        assert proc.returncode != 0
        assert "boot failed on purpose" in proc.stderr
        assert "serving:" not in proc.stdout
        assert elapsed < 60  # the startup_timeout is 600

    def test_failed_boot_is_reported_once_its_process_is_gone(self, tmp_path):
        """A container that fails at boot may take seconds to leave (one
        that opened the chips hands them back at exit). ``serve()`` raises
        its error only then: a caller that exits on the error leaves no
        process behind, still holding what the next run needs."""
        import os

        pid_file = tmp_path / "pid"
        slow = mtpu.App("slow-to-leave")

        @slow.server(port=18973, startup_timeout=600)
        class SlowToLeave:
            @mtpu.enter()
            def start(self):
                import atexit

                pid_file.write_text(str(os.getpid()))
                atexit.register(time.sleep, 1.5)
                raise RuntimeError("boot failed on purpose")

        with slow.run():
            with pytest.raises(RuntimeError, match="boot failed on purpose"):
                SlowToLeave.serve()
            with pytest.raises(ProcessLookupError):  # gone, and reaped
                os.kill(int(pid_file.read_text()), 0)
            SlowToLeave.stop()

    def test_web_server_that_never_opens_its_port_exits_nonzero(self, tmp_path):
        proc, _ = self._serve(tmp_path, """
            import modal_examples_tpu as mtpu

            app = mtpu.App("silent-server")

            @app.function()
            @mtpu.web_server(18972, startup_timeout=1)
            def never_listens():
                pass
        """)
        assert proc.returncode != 0
        assert "never opened port 18972" in proc.stderr
        assert "serving:" not in proc.stdout
