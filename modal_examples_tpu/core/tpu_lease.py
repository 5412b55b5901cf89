"""One process per chip: the host-wide TPU lease.

libtpu hands a host's chips to the first process that initialises the
backend; a second process then fails or hangs inside libtpu with nothing
that names the cause. A ``tpu=`` container therefore takes this lease before
it imports JAX and holds it until it exits. The lease covers the whole host
(one process may drive all of a host's chips; two may not share them), so a
second ``tpu=`` container is refused at once with a message naming the
holder.

The lease is an ``flock`` on one file under the system temp dir: the kernel
drops it when the holder exits, however it exits.
"""

from __future__ import annotations

import fcntl
import os
import tempfile
from pathlib import Path


class TPULeaseHeld(RuntimeError):
    """Another process on this host holds the TPU."""


def lease_path() -> Path:
    return Path(tempfile.gettempdir()) / "mtpu-tpu.lease"


def acquire(holder: str, path: str | os.PathLike | None = None):
    """Take the lease for ``holder`` or raise :class:`TPULeaseHeld`.

    Returns the open lock file; the caller keeps the reference for as long
    as it owns the chips (closing it, or exiting, releases the lease)."""
    f = open(path or lease_path(), "a+")
    try:
        fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError:
        f.seek(0)
        held_by = f.read().strip() or "another process"
        f.close()
        raise TPULeaseHeld(
            f"{holder} needs the TPU, but this host's chips are held by "
            f"{held_by}; a chip belongs to one process at a time"
        ) from None
    f.seek(0)
    f.truncate()
    f.write(f"pid {os.getpid()} ({holder})")
    f.flush()
    return f


def require_tpu_backend(holder: str) -> None:
    """Initialise JAX and fail unless it found a TPU: a ``tpu=`` container
    never serves from the CPU (where every Pallas kernel would run in the
    interpreter) without saying so."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{holder} asks for tpu={os.environ.get('MTPU_TPU_SPEC')!r} but "
            f"JAX's backend in its container is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); run it on "
            "a TPU host, or drop tpu= to run it as a CPU container"
        )
