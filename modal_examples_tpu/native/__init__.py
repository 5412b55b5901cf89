"""Native host runtime: ctypes bridge to mtpu_host.cpp.

Builds the shared library on first use (g++ is in the image; no pybind11 —
plain C ABI via ctypes) next to the source, where git ignores it: a fresh
checkout builds its own. Every consumer has a pure-Python fallback and
:func:`load_error` says why it was taken; the KV cache reports which page
allocator it ended up with (``PagedKVCache.allocator_impl``).
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from pathlib import Path

import numpy as np

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "mtpu_host.cpp"
_LIB = _HERE / "libmtpu_host.so"
_lock = threading.Lock()
_lib = None
_tried = False
_load_error: str | None = None


def _build() -> str | None:
    """Compile the library; returns why it could not, or None."""
    try:
        subprocess.run(
            [
                "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                str(_SRC), "-o", str(_LIB),
            ],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return None
    except subprocess.CalledProcessError as e:
        return f"g++ failed: {e.stderr.decode(errors='replace')[-400:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ did not run: {e}"


def load_error() -> str | None:
    """Why :func:`load` returned None (None while it has not, or did not)."""
    return _load_error


def load():
    """The loaded library, or None when native isn't available."""
    global _lib, _tried, _load_error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _LIB.exists() or _LIB.stat().st_mtime < _SRC.stat().st_mtime:
            _load_error = _build()
            if _load_error is not None:
                return None
        try:
            lib = ctypes.CDLL(str(_LIB))
        except OSError as e:
            _load_error = f"cannot load {_LIB.name}: {e}"
            return None
        lib.mtpu_alloc_create.restype = ctypes.c_void_p
        lib.mtpu_alloc_create.argtypes = [ctypes.c_int32]
        lib.mtpu_alloc_destroy.argtypes = [ctypes.c_void_p]
        lib.mtpu_alloc_alloc.restype = ctypes.c_int32
        lib.mtpu_alloc_alloc.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ]
        lib.mtpu_alloc_free.restype = ctypes.c_int32
        lib.mtpu_alloc_free.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        lib.mtpu_alloc_available.restype = ctypes.c_int32
        lib.mtpu_alloc_available.argtypes = [ctypes.c_void_p]
        lib.mtpu_byte_encode_batch.restype = ctypes.c_int32
        lib.mtpu_byte_encode_batch.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.mtpu_levenshtein.restype = ctypes.c_int32
        lib.mtpu_levenshtein.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        _lib = lib
        return _lib


class NativePageAllocator:
    """C++ free-list allocator (drop-in for kv_cache.PageAllocator)."""

    def __init__(self, n_pages: int):
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {load_error()}")
        self._lib = lib
        self.n_pages = n_pages
        self._h = lib.mtpu_alloc_create(n_pages)
        if not self._h:
            raise ValueError(f"bad page count {n_pages}")

    def alloc(self, n: int) -> list[int]:
        out = (ctypes.c_int32 * max(n, 1))()
        rc = self._lib.mtpu_alloc_alloc(self._h, n, out)
        if rc != 0:
            from ..serving.kv_cache import OutOfPages

            raise OutOfPages(f"need {n} pages, {self.available} free")
        return list(out[:n])

    def free(self, pages: list[int]) -> None:
        arr = (ctypes.c_int32 * max(len(pages), 1))(*pages)
        self._lib.mtpu_alloc_free(self._h, arr, len(pages))

    @property
    def available(self) -> int:
        return self._lib.mtpu_alloc_available(self._h)

    def __del__(self):
        try:
            self._lib.mtpu_alloc_destroy(self._h)
        except Exception:
            pass


def byte_encode_batch(
    texts: list[str], max_len: int, bos_id: int = 256, pad_id: int = 258
) -> tuple[np.ndarray, np.ndarray, int]:
    """Batched byte tokenization -> (ids [n, max_len] i32, mask, max_true).

    Native single-call path with a numpy fallback.
    """
    n = len(texts)
    blobs = [t.encode("utf-8", errors="replace") for t in texts]
    lib = load()
    if lib is not None and n:
        data = b"".join(blobs)
        buf = np.frombuffer(data, np.uint8) if data else np.zeros(1, np.uint8)
        lengths = np.asarray([len(b) for b in blobs], np.int64)
        ids = np.empty((n, max_len), np.int32)
        mask = np.empty((n, max_len), np.int32)
        max_true = lib.mtpu_byte_encode_batch(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, max_len, bos_id, pad_id,
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            mask.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return ids, mask, int(max_true)
    # fallback
    ids = np.full((n, max_len), pad_id, np.int32)
    mask = np.zeros((n, max_len), np.int32)
    max_true = 0
    for i, b in enumerate(blobs):
        row = ([bos_id] if bos_id >= 0 else []) + list(b)
        row = row[:max_len]
        ids[i, : len(row)] = row
        mask[i, : len(row)] = 1
        max_true = max(max_true, len(row))
    return ids, mask, max_true


def levenshtein_ids(a: list[int], b: list[int]) -> int:
    lib = load()
    if lib is None:
        from ..utils.metrics import _levenshtein

        return _levenshtein([str(x) for x in a], [str(x) for x in b])
    aa = (ctypes.c_int32 * max(len(a), 1))(*a)
    bb = (ctypes.c_int32 * max(len(b), 1))(*b)
    return lib.mtpu_levenshtein(aa, len(a), bb, len(b))
