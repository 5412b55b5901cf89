"""Paged KV cache: device pages + host-side block allocator.

The TPU analog of vLLM's PagedAttention block manager (the engine inside the
reference's vllm_inference.py). Device side: two arrays
``[n_layers, n_pages, page_size, n_kv_heads, head_dim]`` living in HBM — a
page holds all kv heads contiguously so the decode kernel moves one fat DMA
per page — with page 0 reserved as the trash page (padded/dead slots write
there). Host side: a
free-list allocator — intentionally simple; each sequence claims
``ceil(max_tokens/page_size)`` pages at admission so decode can never fail
mid-flight (no preemption/swap in v1, documented trade-off vs vLLM's
best-effort allocation + preemption).

``create(kv_dtype="int8")`` stores the pages quantized
(:class:`~..ops.kv_quant.QuantizedKV`: int8 data + per-token-head f32
scales ``[L, P, page_size, Hkv]``), making the cache a **4-leaf jax
pytree** — k data/scale + v data/scale — that flows through jit/donation/
sharding like the plain 2-leaf bf16 cache. Halves KV HBM traffic AND
residency (~2x the slots/context in the same HBM); see docs/kv_cache.md
for the layout and the tolerance-based accuracy contract.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp

from ..observability import metrics as _obs
from ..ops.kv_quant import is_quantized, kv_dtype_name, kv_empty
from ..utils.log import get_logger

_log = get_logger("kv_cache")


class OutOfPages(RuntimeError):
    pass


class PageAllocator:
    """Thread-safe free-list over physical page ids (page 0 is reserved).

    Occupancy telemetry: every alloc/free refreshes the
    ``mtpu_kv_pages_used`` / ``mtpu_kv_pages_free`` / ``mtpu_kv_page_occupancy``
    gauges — per-request frequency (admission/release), never per-token, so
    the decode hot loop pays nothing. Multiple allocators in one process
    share the gauges last-writer-wins (one serving engine per process is the
    deployed shape); ``track=False`` opts an auxiliary allocator out.
    """

    def __init__(self, n_pages: int, *, track: bool = True):
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, 0, -1))  # pop() yields low ids first
        self._lock = threading.Lock()
        self._track = track

    def _emit_gauges_locked(self) -> None:
        if not self._track:
            return
        usable = self.n_pages - 1  # page 0 is the reserved trash page
        free = len(self._free)
        _obs.set_kv_occupancy(
            used=usable - free, free=free, total_usable=usable
        )

    def alloc(self, n: int) -> list[int]:
        with self._lock:
            if n > len(self._free):
                raise OutOfPages(f"need {n} pages, {len(self._free)} free")
            out = [self._free.pop() for _ in range(n)]
            self._emit_gauges_locked()
            return out

    def free(self, pages: list[int]) -> None:
        with self._lock:
            for p in pages:
                if p != 0:
                    self._free.append(p)
            self._emit_gauges_locked()

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used(self) -> int:
        with self._lock:
            return (self.n_pages - 1) - len(self._free)

    @property
    def occupancy(self) -> float:
        """Allocated fraction of the usable pool (0..1)."""
        usable = self.n_pages - 1
        return self.used / usable if usable > 0 else 0.0


@dataclasses.dataclass
class PagedKVCache:
    # plain [L, P, page_size, Hkv, hd] arrays, or QuantizedKV (int8 data +
    # [L, P, page_size, Hkv] f32 scales) — two device leaves each way, so
    # the whole cache is a 2- (bf16) or 4-leaf (int8) pytree. The two need
    # not be alike: a model declares each leaf's per-token shape
    # (``create(leaf_shapes=)``), the page axis stays at 1 in both
    k_pages: object
    v_pages: object
    page_size: int
    allocator: PageAllocator
    # per-slot leaves ``[layers, max_slots, *shape]``: state a model keeps per
    # *sequence*, not per token (a recurrent layer's: docs/recurrent_state.md).
    # No page axis: a row is addressed by the slot's index, written by the
    # prefill that fills the slot and by every decode step, and means nothing
    # once the slot is free. A model that declares none has none
    state: tuple = ()
    # paged leaves beyond the first two, ``[layers_i, n_pages, page_size,
    # *leaf]`` each with a layer count of its own (GLM-5.2 keeps its indexer's
    # keys for the layers that have an indexer: docs/sparse_attention.md).
    # They share the page table and the allocator: a page id names one page
    # of every paged leaf, so what the prefix cache shares or frees, it
    # shares or frees in all of them. A model that declares two has none
    more_pages: tuple = ()

    @classmethod
    def create(
        cls,
        *,
        n_layers: int,
        n_kv_heads: int | None = None,
        head_dim: int | None = None,
        n_pages: int,
        page_size: int = 16,
        # per-token shape of the paged leaves, for a model whose cache is not
        # a symmetric pair of per-head K and V (a configuration's
        # ``cache_leaf_shapes``: DeepSeek-V2 keeps ``(1, 512)`` latents and
        # ``(1, 64)`` rotated keys; a third entry and on makes ``more_pages``).
        # Default: ``(n_kv_heads, head_dim)`` twice
        leaf_shapes: tuple | None = None,
        # layers of each paged leaf (a configuration's ``cache_leaf_layers``);
        # default: ``n_layers`` for every one
        leaf_layers: tuple | None = None,
        kv_dtype=None,  # "int8" | jnp dtype; the canonical spelling
        dtype=None,  # legacy alias for kv_dtype (kept for callers)
        prefer_native: bool = True,
        # a configuration's ``state_leaves``: ``(layers, per-slot shape,
        # dtype)`` for each per-slot leaf, made for ``max_slots`` slots
        state_leaves: tuple = (),
        max_slots: int = 0,
    ) -> "PagedKVCache":
        if kv_dtype is not None and dtype is not None:
            raise ValueError("pass kv_dtype= or dtype=, not both")
        kv_dtype = kv_dtype if kv_dtype is not None else dtype
        if kv_dtype is None:
            kv_dtype = jnp.bfloat16
        if leaf_shapes is None:
            if n_kv_heads is None or head_dim is None:
                raise ValueError("pass n_kv_heads= and head_dim=, or leaf_shapes=")
            leaf_shapes = ((n_kv_heads, head_dim),) * 2
        if leaf_layers is None:
            leaf_layers = (n_layers,) * len(leaf_shapes)
        if len(leaf_layers) != len(leaf_shapes) or len(leaf_shapes) < 2:
            raise ValueError(
                f"{len(leaf_shapes)} paged leaves with {len(leaf_layers)} layer counts"
            )
        k_shape, v_shape, *more_shapes = (
            (layers, n_pages, page_size, *leaf)
            for layers, leaf in zip(leaf_layers, leaf_shapes)
        )
        allocator = None
        if prefer_native:
            try:  # C++ free list (native/mtpu_host.cpp); same semantics
                from ..native import NativePageAllocator

                allocator = NativePageAllocator(n_pages)
            except Exception as e:
                _log.warning("page allocator: python fallback (%s)", e)
        return cls(
            k_pages=kv_empty(k_shape, kv_dtype),
            v_pages=kv_empty(v_shape, kv_dtype),
            page_size=page_size,
            allocator=allocator or PageAllocator(n_pages),
            state=tuple(
                jnp.zeros((layers, max_slots, *shape), dtype)
                for layers, shape, dtype in state_leaves
            ),
            more_pages=tuple(kv_empty(shape, kv_dtype) for shape in more_shapes),
        )

    @property
    def n_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def allocator_impl(self) -> str:
        """Which page allocator this cache loaded: "native" (the C++ free
        list) or "python"."""
        if isinstance(self.allocator, PageAllocator):
            return "python"
        return "native"

    @property
    def kv_dtype(self) -> str:
        """Reporting name of the page dtype: "int8" (quantized) or the
        array dtype name ("bfloat16"/"float32")."""
        return kv_dtype_name(self.k_pages)

    @property
    def quantized(self) -> bool:
        return is_quantized(self.k_pages)

    def bytes(self) -> int:
        """Total device bytes of the page arrays, dtype-aware: int8 caches
        count the int8 payload plus the f32 scale rows (~3% at D=128) —
        about half the bf16 figure, which is exactly the headroom the
        occupancy gauges and bench.py's ``kv_cache`` section report.
        (``nbytes`` is a property on QuantizedKV and jax.Array alike.)"""
        return (
            self.k_pages.nbytes + self.v_pages.nbytes
            + sum(leaf.nbytes for leaf in self.more_pages)
        )

    @property
    def beside(self) -> tuple:
        """The leaves the programs carry beside ``k_pages`` and ``v_pages``,
        as one tuple (the ``state=`` they take and hand back: docs/mla.md):
        the further paged leaves first, then the per-slot ones."""
        return self.more_pages + self.state

    @beside.setter
    def beside(self, leaves: tuple) -> None:
        n = len(self.more_pages)
        self.more_pages, self.state = tuple(leaves[:n]), tuple(leaves[n:])

    def state_bytes(self) -> int:
        """Device bytes of the per-slot leaves (0 for a model with none)."""
        return sum(leaf.nbytes for leaf in self.state)

    def pages_for(self, n_tokens: int) -> int:
        return (n_tokens + self.page_size - 1) // self.page_size

    def occupancy(self) -> dict:
        """Page-pool occupancy snapshot (works for the native allocator too,
        which has no gauge hooks of its own): used/free/total pages, the
        allocated fraction, and the HBM bytes that fraction pins (dtype-
        aware via :meth:`bytes` — int8 caches report ~half the bf16
        footprint for the same page count)."""
        usable = self.n_pages - 1
        free = self.allocator.available
        used = usable - free
        bytes_per_page = self.bytes() // self.n_pages
        return {
            "pages_used": used,
            "pages_free": free,
            "pages_total": usable,
            "occupancy": used / usable if usable > 0 else 0.0,
            "bytes_used": used * bytes_per_page,
            "bytes_total": self.bytes(),
        }


# a jax pytree (device leaves: k/v pages — 2 for bf16, 4 for int8 with the
# scale arrays riding alongside) so tree utilities (jax.tree.leaves,
# jax.block_until_ready, snapshot codecs) see the device state. The leaf set is
# also the WIRE CONTRACT of disaggregated serving: the KV-page transport
# (serving/disagg/transport.wire_leaves) enumerates these leaves by tree
# flattening and ships every one per migrated page, with every leaf's page
# axis at axis 1 — keep that invariant when adding leaves (a static guard
# asserts codec leaves == pytree leaves; docs/disagg.md). CAUTION: the
# allocator rides in meta_fields and compares by IDENTITY (mutable host
# state, no __eq__) — do NOT pass a whole cache as a jit argument; every
# distinct allocator would be a distinct static key (silent retraces).
# Jitted programs take cache.k_pages / cache.v_pages, as the engine does.
# The per-slot leaves (``state``) have no page axis and are not on the wire:
# a model that declares them refuses disaggregated transfer.
jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=("k_pages", "v_pages", "state", "more_pages"),
    meta_fields=("page_size", "allocator"),
)
