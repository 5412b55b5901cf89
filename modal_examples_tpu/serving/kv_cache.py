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
import numpy as np

from ..observability import metrics as _obs
from ..ops.kv_quant import is_quantized, kv_dtype_name, kv_empty
from ..ops.paged_attention import window_ring_pages
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


class WindowGroup:
    """The host side of a second group of paged K/V: the layers whose
    attention sees a sliding window (docs/kv_cache.md, "Two page groups").
    Its device leaves are ``PagedKVCache.window_pages``; here are its own
    allocator (a page budget apart from the whole-context group's), the pages
    a sequence holds at most (``ring``: the window's pages and one of slack)
    and its own page table, one row a slot.

    A sequence claims ``min(pages its longest context needs, ring)`` pages
    when it is admitted and uses them as a ring: position ``p`` lives in page
    ``tables[slot, (p // page_size) % ring]``, so the page the window has
    left is written over by the page that enters it. Nothing is allocated or
    freed while a sequence runs; all its pages go back at release."""

    def __init__(self, *, window: int, page_size: int, n_pages: int, max_slots: int):
        self.window = window
        self.page_size = page_size
        self.ring = window_ring_pages(window, page_size)
        self.allocator = PageAllocator(n_pages, track=False)
        self.tables = np.zeros((max_slots, self.ring), np.int32)
        self._held: dict[int, list[int]] = {}
        self.peak_used = 0

    def pages_for(self, n_tokens: int) -> int:
        """Pages a sequence whose context reaches ``n_tokens`` claims."""
        return min(-(-n_tokens // self.page_size), self.ring)

    def claim(self, n_tokens: int) -> list[int]:
        """Pages for one sequence; ``OutOfPages`` when the budget is short."""
        pages = self.allocator.alloc(self.pages_for(n_tokens))
        self.peak_used = max(self.peak_used, self.allocator.used)
        self._emit()
        return pages

    def free(self, pages: list[int]) -> None:
        """Give back a claim no slot was given (a failed admission)."""
        self.allocator.free(pages)
        self._emit()

    def install(self, slot: int, pages: list[int]) -> None:
        """Slot ``slot`` runs a sequence that holds ``pages``."""
        self.release(slot)
        self._held[slot] = pages
        self.tables[slot] = 0
        self.tables[slot, : len(pages)] = pages

    def release(self, slot: int) -> None:
        """The slot's sequence is over: its pages go back, its row reads the
        trash page."""
        pages = self._held.pop(slot, None)
        if pages:
            self.allocator.free(pages)
            self.tables[slot] = 0
            self._emit()

    def held(self, slot: int) -> int:
        return len(self._held.get(slot, ()))

    def recycled(self, first, end) -> int:
        """Pages written over when positions ``first .. end - 1`` of a
        sequence are written (numpy arrays or ints): the pages begun there
        beyond the ring's first turn."""
        ps = self.page_size
        begun_from = np.maximum(-(-np.asarray(first) // ps), self.ring)
        return int(np.maximum(-(-np.asarray(end) // ps) - begun_from, 0).sum())

    def occupancy(self) -> dict:
        usable = self.allocator.n_pages - 1
        used = self.allocator.used
        return {
            "pages_used": used, "pages_free": usable - used, "pages_total": usable,
            "pages_peak": self.peak_used, "ring": self.ring,
        }

    def _emit(self) -> None:
        _obs.set_kv_window_pages(
            used=self.allocator.used, peak=self.peak_used,
            total_usable=self.allocator.n_pages - 1,
        )


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
    # the K and V of the layers whose attention sees a sliding window,
    # ``[window layers, n_window_pages, page_size, *leaf]`` each: a second
    # page group with a page table and a budget of its own (``window``, a
    # ``WindowGroup``), because such a layer keeps a window's worth of a
    # context and the first two leaves keep all of it. A model that declares
    # no window group (``cfg.window_group``) has neither
    window_pages: tuple = ()
    window: WindowGroup | None = None

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
        # a configuration's ``window_group``: ``(layers, window)``, the layers
        # kept in the second page group and the positions their attention
        # sees; ``n_window_pages`` is that group's budget (page 0 its trash)
        window_group: tuple | None = None,
        n_window_pages: int | None = None,
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
        window, window_pages = None, ()
        if window_group is not None:
            w_layers, w_positions = window_group
            ring = window_ring_pages(w_positions, page_size)
            if n_window_pages is None:
                n_window_pages = 1 + max_slots * ring
            window = WindowGroup(
                window=w_positions, page_size=page_size, n_pages=n_window_pages,
                max_slots=max_slots,
            )
            window_pages = tuple(
                kv_empty((w_layers, n_window_pages, page_size, *leaf), kv_dtype)
                for leaf in leaf_shapes[:2]
            )
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
            window_pages=window_pages,
            window=window,
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
            + sum(leaf.nbytes for leaf in self.more_pages + self.window_pages)
        )

    @property
    def beside(self) -> tuple:
        """The leaves the programs carry beside ``k_pages`` and ``v_pages``,
        as one tuple (the ``state=`` they take and hand back: docs/mla.md):
        the further paged leaves first, then the window group's, then the
        per-slot ones."""
        return self.more_pages + self.window_pages + self.state

    @beside.setter
    def beside(self, leaves: tuple) -> None:
        n, w = len(self.more_pages), len(self.window_pages)
        self.more_pages, self.window_pages, self.state = (
            tuple(leaves[:n]), tuple(leaves[n:n + w]), tuple(leaves[n + w:])
        )

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
        whole = self.bytes() - sum(leaf.nbytes for leaf in self.window_pages)
        bytes_per_page = whole // self.n_pages
        return {
            # the pages of the group that keeps whole contexts; the window
            # group's count is beside it (absent: a model with no such group)
            **({} if self.window is None else {"window": self.window.occupancy()}),
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
    data_fields=("k_pages", "v_pages", "state", "more_pages", "window_pages"),
    meta_fields=("page_size", "allocator", "window"),
)
