"""int8 quantized paged-KV storage: the dtype half of the paged cache.

Why: at the headline shape (llama2-7b, ctx 128, 32 slots) decode-step KV
reads are ~4.3 GB — already comparable to the int8 *weight* floor — and at
ctx 1024 they grow to ~34 GB/step and dominate the step entirely (NOTES.md
round-5 design note). int8 KV halves that bandwidth AND halves cache
residency, so the same HBM holds ~2x the slots/context. This mirrors what
TPU-native serving kernels assume (Ragged Paged Attention) and what vLLM
ships as fp8 KV — the accuracy contract is tolerance-based (quantization
legitimately changes logits), never token-exact.

Scheme (NOTES.md round 5, "int8 KV cache — design note"):
- pages keep the ``[L, P, page_size, Hkv, D]`` layout but store int8, with a
  per-token-head f32 scale array ``[L, P, page_size, Hkv]`` riding alongside
  (~3% overhead at D=128) — together a 2-leaf :class:`QuantizedKV` pytree,
  which makes the full :class:`~..serving.kv_cache.PagedKVCache` a 4-leaf
  pytree (k data+scale, v data+scale);
- **quantize at write**: per token-head symmetric ``amax/127`` over D, fused
  into the producing program (prefill page scatter, the post-scan decode
  scatter, the verify-chain writes);
- **dequantize at read**: one bf16 multiply fused into the XLA page gather,
  or into the ragged kernels' VMEM loads (they DMA the int8 page plus its
  scale row — int8 packs legal (32, 128) Mosaic tiles).

Every helper below is a no-op pass-through for plain (bf16/f32) page
arrays, so the default ``kv_dtype`` path stays bit-identical: no
QuantizedKV object is ever constructed unless the cache was created int8.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .scopes import KV_SCATTER, PAGE_GATHER

#: scale granularity: one f32 per (token, kv-head) over the D axis
_QMAX = 127.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedKV:
    """int8 KV pages + per-token-head f32 scales, as one pytree node.

    ``data`` is ``[..., D]`` int8; ``scale`` is ``data.shape[:-1]`` f32 with
    ``dequant = data * scale[..., None]``. Shape/dtype properties delegate
    to ``data`` so shape-probing call sites (``k_pages.shape[2]`` etc.)
    work unchanged; consumers that touch VALUES must branch (the static
    guard in tests/test_static.py enforces that every cache consumer does).
    """

    data: jax.Array  # int8 [..., D]
    scale: jax.Array  # f32  [...] == data.shape[:-1]

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self) -> int:
        return self.data.ndim

    def __getitem__(self, idx) -> "QuantizedKV":
        """Index data and scale together — valid for indices into the
        leading (non-D) axes only (a layer view ``pages[li]``, a page
        gather ``pages[tables]``); indexing the trailing D axis would
        desynchronize the pair and is the caller's bug."""
        return QuantizedKV(data=self.data[idx], scale=self.scale[idx])

    @property
    def nbytes(self) -> int:
        """Total device bytes (int8 payload + f32 scales). A property to
        match ``jax.Array.nbytes``, so byte accounting needs no
        is_quantized branch."""
        return (
            self.data.size * self.data.dtype.itemsize
            + self.scale.size * self.scale.dtype.itemsize
        )


def is_quantized(pages) -> bool:
    return isinstance(pages, QuantizedKV)


def resolve_kv_dtype(kv_dtype):
    """Normalize an engine/env kv_dtype spec: returns the string ``"int8"``
    for the quantized cache, else a jnp dtype. Accepts jnp dtypes, numpy
    dtypes, and the ``MTPU_KV_DTYPE`` spellings."""
    if isinstance(kv_dtype, str):
        name = kv_dtype.lower()
        if name in ("int8", "i8"):
            return "int8"
        aliases = {"bf16": "bfloat16", "f32": "float32", "fp32": "float32"}
        return jnp.dtype(aliases.get(name, name))
    if kv_dtype == jnp.int8:
        return "int8"
    return jnp.dtype(kv_dtype)


def kv_dtype_name(pages) -> str:
    """Reporting name for a cache leaf: "int8" or the array dtype name."""
    if is_quantized(pages):
        return "int8"
    return str(jnp.dtype(pages.dtype))


def quantize_kv(x: jax.Array) -> QuantizedKV:
    """Per-token-head symmetric int8 over the last (D) axis.

    ``scale = amax/127`` (1.0 where the row is all zero, so dequant of a
    zero row is exactly zero), ``data = round(x / scale)``. Deterministic:
    the prefix cache relies on same-tokens + same-weights => same quantized
    page bytes when concurrent prefills rewrite a shared page."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0)
    q = jnp.round(xf / scale[..., None])
    q = jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)
    return QuantizedKV(data=q, scale=scale)


def dequantize_kv(pages, dtype=jnp.bfloat16):
    """One multiply at ``dtype`` (bf16 on the serving path); pass-through
    for plain arrays."""
    if not is_quantized(pages):
        return pages
    return pages.data.astype(dtype) * pages.scale[..., None].astype(dtype)


def kv_empty(shape: tuple, kv_dtype) -> jax.Array | QuantizedKV:
    """A zeroed cache-page array of ``shape`` = [..., D] at ``kv_dtype``
    ("int8" => QuantizedKV with unit scales; dequant of the empty cache is
    exactly zero either way)."""
    kv_dtype = resolve_kv_dtype(kv_dtype)
    if kv_dtype == "int8":
        return QuantizedKV(
            data=jnp.zeros(shape, jnp.int8),
            scale=jnp.ones(shape[:-1], jnp.float32),
        )
    return jnp.zeros(shape, kv_dtype)


@jax.named_scope(PAGE_GATHER)
def kv_gather(pages, tables, layer=None, *, dtype=jnp.bfloat16):
    """``pages[(layer,) tables]`` with the dequant multiply fused into the
    gather (XLA fuses gather -> convert -> multiply into one bandwidth-bound
    loop, so the HBM reads stay int8). Plain arrays gather untouched —
    bit-identical to direct indexing."""
    if is_quantized(pages):
        if layer is None:
            d, s = pages.data[tables], pages.scale[tables]
        else:
            d, s = pages.data[layer, tables], pages.scale[layer, tables]
        return d.astype(dtype) * s[..., None].astype(dtype)
    return pages[tables] if layer is None else pages[layer, tables]


def shard_kv(pages, data_sharding, scale_sharding):
    """Place cache pages on a mesh: plain arrays take ``data_sharding``;
    QuantizedKV shards its f32 scale array WITH the int8 data on the same
    kv-head axis (``scale_sharding`` = the data spec minus the D axis), so
    dequant never crosses chips (engine._shard_cache's placement)."""
    if is_quantized(pages):
        return QuantizedKV(
            data=jax.device_put(pages.data, data_sharding),
            scale=jax.device_put(pages.scale, scale_sharding),
        )
    return jax.device_put(pages, data_sharding)


@jax.named_scope(KV_SCATTER)
def kv_scatter(pages, update, page_idx, slot, *, leading_layer: bool = True):
    """``pages.at[(:,) page_idx, slot].set(update)`` with quantize-at-write
    fused in for int8 caches (per token-head amax/127 computed on the
    full-precision update, then one int8 scatter + one f32 scale scatter).
    Plain arrays take the identical ``.at[].set`` as before."""
    if is_quantized(pages):
        q = quantize_kv(update)
        if leading_layer:
            return QuantizedKV(
                data=pages.data.at[:, page_idx, slot].set(q.data),
                scale=pages.scale.at[:, page_idx, slot].set(q.scale),
            )
        return QuantizedKV(
            data=pages.data.at[page_idx, slot].set(q.data),
            scale=pages.scale.at[page_idx, slot].set(q.scale),
        )
    # cast to the page dtype explicitly (no-op when they already match):
    # jax deprecates implicit down-cast in scatter, and a f32-model +
    # bf16-cache engine would otherwise warn (then error) on every write
    if leading_layer:
        return pages.at[:, page_idx, slot].set(update.astype(pages.dtype))
    return pages.at[page_idx, slot].set(update.astype(pages.dtype))
