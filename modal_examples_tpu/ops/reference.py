"""Pure-XLA reference implementations of the framework's custom kernels.

Three jobs (SURVEY.md §4's "fake backend" tier):
1. numerical ground truth for Pallas kernel tests;
2. CPU fallback so every model runs (slowly) without a TPU;
3. the recompute path for backward passes until dedicated bwd kernels land.

These replace the reference repo's dependence on flash-attn / vLLM CUDA
kernels (install_flash_attn.py:19-33, vllm_inference.py engine internals) —
the semantics live here, the speed lives in the Pallas siblings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kv_quant import kv_gather


def attention(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, S, D]
    v: jax.Array,  # [B, Hkv, S, D]
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    logit_cap: float | None = None,
) -> jax.Array:
    """Dense softmax attention with GQA (Hq a multiple of Hkv)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if sm_scale is None:
        sm_scale = D**-0.5
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, S, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(B, Hq, S, D)


def attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Dense attention also returning per-row logsumexp [B, Hq, S] — the
    differentiable ground truth for flash_attention_with_lse (ring attention's
    backward recomputes through this)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if sm_scale is None:
        sm_scale = D**-0.5
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, S, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B,Hkv,g,S]
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(B, Hq, S, D), lse.reshape(B, Hq, S)


def attention_chunked(
    q: jax.Array,  # [B, Hq, Sq, D] — queries at positions q_offset..q_offset+Sq
    k: jax.Array,  # [B, Hkv, Skv, D] — full (or so-far) K
    v: jax.Array,
    *,
    q_offset: int,
    sm_scale: float | None = None,
) -> jax.Array:
    """Rectangular causal attention: the XLA ground truth for
    ops.flash_attention_chunked (TP prefill now keeps the flash kernel via
    ops.sharded's shard_map dispatch; this reference stays the
    auto-partitionable fallback and the exactness oracle)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if sm_scale is None:
        sm_scale = D**-0.5
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * sm_scale
    rows = q_offset + jnp.arange(Sq)[:, None]
    cols = jnp.arange(Skv)[None, :]
    s = jnp.where(rows >= cols, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(B, Hq, Sq, v.shape[-1])  # values may be narrower than q/k


def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] — one new token per sequence
    k_pages: jax.Array,  # [n_pages, page_size, Hkv, D]
    v_pages: jax.Array,  # [n_pages, page_size, Hkv, D]
    page_tables: jax.Array,  # [B, pages_per_seq] int32 — physical page ids
    context_lens: jax.Array,  # [B] int32 — tokens already in cache (incl. new)
    *,
    sm_scale: float | None = None,
) -> jax.Array:
    """Decode-step attention over a paged KV cache (vLLM-semantics ground
    truth for the Pallas ragged kernel). int8 (QuantizedKV) page caches
    dequantize in the gather."""
    B, Hq, D = q.shape
    _, page_size, Hkv, _ = k_pages.shape
    group = Hq // Hkv
    pages_per_seq = page_tables.shape[1]
    S = pages_per_seq * page_size
    if sm_scale is None:
        sm_scale = D**-0.5

    # gather each sequence's logical KV [B, Hkv, S, D]; int8 caches
    # dequantize at the query's dtype (same as the kernels' VMEM dequant)
    ks = kv_gather(k_pages, page_tables, dtype=q.dtype)
    vs = kv_gather(v_pages, page_tables, dtype=q.dtype)
    ks = ks.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)
    vs = vs.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)

    qg = q.reshape(B, Hkv, group, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, ks, preferred_element_type=jnp.float32)
    s = s * sm_scale
    positions = jnp.arange(S)[None, :]  # [1, S]
    valid = positions < context_lens[:, None]  # [B, S]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(vs.dtype), vs)
    return o.reshape(B, Hq, D)


def paged_verify_attention(
    q: jax.Array,  # [B, T, Hq, D] — a short chain of new tokens per sequence
    k_pages: jax.Array,  # [n_pages, page_size, Hkv, D]
    v_pages: jax.Array,  # [n_pages, page_size, Hkv, D]
    page_tables: jax.Array,  # [B, pages_per_seq] int32
    positions: jax.Array,  # [B, T] int32 — global position of each query
    *,
    sm_scale: float | None = None,
) -> jax.Array:  # [B, T, Hq, D]
    """Teacher-forced attention of a T-token chain against the paged cache
    (the chain's own KV must already be written). Query t attends to cache
    positions <= positions[b, t] — the multi-token generalization of
    ``paged_decode_attention`` above, used by speculative-decoding verification
    (the reference ships spec decode engine-side, vllm_inference.py:196-205).
    int8 (QuantizedKV) page caches dequantize in the gather, so the verify
    pass scores proposals against exactly the KV values decode will read.
    """
    B, T, Hq, D = q.shape
    _, page_size, Hkv, _ = k_pages.shape
    group = Hq // Hkv
    pages_per_seq = page_tables.shape[1]
    S = pages_per_seq * page_size
    if sm_scale is None:
        sm_scale = D**-0.5

    # int8 caches dequantize in the gather at the query's dtype
    ks = kv_gather(k_pages, page_tables, dtype=q.dtype)
    vs = kv_gather(v_pages, page_tables, dtype=q.dtype)
    ks = ks.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)
    vs = vs.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)

    qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, group, T, D)
    s = jnp.einsum(
        "bhgtd,bhkd->bhgtk", qg, ks, preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    cols = jnp.arange(S)[None, None, :]  # [1, 1, S]
    valid = cols <= positions[:, :, None]  # [B, T, S]
    s = jnp.where(valid[:, None, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgtk,bhkd->bhgtd", p.astype(vs.dtype), vs)
    return o.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)
