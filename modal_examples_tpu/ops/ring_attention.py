"""Ring attention: context parallelism over a sequence-sharded mesh axis.

The reference has NO sequence-parallel/long-context machinery — its longest
contexts are engine flags (max_seq_length=32768, unsloth_finetune.py:386) and
vLLM/SGLang internals (SURVEY.md §5.7 calls this out as our value-add). This
module provides it TPU-natively:

- the sequence dimension is sharded over a mesh axis (``seq``);
- each shard computes blockwise attention between its local queries and a
  rotating K/V shard, passed around the ring with ``ppermute`` — on a TPU
  torus each hop is a neighbor ICI transfer, so K/V transit overlaps compute
  and no device ever holds the full sequence;
- partial results merge with the standard online-softmax rule using each
  block's logsumexp (from the flash kernel), so the result is exactly dense
  attention.

Causal masking: shard i attends to shard j's K/V only when j <= i (block
granularity), with the diagonal block using the in-kernel causal mask. The
per-hop `kv_index` bookkeeping makes that exact.

Usage: wrap in shard_map over a mesh with a "seq" axis — see
ring_attention_sharded() and tests/test_ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import flash_attention_with_lse
from ..parallel.collectives import axis_size as _axis_size


def _merge(o1, lse1, o2, lse2):
    """Combine two attention partials over disjoint K/V sets."""
    m = jnp.maximum(lse1, lse2)
    # guard -inf (a block that saw nothing)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - m_safe), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - m_safe), 0.0)
    denom = w1 + w2
    denom_safe = jnp.where(denom > 0, denom, 1.0)
    o = (
        o1.astype(jnp.float32) * (w1 / denom_safe)[..., None]
        + o2.astype(jnp.float32) * (w2 / denom_safe)[..., None]
    )
    lse = jnp.where(denom > 0, m_safe + jnp.log(denom_safe), -jnp.inf)
    return o.astype(o1.dtype), lse


def ring_attention(
    q: jax.Array,  # [B, H, S_local, D] — this shard's queries
    k: jax.Array,  # [B, Hkv, S_local, D] — this shard's keys (hop 0)
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    sm_scale: float | None = None,
) -> jax.Array:
    """Call INSIDE shard_map with the sequence dim sharded over ``axis_name``."""
    n = _axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, S, D = q.shape

    o_acc = jnp.zeros_like(q)
    lse_acc = jnp.full((B, H, S), -jnp.inf, jnp.float32)

    def hop(carry, step):
        o_acc, lse_acc, k_cur, v_cur = carry
        kv_index = (my_idx - step) % n  # whose K/V we hold this hop

        # contribution of this K/V shard to our queries
        if causal:
            # diagonal shard: in-kernel causal mask; earlier shards: full;
            # later shards: masked out entirely. cond executes one branch.
            o_blk, lse_blk = lax.cond(
                kv_index == my_idx,
                lambda: flash_attention_with_lse(
                    q, k_cur, v_cur, causal=True, sm_scale=sm_scale
                ),
                lambda: flash_attention_with_lse(
                    q, k_cur, v_cur, causal=False, sm_scale=sm_scale
                ),
            )
            visible = kv_index <= my_idx
            o_blk = jnp.where(visible, o_blk, 0.0)
            lse_blk = jnp.where(visible, lse_blk, -jnp.inf)
        else:
            o_blk, lse_blk = flash_attention_with_lse(
                q, k_cur, v_cur, causal=False, sm_scale=sm_scale
            )
        o_new, lse_new = _merge(o_acc, lse_acc, o_blk, lse_blk)

        # rotate K/V one hop around the ring (neighbor ICI transfer)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, lse_new, k_nxt, v_nxt), None

    # scan (not fori_loop) so the ring is differentiable end to end:
    # ppermute transposes to the reverse ring in the backward pass
    (o_acc, lse_acc, _, _), _ = lax.scan(
        hop, (o_acc, lse_acc, k, v), jnp.arange(n)
    )
    return o_acc


def ring_attention_sharded(
    q, k, v, mesh, *, seq_axis: str = "seq", causal: bool = True,
    sm_scale: float | None = None,
):
    """Convenience wrapper: shard q/k/v over ``seq_axis`` and run the ring."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, seq_axis, None)
    fn = functools.partial(
        ring_attention, axis_name=seq_axis, causal=causal, sm_scale=sm_scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,  # [B, H, S_local, D] — this shard's sequence slice
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
    sm_scale: float | None = None,
) -> jax.Array:
    """Ulysses (DeepSpeed-style) sequence parallelism: two all_to_alls swap
    the sharded dimension from SEQUENCE to HEADS, so each shard runs plain
    full-sequence attention on H/n heads — exact, and a good fit when
    H >= shards and the interconnect is all-to-all friendly. The reference
    has no equivalent (SURVEY §2.3 row 'Ulysses: absent'); on a TPU torus
    the ring variant is usually preferred, but both are exact — pick by
    profile. Call inside shard_map with the seq dim sharded over
    ``axis_name``."""
    n = _axis_size(axis_name)
    B, H, S_loc, D = q.shape
    if H % n:
        raise ValueError(f"heads {H} must be divisible by seq shards {n}")

    def seq_to_heads(x):
        # [B, H, S_loc, D] -> [B, H/n, S_global, D]: give away head blocks,
        # receive every shard's tokens for our head block. concat_axis indexes
        # the shape AFTER the split dim is removed: [B, H/n, S_loc, D] with
        # the shard dim inserted at 2 -> [B, H/n, n, S_loc, D] (shard-major
        # global sequence).
        x = x.reshape(B, n, H // n, S_loc, D)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=False)
        return x.reshape(B, H // n, n * S_loc, D)

    def heads_to_seq(x):
        # inverse: [B, H/n, S_global, D] -> [B, H, S_loc, D]
        x = x.reshape(B, H // n, n, S_loc, D)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
        # [B, n, H/n, S_loc, D] -> [B, H, S_loc, D]
        return x.reshape(B, H, S_loc, D)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    og, _ = flash_attention_with_lse(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(og)


def ulysses_attention_sharded(
    q, k, v, mesh, *, seq_axis: str = "seq", causal: bool = True,
    sm_scale: float | None = None,
):
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, seq_axis, None)
    fn = functools.partial(
        ulysses_attention, axis_name=seq_axis, causal=causal, sm_scale=sm_scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
