"""Shared transformer building blocks (pure-functional JAX).

Replaces the torch module zoo the reference leans on (HF transformers /
vLLM / unsloth internals) with TPU-first primitives: parameters are plain
pytrees (nested dicts of jax arrays) so sharding is a PartitionSpec tree and
checkpointing is orbax-native; compute is bf16 on the MXU with f32 for norms
and softmax; attention goes through ops.flash_attention (training/prefill)
or ops.paged_decode_attention_chunked / _ragged (serving decode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import flash_attention
from ..ops.scopes import DENSE_MLP, KV_SCATTER


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in f32, cast back to input dtype (llama-family norm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (normed * weight + bias).astype(x.dtype)


def rotary_embedding(
    positions: jax.Array,  # [..., S] int32
    head_dim: int,
    theta: float = 10000.0,
    dtype=jnp.float32,
    rope_scaling: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for RoPE at the given positions: [..., S, head_dim/2].

    ``rope_scaling`` supports the llama3.1 scheme (HF config keys:
    factor, low_freq_factor, high_freq_factor, original_max_position_
    embeddings): low-frequency components are stretched by ``factor``,
    high-frequency kept, mid-band smoothly interpolated — the context
    extension used by llama-3.1/3.2 checkpoints.
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if rope_scaling:
        factor = float(rope_scaling.get("factor", 8.0))
        low = float(rope_scaling.get("low_freq_factor", 1.0))
        high = float(rope_scaling.get("high_freq_factor", 4.0))
        orig = float(
            rope_scaling.get("original_max_position_embeddings", 8192)
        )
        wavelen = 2.0 * jnp.pi / freqs
        low_wavelen = orig / low
        high_wavelen = orig / high
        # smooth factor in [0,1]: 1 at high-freq end, 0 at low-freq end
        smooth = jnp.clip(
            (orig / wavelen - low) / jnp.maximum(high - low, 1e-6), 0.0, 1.0
        )
        scaled = jnp.where(
            wavelen > low_wavelen,
            freqs / factor,  # low frequency: stretch fully
            jnp.where(
                wavelen < high_wavelen,
                freqs,  # high frequency: keep
                (1 - smooth) * freqs / factor + smooth * freqs,
            ),
        )
        freqs = scaled
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (split-half convention, matching llama weights).

    x: [B, H, S, D]; cos/sin: [B, S, D/2] or [S, D/2].
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # [S, half] -> broadcast over B, H
        cos_b = cos[None, None]
        sin_b = sin[None, None]
    else:  # [B, S, half] -> broadcast over H
        cos_b = cos[:, None]
        sin_b = sin[:, None]
    o1 = x1 * cos_b - x2 * sin_b
    o2 = x2 * cos_b + x1 * sin_b
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


def mm(x: jax.Array, w) -> jax.Array:
    """x @ w with f32 accumulation; ``w`` may be an int8 QuantizedWeight
    (weights upcast tile-wise into the MXU, then per-channel rescale)."""
    from .quantize import QuantizedWeight

    if isinstance(w, QuantizedWeight):
        y = jnp.dot(x, w.q.astype(x.dtype), preferred_element_type=jnp.float32)
        return y * w.scale
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _proj_f32(x, w, name, lora, lora_scale):
    """x @ w in f32 accumulation, plus the LoRA low-rank delta when an
    adapter targets ``name``. Returns f32 (caller decides when to round)."""
    out = mm(x, w)
    if lora is not None and f"{name}_a" in lora:
        from .lora import delta

        out = out + delta(x, lora[f"{name}_a"], lora[f"{name}_b"], lora_scale)
    return out


def _proj(x, w, name, lora, lora_scale):
    return _proj_f32(x, w, name, lora, lora_scale).astype(x.dtype)


@jax.named_scope(DENSE_MLP)
def swiglu_mlp(
    params: dict, x: jax.Array, lora: dict | None = None, lora_scale: float = 1.0
) -> jax.Array:
    """SwiGLU feed-forward: silu(x W_gate) * (x W_up) W_down.

    gate/up stay f32 through the silu product (one rounding at the end),
    matching f32-accumulated MXU semantics.
    """
    gate = _proj_f32(x, params["gate"], "gate", lora, lora_scale)
    up = _proj_f32(x, params["up"], "up", lora, lora_scale)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return _proj(h, params["down"], "down", lora, lora_scale)


def quick_gelu(x: jax.Array) -> jax.Array:
    """CLIP's activation: x * sigmoid(1.702 x) (published CLIP towers and
    text encoders use this, not tanh/erf GELU)."""
    return x * jax.nn.sigmoid(1.702 * x)


def gelu_mlp(params: dict, x: jax.Array, *, exact: bool = False) -> jax.Array:
    """GELU feed-forward with biases. ``exact`` selects erf-GELU (BERT/
    Whisper convention) vs the default tanh approximation (GPT-2's
    gelu_new) — the flavors differ by ~1e-3 and published checkpoints mix
    them, so the model picks."""
    h = jnp.dot(x, params["fc_w"], preferred_element_type=jnp.float32) + params[
        "fc_b"
    ].astype(jnp.float32)
    h = jax.nn.gelu(h, approximate=not exact).astype(x.dtype)
    return (
        jnp.dot(h, params["proj_w"], preferred_element_type=jnp.float32)
        + params["proj_b"].astype(jnp.float32)
    ).astype(x.dtype)


def attention_op(q, k, v, causal: bool, impl: str = "flash") -> jax.Array:
    """Dispatch between the Pallas flash kernel and XLA attention.

    ``flash``: the Pallas kernel — use on a single chip or inside shard_map
    (where operands are shard-local). ``xla``: plain einsum attention that
    XLA auto-partitions — use under multi-device jit with sharded params,
    where a pallas_call can't be partitioned by the compiler.
    """
    if impl == "flash":
        return flash_attention(q, k, v, causal)
    from ..ops import reference

    return reference.attention(q, k, v, causal=causal)


def causal_self_attention(
    params: dict,
    x: jax.Array,  # [B, S, E]
    *,
    n_heads: int,
    n_kv_heads: int,
    cos: jax.Array | None = None,
    sin: jax.Array | None = None,
    causal: bool = True,
    attn_impl: str = "flash",
    lora: dict | None = None,
    lora_scale: float = 1.0,
) -> jax.Array:
    """Projection + (optional RoPE) + fused attention + output projection."""
    B, S, E = x.shape
    D = E // n_heads
    q = _proj(x, params["wq"], "wq", lora, lora_scale)
    k = _proj(x, params["wk"], "wk", lora, lora_scale)
    v = _proj(x, params["wv"], "wv", lora, lora_scale)
    q = q.reshape(B, S, n_heads, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, n_kv_heads, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, n_kv_heads, D).transpose(0, 2, 1, 3)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = attention_op(q, k, v, causal, attn_impl)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
    return _proj(o, params["wo"], "wo", lora, lora_scale)


def init_dense(key, shape, scale: float | None = None, dtype=jnp.bfloat16):
    # fan-in is the contraction dim: shape[-2] for (possibly layer-stacked)
    # [..., in, out] weights, not shape[0] (which is n_layers when stacked)
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    if scale is None:
        scale = fan_in**-0.5
    # sample directly in the target dtype: a 7B bf16 init must never
    # materialize an f32 copy (2x HBM) on a 16GB chip
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


# -- shared by the model files behind the engine's seam (docs/mla.md) -------------


def refuse(cfg, feature: str) -> None:
    """Raise, by name, if ``cfg``'s programs do not implement ``feature``
    (one of its ``unsupported``). A config with no such list refuses nothing."""
    if feature in getattr(cfg, "unsupported", ()):
        raise NotImplementedError(
            f"{type(cfg).__name__} does not support {feature} yet "
            f"(models/{cfg.model.__name__.rpartition('.')[2]}.py refuses: "
            f"{', '.join(cfg.unsupported)})"
        )


@jax.named_scope(KV_SCATTER)
def scatter_rows(pages, rows, page_idx, slot):
    """Write ``rows`` [L, ..., h, w] (every layer's new cache rows of the
    tokens at ``page_idx`` / ``slot`` [...]) into ``pages`` [L, P, ps, h, w].
    The layer index is spelt out beside the page and the slot: with the
    layers as a slice (``pages.at[:, page_idx, slot]``) the TPU compiler
    lays the whole cache out layers-minor for the scatter and copies it in
    and out of every call (1.5 GiB each way at the benchmark's size)."""
    layer = jnp.arange(pages.shape[0]).reshape(-1, *(1,) * page_idx.ndim)
    return pages.at[layer, page_idx[None], slot[None]].set(rows.astype(pages.dtype))
