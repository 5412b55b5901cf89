"""Seeded weights, made on the device in the type they are served in.

The benchmark makes the weights, not the program: the reference may take
nothing the program has made, and both have to see the same numbers. So one
generator feeds both. :func:`make_tree` builds the whole int8 tree in one
jitted call for the serving engine (a ``lax.map`` over layers: the peak is
the tree plus one layer's temporaries, which is what lets 8 Mixtral layers
boot where the program's own init, whole bf16 leaves at a time, cannot);
:func:`layer_weights` gives the reference the same layer again, alone.

A weight is ``q * scale``: ``q`` int8, uniform on -127..127, cut straight
from the device generator's random bits (four to a 32-bit draw: no normal
deviates, no rounding pass), and ``scale`` f32 per output channel, ``fan_in**-0.5`` over the
standard deviation of ``q`` times a seeded factor in 0.75..1.25, so that a
program that dropped the scales could not pass. Activations keep unit size
and the logits come out near N(0, 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_Q_STD = 127.0 / 3.0**0.5  # of a uniform draw on -127..127


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json``."""
    heads = int(config["num_attention_heads"])
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": heads,
        "kv_heads": int(config.get("num_key_value_heads", heads)),
        "head_dim": int(config.get("head_dim", int(config["hidden_size"]) // heads)),
        "ffn": int(config["intermediate_size"]),
        "experts": int(config.get("num_local_experts", 0)),
        "top_k": int(config.get("num_experts_per_tok", 2)),
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "norm_eps": float(config.get("rms_norm_eps", 1e-5)),
    }


def root_key(seed: int):
    """``--seed`` may exceed 32 signed bits: fold the high part in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _int8_weight(key, shape) -> dict:
    """{"q": int8 ``shape``, "scale": f32 ``shape[:-2] + (1, dout)``}. A
    leading axis (the experts of a routed layer) is made one slice at a time,
    so the temporaries stay those of one matrix."""
    if len(shape) > 2:
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: _int8_weight(k, shape[1:]), keys)
    kq, ks = jax.random.split(key)
    din, dout = shape
    # the bulk of the bits from the device's own generator (XLA
    # RngBitGenerator): threefry in software makes ~0.15 G draws a second on
    # a v5e, which was 12 s of every boot and of every reference pass
    kq = jax.random.wrap_key_data(
        jax.random.key_data(jax.random.split(kq)).reshape(4), impl="rbg"
    )
    bits = jax.random.bits(kq, (din, dout // 4), jnp.uint32)
    q = jax.lax.bitcast_convert_type(bits, jnp.int8).reshape(shape)
    q = jnp.maximum(q, jnp.int8(-127))
    jitter = jax.random.uniform(ks, (1, dout), jnp.float32, 0.75, 1.25)
    return {"q": q, "scale": jitter * (din**-0.5 / _Q_STD)}


def layer_weights(key, d: dict) -> dict:
    """One decoder layer. Matmul weights are int8 pairs, ``[in, out]``."""
    D, H, KV, hd, F, E = (
        d["hidden"], d["heads"], d["kv_heads"], d["head_dim"], d["ffn"], d["experts"]
    )
    k = jax.random.split(key, 8)
    out = {
        "attn_norm": jnp.ones((D,), jnp.bfloat16),
        "mlp_norm": jnp.ones((D,), jnp.bfloat16),
        "wq": _int8_weight(k[0], (D, H * hd)),
        "wk": _int8_weight(k[1], (D, KV * hd)),
        "wv": _int8_weight(k[2], (D, KV * hd)),
        "wo": _int8_weight(k[3], (H * hd, D)),
    }
    if E:
        out["router"] = (
            jax.random.normal(k[4], (D, E), jnp.float32) * D**-0.5
        ).astype(jnp.bfloat16)
        out["moe_gate"] = _int8_weight(k[5], (E, D, F))
        out["moe_up"] = _int8_weight(k[6], (E, D, F))
        out["moe_down"] = _int8_weight(k[7], (E, F, D))
    else:
        out["gate"] = _int8_weight(k[5], (D, F))
        out["up"] = _int8_weight(k[6], (D, F))
        out["down"] = _int8_weight(k[7], (F, D))
    return out


def _split(key, d: dict):
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_layers, d["layers"])


def _outer(k_embed, k_head, d: dict) -> dict:
    return {
        "embed": (
            jax.random.normal(k_embed, (d["vocab"], d["hidden"]), jnp.float32) * 0.02
        ).astype(jnp.bfloat16),
        "final_norm": jnp.ones((d["hidden"],), jnp.bfloat16),
        "lm_head": _int8_weight(k_head, (d["hidden"], d["vocab"])),
    }


def layer_key(seed: int, d: dict, index: int):
    return _split(root_key(seed), d)[2][index]


def outer_weights(seed: int, d: dict) -> dict:
    """Embedding, final norm and output head."""
    k_embed, k_head, _ = _split(root_key(seed), d)
    return _outer(k_embed, k_head, d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree, layers stacked on a leading axis, in one jitted call."""

    @jax.jit
    def build(key):
        k_embed, k_head, keys = _split(key, d)
        tree = _outer(k_embed, k_head, d)
        tree["layers"] = jax.lax.map(lambda k: layer_weights(k, d), keys)
        return tree

    return build(root_key(seed))
