"""Seeded weights, made on the device in the type they are served in: the
primitives a family's file builds its tree from.

The benchmark makes the weights, not the program: the reference may take
nothing the program has made, and both have to see the same numbers. So one
generator feeds both: a family's ``make_tree`` builds the whole int8 tree in
one jitted call for the serving engine, and gives its reference the same
layer again, alone (``families/<family>.py``).

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
