"""Masked early-exit scan: a ``lax.scan`` that skips steps no lane needs.

:func:`masked_scan` runs a per-step body over a leading axis of inputs
while any lane of a boolean ``live`` mask is still set, and skips the body
entirely — one ``lax.cond`` per step, no transformer math — once every
lane is dead. Its caller is the speculative round's draft-propose loop
(``serving/spec_runtime/runtime.py``, docs/speculative.md): a lane dies
when its γ budget is spent, and the tail steps skip the draft model.

The contract mirrors ``jax.lax.scan`` with a mask threaded through:

- ``step(live, state, x) -> (live', state', out)`` runs when any lane is
  live. It must keep dead lanes inert itself (``jnp.where(live, ...)``) —
  the mask only short-circuits *whole* steps, not single lanes.
- ``hold(live, state, x) -> out`` produces the stacked output for a
  skipped step (typically the held tokens plus an all-false validity
  row). It must return the same pytree structure/dtypes as ``step``'s
  ``out`` — ``lax.cond`` requires matching branch signatures.

Both branches trace at compile time; the runtime cost of a skipped step
is the cond predicate plus a copy-through of the carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def masked_scan(step, hold, live0, state0, xs):
    """Scan ``step`` over ``xs`` carrying ``(live, state)``; skip steps via
    ``lax.cond`` once no lane is live. Returns ``(live, state, outs)`` with
    ``outs`` stacked along the leading axis like ``lax.scan``."""

    def body(carry, x):
        live, state = carry

        def run(operand):
            live_, state_ = operand
            return step(live_, state_, x)

        def skip(operand):
            live_, state_ = operand
            return live_, state_, hold(live_, state_, x)

        live, state, out = jax.lax.cond(
            jnp.any(live), run, skip, (live, state)
        )
        return (live, state), out

    (live, state), outs = jax.lax.scan(body, (live0, state0), xs)
    return live, state, outs
