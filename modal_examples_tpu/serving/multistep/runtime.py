"""The macro-step decode program: N fused decode+sample steps per dispatch.

Program shape (docs/multistep.md): the same per-step body the classic
block program scans — ``llama.decode_step`` (attention over the paged KV
cache, scatter of the new KV fused in) followed by ``sample`` — wrapped
in :func:`~...ops.scan_loop.masked_scan` so a step whose every lane is
dead skips the transformer entirely. Each lane (slot) carries a ``live``
bit that drops at its stop token or when its per-slot length budget is
spent; the program returns, besides the token matrix, a ``[N, B]``
validity mask — the harvest-boundary contract: the host accepts exactly
the valid prefix per slot and nothing behind it, so checkpoints and live
KV migration taken between harvests see only committed tokens.

Exactness: sampling inside the scan is (seed, position)-keyed
(``serving.sampling.seeded_row_keys``) — a seeded row's token depends
only on its request seed and absolute decode position, never on how many
steps share a dispatch — and the per-step KV arithmetic is the identical
``decode_step`` body the classic block program runs, so N>1 is
token-identical to N=1 on the same replica (asserted across
{greedy, seeded} x {bf16, int8} in tests/test_multistep.py). Cross-TP
exactness is never asserted anywhere in this repo — psum reordering —
only the documented logit-tolerance contract (docs/tensor_parallel.md).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ...models import llama
from ...ops.scan_loop import masked_scan
from ..sampling import sample

#: the runtime knob: decode steps fused into one dispatch (1 = classic)
DECODE_STEPS_ENV = "MTPU_DECODE_STEPS"


def resolve_decode_steps(arg: int | None = None) -> int:
    """Resolve the macro-step count ONCE, the engine's knob rule
    (MTPU_KV_DTYPE / MTPU_PREFILL_BUDGET): explicit arg beats
    ``MTPU_DECODE_STEPS`` beats 1. The result lands on a plain engine
    attribute read per dispatch, so benches and tests mutate it at
    runtime without recompiling anything already traced."""
    if arg is None:
        raw = os.environ.get(DECODE_STEPS_ENV, "")
        arg = int(raw) if raw else 1
    return max(1, int(arg))


def build_multistep_fn(
    cfg,
    *,
    paged_impl: str | None,
    scatter_impl: str,
    mesh,
    eos_id: int,
    n_steps: int,
):
    """Build the jittable N-step decode program for one engine config.

    Signature matches the classic block program plus a trailing
    ``budgets`` [B] int32 — the per-slot count of tokens the host would
    still accept (min of remaining ``max_tokens`` and remaining context),
    computed at dispatch from the optimistic positions. A lane dies when
    it samples ``eos_id`` or exhausts its budget; the eos / budget-final
    token itself is still valid (the host finishes ON it, mirroring the
    classic accept path's stop/length checks exactly).

    Returns ``(toks [N, B], valid [N, B] bool, last [B], k_pages,
    v_pages)``. ``valid[k, i]`` means lane ``i`` was live entering step
    ``k``; invalid tail tokens are holds and must not be accepted.
    """

    def multistep_fn(
        params, k_pages, v_pages, prev_tokens, override, override_mask,
        positions, page_tables, active, key, temps, top_ps, top_ks, seeds,
        budgets,
    ):
        tok0 = jnp.where(override_mask, override, prev_tokens)
        taken0 = jnp.zeros_like(budgets)

        def step(live, state, k_i):
            tok, pos, taken, kp, vp = state
            logits, kp, vp = llama.decode_step(
                params, tok, pos, kp, vp, page_tables, live, cfg,
                impl=paged_impl, scatter_impl=scatter_impl, mesh=mesh,
            )
            nxt = sample(
                logits, k_i, temps, top_ps, top_ks, seeds=seeds,
                step_ids=pos,
            )
            nxt = jnp.where(live, nxt, tok)  # dead lanes hold steady
            valid = live
            one = live.astype(taken.dtype)
            taken = taken + one
            pos = pos + one  # dead lanes stop advancing (position-keyed)
            live = live & (nxt != eos_id) & (taken < budgets)
            return live, (nxt, pos, taken, kp, vp), (nxt, valid)

        def hold(live, state, k_i):
            # all lanes dead: hold tokens, emit an all-false validity row
            return state[0], live

        live, state, (toks, valid) = masked_scan(
            step,
            hold,
            active,
            (tok0, positions, taken0, k_pages, v_pages),
            jax.random.split(key, n_steps),
        )
        last, _pos, _taken, k_pages, v_pages = state
        return toks, valid, last, k_pages, v_pages

    return multistep_fn
