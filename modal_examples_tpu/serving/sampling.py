"""Token sampling (jittable, static-shaped — runs inside the decode step).

Covers the sampling surface the reference's served engines expose via the
OpenAI API (temperature / top_p / top_k / greedy; vllm_inference.py client
:309-345 and openai_compatible/client.py)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.scopes import SAMPLING


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    max_tokens: int = 128
    stop: tuple[str, ...] = ()
    seed: int | None = None  # per-request determinism (OpenAI `seed`)
    #: relative deadline in seconds from submit (``x-mtpu-deadline-ms`` over
    #: HTTP). Past it, queued requests are cancelled and in-flight decodes
    #: aborted with finish_reason="deadline" (scheduling/admission.py).
    deadline_s: float | None = None


def seeded_row_keys(
    key: jax.Array,
    seeds: jax.Array,  # [B] int32; >=0 selects the seeded derivation
    step_ids: jax.Array,  # [B] int32 per-slot decode position
) -> jax.Array:  # [B, 2] PRNG keys
    """Per-row sampling keys, (seed, position)-derived for seeded rows.

    A row with ``seeds[i] >= 0`` gets ``fold_in(fold_in(PRNGKey(0),
    seed), step_id)`` — a function of the REQUEST's seed and its absolute
    decode position only. This is the exactness anchor a resume on another
    engine relies on (docs/failover.md): engines of different
    ``decode_block``, and a speculative round's classic lane, burn the
    engine key differently, but every real request carries a seed (submit() assigns
    ``auto_seed`` when the caller passes none), so its sampled tokens
    depend on nothing the dispatch shape changes. Unseeded rows fall back
    to splits of the per-dispatch engine ``key`` and make no cross-shape
    promise."""
    B = seeds.shape[0]
    base_keys = jax.random.split(key, B)

    def row_key(i):
        seeded = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seeds[i]), step_ids[i]
        )
        return jnp.where(seeds[i] >= 0, seeded, base_keys[i])

    return jax.vmap(row_key)(jnp.arange(B))


@jax.named_scope(SAMPLING)
def sample(
    logits: jax.Array,  # [B, V] f32
    key: jax.Array,
    temperature: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32 (0 = off)
    seeds: jax.Array | None = None,  # [B] int32; >=0 rows use fold_in(seed,
    #                                  step) instead of the engine key, so a
    #                                  request with seed= samples identically
    #                                  regardless of batch composition
    step_ids: jax.Array | None = None,  # [B] int32 per-slot decode step
) -> jax.Array:  # [B] int32
    """Vectorized per-slot sampling; temperature 0 means greedy."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)

    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t

    def _mask_topk_topp(scaled):
        # top-k: mask everything below the k-th logit
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]  # descending
        k_idx = jnp.clip(jnp.where(top_k > 0, top_k, V) - 1, 0, V - 1)
        kth = jnp.take_along_axis(sorted_logits, k_idx[:, None], axis=-1)
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)

        # top-p (nucleus): keep the smallest prefix of the sorted
        # distribution with cumulative prob >= top_p
        sort_idx = jnp.argsort(scaled, axis=-1)[:, ::-1]
        sorted_scaled = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        probs_sorted = jax.nn.softmax(sorted_scaled, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        keep_sorted = cum - probs_sorted < top_p[:, None]
        keep_sorted = keep_sorted.at[:, 0].set(True)
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(keep_sorted.shape[0])[:, None], sort_idx
        ].set(keep_sorted)
        return jnp.where(keep, scaled, -jnp.inf)

    # both vocab-size sorts are dead weight for the common temperature-only
    # request mix — branch them out at RUNTIME (measured 4.8 ms/step at
    # 32k vocab on v5e; the decode hot loop runs this every step)
    needs_filter = jnp.any((top_p < 1.0) | (top_k > 0))
    scaled = jax.lax.cond(
        needs_filter, _mask_topk_topp, lambda s: s, scaled
    )

    if seeds is not None:
        B = logits.shape[0]
        if step_ids is None:
            step_ids = jnp.zeros((B,), jnp.int32)
        keys = seeded_row_keys(key, seeds, step_ids)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(keys, scaled)
    else:
        sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)
