"""Speculative decoding REFERENCE ORACLE: single-sequence propose/verify.

This module is NOT the serving path. The engine's production speculation is
the fused, batched, paged-KV round in :mod:`serving.spec_runtime`
(docs/speculative.md) — scheduler-integrated, adaptive-depth, harvested
through the engine's one harvest site. What lives here is the textbook algorithm in
its simplest possible form, kept as the correctness yardstick the fused
runtime is tested against (tests/test_speculative.py; the quarantine is
enforced by tests/test_static.py — nothing in the package may import this
module outside spec-parity tests).

The algorithm (SURVEY.md §2.3; vllm_inference.py:115-116,196-205 enables
the same idea via flags): a small draft llama proposes gamma tokens
autoregressively, the target scores all of them in ONE teacher-forced
forward, and standard speculative sampling accepts a prefix (greedy mode:
accept while draft == target argmax; stochastic mode: accept token x with
prob min(1, p_t(x)/p_d(x)), resampling from the adjusted residual on
rejection) — guaranteeing the output distribution equals the target
model's.

Static-shape jit: fixed token buffer, ``lax.while_loop`` over rounds,
``lax.scan`` for the draft chain. Scoring recomputes over the fixed window
(no KV cache) — fine for an oracle, exactly the cost the fused runtime's
paged ``verify_step`` removes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models import llama


def _logits_at(params, cfg, buf, attn_impl="xla"):
    """[S] token buffer -> [S, V] next-token logits (teacher-forced)."""
    return llama.forward(params, buf[None], cfg, attn_impl=attn_impl)[0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "target_cfg", "draft_cfg", "max_new", "gamma", "greedy", "temperature",
    ),
)
def speculative_generate(
    target_params,
    draft_params,
    target_cfg: llama.LlamaConfig,
    draft_cfg: llama.LlamaConfig,
    prompt: jax.Array,  # [S0] int32
    prompt_len: int | jax.Array,
    key: jax.Array,
    *,
    max_new: int = 32,
    gamma: int = 4,
    greedy: bool = True,
    temperature: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Returns (buffer [S0+max_new], n_generated). Greedy mode reproduces the
    target model's greedy decode exactly; stochastic mode samples from the
    target distribution via accept/reject."""
    S = prompt.shape[0] + max_new
    buf = jnp.zeros((S,), jnp.int32).at[: prompt.shape[0]].set(prompt)
    pos0 = jnp.asarray(prompt_len, jnp.int32)

    def cond(state):
        buf, pos, n_gen, key = state
        return (n_gen < max_new) & (pos < S)

    def body(state):
        buf, pos, n_gen, key = state
        key, k_draft, k_acc, k_res = jax.random.split(key, 4)

        # 1) draft proposes gamma tokens autoregressively
        def draft_step(carry, k):
            buf_d, p = carry
            logits = _logits_at(draft_params, draft_cfg, buf_d)
            lp = logits[jnp.clip(p - 1, 0, S - 1)] / max(temperature, 1e-6)
            tok = jnp.where(
                greedy,
                jnp.argmax(lp).astype(jnp.int32),
                jax.random.categorical(k, lp).astype(jnp.int32),
            )
            buf_d = buf_d.at[jnp.clip(p, 0, S - 1)].set(tok)
            return (buf_d, jnp.minimum(p + 1, S)), (tok, lp)

        (buf_d, _), (draft_toks, draft_logits) = jax.lax.scan(
            draft_step, (buf, pos), jax.random.split(k_draft, gamma)
        )

        # 2) target scores the whole draft chain in one forward
        t_logits_all = _logits_at(target_params, target_cfg, buf_d)
        idx = jnp.clip(pos - 1 + jnp.arange(gamma), 0, S - 1)
        t_logits = t_logits_all[idx] / max(temperature, 1e-6)  # [gamma, V]

        # 3) acceptance
        if greedy:
            t_choice = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
            match = t_choice == draft_toks
            n_acc = jnp.argmin(
                jnp.concatenate([match.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
            )
            # token written at the first mismatch = target's choice there
            fix_tok = t_choice[jnp.clip(n_acc, 0, gamma - 1)]
        else:
            p_t = jax.nn.softmax(t_logits, axis=-1)
            p_d = jax.nn.softmax(draft_logits, axis=-1)
            tok_pt = jnp.take_along_axis(p_t, draft_toks[:, None], 1)[:, 0]
            tok_pd = jnp.take_along_axis(p_d, draft_toks[:, None], 1)[:, 0]
            u = jax.random.uniform(k_acc, (gamma,))
            accept = u < jnp.minimum(1.0, tok_pt / jnp.maximum(tok_pd, 1e-20))
            n_acc = jnp.argmin(
                jnp.concatenate([accept.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
            )
            # resample the rejected position from max(p_t - p_d, 0)
            j = jnp.clip(n_acc, 0, gamma - 1)
            residual = jnp.maximum(p_t[j] - p_d[j], 0.0)
            residual = jnp.where(
                residual.sum() > 0, residual / residual.sum(), p_t[j]
            )
            fix_tok = jax.random.categorical(k_res, jnp.log(residual + 1e-20))
            fix_tok = fix_tok.astype(jnp.int32)

        # 4) commit accepted draft tokens, then the fix token. Scatters use
        # mode="drop": masked-out lanes write to index S (out of bounds) and
        # are dropped — no duplicate in-bounds indices, so no nondeterministic
        # clobbering when the budget truncates the accepted run.
        budget = max_new - n_gen
        n_draft_take = jnp.minimum(n_acc, budget)
        keep = jnp.arange(gamma) < n_draft_take
        write_pos = jnp.where(keep, pos + jnp.arange(gamma), S)
        new_buf = buf.at[write_pos].set(draft_toks, mode="drop")
        do_fix = (n_acc < gamma) & (n_acc < budget)
        fix_pos = jnp.where(do_fix, pos + n_acc, S)
        new_buf = new_buf.at[fix_pos].set(fix_tok, mode="drop")
        advanced = n_draft_take + do_fix.astype(jnp.int32)
        return new_buf, pos + advanced, n_gen + advanced, key

    buf, pos, n_gen, _ = jax.lax.while_loop(cond, body, (buf, pos0, jnp.zeros((), jnp.int32), key))
    return buf, n_gen


def greedy_generate(params, cfg, prompt, prompt_len, max_new: int):
    """Plain greedy reference (what speculative greedy must reproduce)."""
    S = prompt.shape[0] + max_new
    buf = jnp.zeros((S,), jnp.int32).at[: prompt.shape[0]].set(prompt)

    def step(carry, _):
        buf, p = carry
        logits = _logits_at(params, cfg, buf)
        tok = jnp.argmax(logits[jnp.clip(p - 1, 0, S - 1)]).astype(jnp.int32)
        buf = buf.at[jnp.clip(p, 0, S - 1)].set(tok)
        return (buf, jnp.minimum(p + 1, S)), None

    (buf, _), _ = jax.lax.scan(step, (buf, jnp.asarray(prompt_len)), None, length=max_new)
    return buf
