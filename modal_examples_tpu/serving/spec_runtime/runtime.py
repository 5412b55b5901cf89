"""The fused speculative round program: propose(γ) + verify + accept in one
dispatch.

Program shape (docs/speculative.md): one engine round of speculative
decoding is ONE jitted program — in draft mode a γ-step draft-model propose
loop on :func:`~...ops.scan_loop.masked_scan` (lanes die when
their per-slot γ budget is spent or they run out of page-table capacity,
and a step whose every lane is dead skips the draft transformer entirely),
then ONE ragged teacher-forced target forward over all γ+1 chain positions
against the paged KV cache (``llama.verify_step``), then the accept/reject
cut in-graph. Prompt-lookup (ngram) mode skips the draft scan — proposals
arrive host-computed — and runs the same verify + accept tail.

Per-slot γ rides the batch as a traced ``gammas [B]`` argument, so mixed
spec/non-spec slots coexist in one compiled program: a lane with
``gammas[i] == 0`` proposes nothing and takes the CLASSIC sampling path —
its one token is drawn by the very same ``serving.sampling.sample`` call
the block program makes, (seed, position)-keyed, with
top_p/top_k honored — which is what lets the adaptive controller
(:mod:`.controller`) shrink γ to 0 per request without switching programs,
and what makes temperature>0 (always-seeded, see ``auto_seed``) requests
token-identical to the non-speculative engine.

Output is the harvest plane (docs/speculative.md#the-harvest-boundary): ``(toks [N, B],
valid [N, B], last [B], caches...)`` with ``N = γ_max + 1`` —
``valid[k, i]`` marks row ``k`` of lane ``i`` as an accepted token, so the
engine's ONE harvest site (``_process_block``: exactly two blocking reads,
AST-pinned) accepts spec rounds and decode blocks identically and the
off-thread detok worker never knows which program produced its tokens.

KV rollback is implicit and trie-safe: ``verify_step`` writes KV for every
chain position, rejected-suffix entries are simply overwritten as the
accepted position advances and are never attended past the accept point
(the causal mask inside the verify attention), and the prefix trie only
ever indexes host-ACCEPTED tokens — junk KV beyond a request's final
position lives on private (non-trie) pages and dies with the slot.

Exactness contract (docs/speculative.md#exactness): greedy lanes commit
only target-argmax tokens, token-identical to the non-spec engine
(asserted across {bf16, int8} x TP1 in tests/test_speculative.py);
temperature>0 lanes never speculate (γ pinned 0) and keep the
(seed, position)-keyed stream; cross-TP stays the logit-tolerance
contract — never asserted token-exact anywhere in this repo.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ...models import llama
from ...ops.scan_loop import masked_scan
from ..sampling import sample

#: the adaptive-γ knob (engine rule: explicit ctor arg beats env beats off)
SPEC_ADAPTIVE_ENV = "MTPU_SPEC_ADAPTIVE"


def resolve_spec_adaptive(arg: bool | None = None) -> bool:
    """Resolve the adaptive-γ controller switch ONCE at engine build
    (the MTPU_KV_DTYPE knob rule): explicit arg beats
    ``MTPU_SPEC_ADAPTIVE`` beats off. Lands on a runtime-mutable engine
    attribute so benches A/B fixed-vs-adaptive without a rebuild."""
    if arg is None:
        raw = os.environ.get(SPEC_ADAPTIVE_ENV, "")
        arg = raw.strip().lower() in ("1", "true", "yes", "on")
    return bool(arg)


def accept_reject(
    t_logits, proposals, temps, keys2, active, *, gamma,
    proposal_logps=None, prop_valid=None,
):
    """The speculative accept/reject cut (both spec modes route here so the
    math can never drift). ``proposal_logps`` is the draft model's log-probs
    ``[B, γ, V]``; ``None`` means a degenerate (delta) proposal
    distribution — prompt-lookup mode — where acceptance is min(1, p_t(x))
    and the rejection residual is p_t with x zeroed. ``prop_valid``
    ``[B, γ]`` marks which proposal slots are real (per-slot γ budgets,
    capacity-died draft lanes, empty ngram lookups); slots beyond it are
    never accepted and an all-false row degrades to exactly one plain
    target step.

    Greedy lanes (temperature 0) accept while proposal == target argmax —
    reproducing the target's greedy decode token-for-token. Sampling lanes
    use standard speculative sampling (accept x with prob
    min(1, p_t(x)/p_d(x)); resample rejections from the residual
    max(p_t - p_d, 0)), so the OUTPUT DISTRIBUTION equals the target's —
    but the engine never dispatches sampling lanes with γ>0 (they are not
    (seed, position)-reproducible through this path; see
    docs/speculative.md#exactness). Returns ``(out [B, γ+1], n_emit [B])``.
    """
    B = proposals.shape[0]
    t_scaled = t_logits / jnp.maximum(temps, 1e-6)[:, None, None]
    t_logp = jax.nn.log_softmax(t_scaled, axis=-1)
    greedy_choice = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)

    rows = jnp.arange(B)
    valid = (
        jnp.ones((B, gamma), bool) if prop_valid is None else prop_valid
    )
    n_prop = valid.sum(axis=1)
    match = (proposals == greedy_choice[:, :gamma]) & valid
    lp_t = jnp.take_along_axis(
        t_logp[:, :gamma], proposals[..., None], axis=-1
    )[..., 0]
    if proposal_logps is None:
        accept_prob = jnp.exp(lp_t)  # min(1, p_t / 1)
    else:
        lp_d = jnp.take_along_axis(
            proposal_logps, proposals[..., None], axis=-1
        )[..., 0]
        accept_prob = jnp.exp(jnp.minimum(0.0, lp_t - lp_d))
    u = jax.random.uniform(keys2[0], (B, gamma))
    accept = jnp.where(
        (temps <= 0.0)[:, None], match, (u < accept_prob) & valid
    )
    n_acc = jnp.argmin(
        jnp.concatenate(
            [accept.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)],
            axis=1,
        ),
        axis=1,
    )  # first rejection; == γ when all accepted

    # token at the cut: target's fix on rejection, fresh bonus sample when
    # every real proposal was accepted
    j = n_acc
    p_t_row = jnp.exp(t_logp[rows, j])  # [B, V]
    if proposal_logps is None:
        prop_at_j = proposals[rows, jnp.minimum(j, gamma - 1)]
        residual = p_t_row.at[rows, prop_at_j].set(0.0)
    else:
        p_d_row = jnp.exp(proposal_logps[rows, jnp.minimum(j, gamma - 1)])
        residual = jnp.maximum(p_t_row - p_d_row, 0.0)
    rejected = j < n_prop
    has_res = residual.sum(-1, keepdims=True) > 0
    residual = jnp.where(rejected[:, None] & has_res, residual, p_t_row)
    sampled_fix = jax.vmap(jax.random.categorical)(
        jax.random.split(keys2[1], B), jnp.log(residual + 1e-20)
    ).astype(jnp.int32)
    fix = jnp.where(temps <= 0.0, greedy_choice[rows, j], sampled_fix)
    out = jnp.concatenate(
        [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    out = out.at[rows, j].set(fix)
    n_emit = jnp.where(active, n_acc + 1, 0)
    return out, n_emit


def _emit_plane(out, n_emit, active, gammas, classic_tok):
    """Convert an accept/reject result to the harvest plane.

    ``classic_tok`` replaces row 0 for γ=0 lanes — the token the classic
    sampling path (``sample`` with the full temperature/top_p/top_k/seed
    surface, (seed, position)-keyed) drew from the verify logits' first
    position, which IS the classic decode distribution for that position.
    Returns ``(toks [N, B], valid [N, B], last [B])``."""
    B, N = out.shape
    rows = jnp.arange(B)
    out = out.at[:, 0].set(
        jnp.where(active & (gammas == 0), classic_tok, out[:, 0])
    )
    toks = out.T  # [N, B]
    valid = jnp.arange(N)[:, None] < n_emit[None, :]  # [N, B]
    last = out[rows, jnp.maximum(n_emit - 1, 0)]
    return toks, valid, last


def build_spec_round_fn(
    cfg,
    draft_cfg,
    *,
    paged_impl: str | None,
    scatter_impl: str,
    mesh,
    gamma: int,
):
    """Build the jittable draft-mode speculative round for one engine
    config: γ-step draft propose on ``masked_scan`` + one ragged target
    verify + accept, emitting the harvest plane.

    Signature: ``(params, d_params, tk, tv, dk, dv, tokens, positions,
    page_tables, active, gammas, key, temps, top_ps, top_ks, seeds)`` →
    ``(toks [γ+1, B], valid [γ+1, B], last [B], tk, tv, dk, dv)``.
    ``gammas [B]`` is the per-slot proposal budget (≤ the compiled γ);
    lanes at 0 take the classic sampling path inside the same program.
    """

    def spec_round_fn(
        params, d_params, tk, tv, dk, dv, tokens, positions, page_tables,
        active, gammas, key, temps, top_ps, top_ks, seeds,
    ):
        B = tokens.shape[0]
        page_size = tk.shape[2]
        cap = page_tables.shape[1] * page_size
        keys = jax.random.split(key, gamma + 3)
        spec_lane = active & (gammas > 0)

        def step(live, state, k_i):
            tok, pos, taken, dkp, dvp = state
            logits, dkp, dvp = llama.decode_step(
                d_params, tok, pos, dkp, dvp, page_tables, live, draft_cfg,
                impl=paged_impl, scatter_impl=scatter_impl, mesh=mesh,
            )
            scaled = (
                logits / jnp.maximum(temps, 1e-6)[:, None]
            ).astype(jnp.float32)
            proposed = jnp.where(
                temps <= 0.0,
                jnp.argmax(logits, axis=-1),
                jax.vmap(jax.random.categorical)(
                    jax.random.split(k_i, B), scaled
                ),
            ).astype(jnp.int32)
            proposed = jnp.where(live, proposed, tok)  # dead lanes hold
            logp = jax.nn.log_softmax(scaled, axis=-1)
            prop_valid = live
            one = live.astype(taken.dtype)
            taken = taken + one
            pos = pos + one  # dead lanes stop advancing
            live = live & (taken < gammas) & (pos < cap)
            return (
                live, (proposed, pos, taken, dkp, dvp),
                (proposed, logp, prop_valid),
            )

        def hold(live, state, k_i):
            # all draft lanes dead: hold tokens, emit junk log-probs under
            # an all-false validity row (never accepted)
            V = cfg.vocab_size
            return (
                state[0],
                jnp.zeros((B, V), jnp.float32),
                jnp.zeros((B,), bool),
            )

        taken0 = jnp.zeros_like(positions)
        live, state, (draft_toks, draft_logps, prop_valid) = masked_scan(
            step,
            hold,
            spec_lane & (positions < cap),
            (tokens, positions, taken0, dk, dv),
            keys[:gamma],
        )
        last_d, last_pos, _taken, dk, dv = state
        # complete the draft cache: the scan proposed its last token but
        # never wrote its KV — without this, a fully-accepted round leaves
        # a hole at position+γ and the NEXT round's draft attends to stale
        # state, collapsing acceptance (logits discarded; the draft is
        # small)
        _, dk, dv = llama.decode_step(
            d_params, last_d, last_pos, dk, dv, page_tables,
            spec_lane & (last_pos < cap), draft_cfg, impl=paged_impl,
            scatter_impl=scatter_impl, mesh=mesh,
        )
        draft_toks = draft_toks.T  # [B, γ]
        draft_logps = draft_logps.transpose(1, 0, 2)  # [B, γ, V]
        prop_valid = prop_valid.T  # [B, γ]

        # target scores the whole chain in ONE ragged pass against the
        # paged cache (γ=0 lanes still write their committed token's KV —
        # the classic decode_step's scatter, chain position 0)
        chain = jnp.concatenate([tokens[:, None], draft_toks], axis=1)
        t_logits, tk, tv = llama.verify_step(
            params, chain, positions, tk, tv, page_tables, active, cfg, mesh=mesh
        )  # [B, γ+1, V]
        out, n_emit = accept_reject(
            t_logits, draft_toks, temps, (keys[gamma], keys[gamma + 1]),
            active, gamma=gamma, proposal_logps=draft_logps,
            prop_valid=prop_valid,
        )
        classic_tok = sample(
            t_logits[:, 0], keys[gamma + 2], temps, top_ps, top_ks,
            seeds=seeds, step_ids=positions,
        )
        toks, valid, last = _emit_plane(
            out, n_emit, active, gammas, classic_tok
        )
        return toks, valid, last, tk, tv, dk, dv

    return spec_round_fn


def build_ngram_round_fn(cfg, *, gamma: int, mesh=None):
    """Build the jittable prompt-lookup round: host proposals → one ragged
    target verify + accept, emitting the harvest plane. No draft model, no
    draft cache, no device propose loop.

    Signature: ``(params, tk, tv, proposals [B, γ], n_prop [B], gammas
    [B], tokens, positions, page_tables, active, key, temps, top_ps,
    top_ks, seeds)`` → ``(toks [γ+1, B], valid [γ+1, B], last [B], tk,
    tv)``. ``n_prop`` counts real proposal slots per lane (already clamped
    ≤ gammas by the host); empty lookups degrade to one plain target step.
    """

    def ngram_round_fn(
        params, tk, tv, proposals, n_prop, gammas, tokens, positions,
        page_tables, active, key, temps, top_ps, top_ks, seeds,
    ):
        k1, k2, k3 = jax.random.split(key, 3)
        chain = jnp.concatenate([tokens[:, None], proposals], axis=1)
        t_logits, tk, tv = llama.verify_step(
            params, chain, positions, tk, tv, page_tables, active, cfg, mesh=mesh
        )  # [B, γ+1, V]
        prop_valid = jnp.arange(gamma)[None, :] < n_prop[:, None]
        out, n_emit = accept_reject(
            t_logits, proposals, temps, (k1, k2), active, gamma=gamma,
            prop_valid=prop_valid,
        )
        classic_tok = sample(
            t_logits[:, 0], k3, temps, top_ps, top_ks,
            seeds=seeds, step_ids=positions,
        )
        toks, valid, last = _emit_plane(
            out, n_emit, active, gammas, classic_tok
        )
        return toks, valid, last, tk, tv

    return ngram_round_fn
