"""Mixture-of-Experts: top-k routing + expert parallelism over a mesh axis.

The reference serves MoE models (Gemma-4-26B-A4B via vllm_inference.py:54-58,
Qwen MoE, DeepSeek configs) but leaves expert parallelism inside the CUDA
engines (SURVEY.md §2.3: "MoE routing + expert sharding on mesh axis;
all_to_all over ICI" is ours to build). This module implements the GShard
dispatch TPU-natively:

- top-k softmax routing with per-(group, expert) capacity and position-in-
  expert assignment (static shapes: dropped tokens are zeroed, not ragged);
- ``moe_mlp``: the single-device ground truth (groups = what shards will
  see, so the EP result is bit-identical);
- ``moe_mlp_ep``: the same math under shard_map with experts sharded over an
  ``expert`` mesh axis — dispatch/return ride two ``all_to_all``s (ICI on a
  real slice);
- the standard load-balancing auxiliary loss;
- the serving form of a routed SwiGLU layer (``moe_swiglu_routed`` ->
  ``moe_swiglu_sparse``, shared by ``models/llama.py`` and
  ``models/deepseek_v2.py``): only the (token, expert) pairs the router
  chose, sorted into tiles of one expert each, the expert's weights indexed
  ``[layer, expert]`` out of the whole stack (``scan_layers`` keeps them
  out of the layer scan's sliced inputs). ``moe_swiglu_nodrop``, every
  expert on every token in float32, is the ground truth of the tests and
  the training forward; no serving program calls it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..ops.expert_swiglu import expert_swiglu, expert_swiglu_shapes_ok
from ..ops.scopes import EXPERT_DISPATCH, EXPERT_SCAN, ROUTER


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_model: int = 64
    d_ff: int = 128

    def capacity(self, tokens_per_group: int) -> int:
        c = int(self.capacity_factor * self.top_k * tokens_per_group / self.n_experts)
        return max(c, 1)


def init_params(key: jax.Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in, s_out = D**-0.5, F**-0.5
    return {
        "router": jax.random.normal(k1, (D, E), dtype) * s_in,
        "w_in": jax.random.normal(k2, (E, D, F), dtype) * s_in,
        "w_out": jax.random.normal(k3, (E, F, D), dtype) * s_out,
    }


def _route(x: jax.Array, router: jax.Array, cfg: MoEConfig, capacity: int):
    """Per-group dispatch/combine tensors.

    x: [T, D] (one group). Returns (dispatch [T, E, C] bool-ish f32,
    combine [T, E, C] f32 weights, aux_loss scalar).
    """
    T = x.shape[0]
    E = cfg.n_experts
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # aux load-balance loss (Switch): mean prob mass * mean assignment frac
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    topk_p, topk_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)  # renormalize

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)  # slots used per expert so far
    for k in range(cfg.top_k):
        e_k = topk_idx[:, k]  # [T]
        onehot = jax.nn.one_hot(e_k, E, dtype=jnp.int32)  # [T, E]
        # position of each token within its expert (prior ks first)
        pos_in_e = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]  # [T, E]
        pos = jnp.take_along_axis(pos_in_e, e_k[:, None], 1)[:, 0]  # [T]
        keep = pos < capacity
        slot = jnp.clip(pos, 0, capacity - 1)
        d_k = (
            jax.nn.one_hot(e_k, E)[:, :, None]
            * jax.nn.one_hot(slot, capacity)[:, None, :]
            * keep[:, None, None]
        )
        dispatch = dispatch + d_k
        combine = combine + d_k * topk_p[:, k][:, None, None]
        counts = counts + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
    return dispatch, combine, aux


def _expert_ffn(w_in, w_out, h):
    """h: [..., C, D] per expert; gelu MLP with that expert's weights."""
    return jnp.einsum(
        "...cf,fd->...cd",
        jax.nn.gelu(jnp.einsum("...cd,df->...cf", h, w_in)),
        w_out,
    )


def moe_mlp(
    params: dict, x: jax.Array, cfg: MoEConfig, *, groups: int = 1
) -> tuple[jax.Array, jax.Array]:
    """Ground-truth MoE layer. x: [T, D]; ``groups`` partitions tokens the
    way EP shards would (so capacities — and therefore drops — match the
    sharded version exactly). Returns (out [T, D], aux_loss)."""
    T, D = x.shape
    assert T % groups == 0
    tg = T // groups
    cap = cfg.capacity(tg)
    xg = x.reshape(groups, tg, D)

    def per_group(xg_i):
        dispatch, combine, aux = _route(xg_i, params["router"], cfg, cap)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xg_i)  # [E, C, D]
        expert_out = jax.vmap(_expert_ffn)(
            params["w_in"], params["w_out"], expert_in
        )  # [E, C, D]
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        return out, aux

    out, aux = jax.vmap(per_group)(xg)
    return out.reshape(T, D), jnp.mean(aux)


def _mm(h, w):
    """h @ w where w may be an int8 QuantizedWeight (a quantized tree goes
    through ``forward`` too: models.quantize.LLAMA_TARGETS includes
    moe_gate/up/down). Delegates to layers.mm (the one quantized-matmul
    dispatch) and rounds back to h's dtype."""
    from .layers import mm

    return mm(h, w).astype(h.dtype)


def _swiglu_expert(w_gate, w_up, w_down, h):
    """SwiGLU expert FFN (Mixtral w1/w3/w2): h [T, D] -> [T, D]."""
    a = _mm(h, w_gate)
    b = _mm(h, w_up)
    return _mm(jax.nn.silu(a) * b, w_down)


def moe_swiglu_nodrop(
    router: jax.Array,  # [D, E]
    w_gate: jax.Array,  # [E, D, F]
    w_up: jax.Array,  # [E, D, F]
    w_down: jax.Array,  # [E, F, D]
    x: jax.Array,  # [T, D]
    top_k: int,
) -> tuple[jax.Array, jax.Array]:
    """Top-k routed SwiGLU experts with NO capacity drops, every expert run
    on every token in float32: the per-token ground truth (what the
    capacity-routed training path approximates and ``moe_swiglu_routed``,
    the serving form, is tested against) and ``llama.forward``'s default.

    Routing is per-token, so a full-sequence pass through it and the serving
    programs' incremental passes agree position by position up to the
    order of float summation. The [T, F] intermediate stays bounded by
    scanning over experts rather than materializing [T, E, F]; the work is
    E / top_k times what the chosen pairs need, which is why nothing that
    serves runs it.

    Returns (out [T, D] float32, aux load-balance loss).
    """
    E = w_gate.shape[0]
    xf = x.astype(jnp.float32)
    with jax.named_scope(ROUTER):
        logits = jnp.einsum("td,de->te", xf, router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

        top1 = jnp.argmax(probs, axis=-1)
        aux = E * jnp.sum(
            jnp.mean(jax.nn.one_hot(top1, E), axis=0) * jnp.mean(probs, axis=0)
        )

        topk_p, topk_idx = jax.lax.top_k(probs, top_k)  # [T, k]
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
        # [T, E] combine weights, zero off the top-k
        w_full = jnp.zeros_like(probs)
        w_full = jax.vmap(lambda w, p, i: w.at[i].add(p))(
            w_full, topk_p, topk_idx
        )

    def body(acc, ew):
        wg, wu, wd, we = ew  # we: [T] this expert's combine weight per token
        return acc + we[:, None] * _swiglu_expert(wg, wu, wd, xf), None

    with jax.named_scope(EXPERT_SCAN):
        out, _ = jax.lax.scan(
            body,
            jnp.zeros_like(xf),
            (w_gate, w_up, w_down, w_full.T),
        )
    return out, aux



def route_group_limited(
    scores: jax.Array,  # [T, E] f32 — the router's softmax over all E experts
    top_k: int,
    *,
    n_group: int = 1,
    topk_group: int = 1,
    scale: float = 1.0,
    renormalize: bool = False,
    bias: jax.Array | None = None,  # [E] f32 — added for the selection only
) -> tuple[jax.Array, jax.Array]:
    """Group-limited greedy top-k (DeepSeek-V2's ``group_limited_greedy``):
    a group's score is its largest expert score, the ``topk_group`` best of
    ``n_group`` groups stay and the other groups' scores read zero, then the
    ``top_k`` largest of what is left. The weights are those scores, not
    renormalised unless asked, times ``scale``. ``n_group=1`` is the plain
    top-k. With ``bias`` (``noaux_tc``: GLM-5.2's, at ``n_group=1``) the
    experts are chosen by ``scores + bias`` and weighted by their unbiased
    scores. Returns (weights [T, k] f32, expert ids [T, k] int32)."""
    T, E = scores.shape
    if bias is not None:
        if n_group > 1:
            raise NotImplementedError("a selection bias with a group limit")
        _, ids = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
        if renormalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return weights * scale, ids.astype(jnp.int32)
    if n_group > 1:
        group_scores = scores.reshape(T, n_group, E // n_group).max(axis=-1)
        _, group_ids = jax.lax.top_k(group_scores, topk_group)  # [T, kg]
        keep = jax.nn.one_hot(group_ids, n_group, dtype=jnp.int32).sum(axis=1) > 0
        scores = jnp.where(jnp.repeat(keep, E // n_group, axis=1), scores, 0.0)
    weights, ids = jax.lax.top_k(scores, top_k)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, ids.astype(jnp.int32)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


#: the expert leaves of a layer stack, [L, E, ...] each
EXPERT_LEAVES = ("moe_gate", "moe_up", "moe_down")


def scan_layers(stack: dict, layer_fn, x, *per_layer):
    """``lax.scan`` of ``layer_fn(x, layer, i, *per_layer[i]) -> (x, ys)``
    over a stack of layers (leaves [n, ...]) for the serving programs. The
    expert leaves stay out of the scanned inputs: a routed layer's dict holds
    the experts' whole stacks and ``expert_layer``, its own index into them,
    and ``moe_swiglu_sparse`` picks ``[layer, expert]`` where it multiplies.
    Scanned like the other leaves, a layer's slice of them is a copy of
    every expert of the layer (1.4 GB of a Mixtral layer in int8, 64% of its
    decode step) before anything reads it."""
    whole = {k: stack[k] for k in EXPERT_LEAVES if k in stack}
    sliced = {k: v for k, v in stack.items() if k not in whole}
    n = jax.tree.leaves(sliced)[0].shape[0]

    def body(x, scanned):
        layer, i, *rest = scanned
        if whole:
            layer = dict(layer, **whole, expert_layer=i)
        return layer_fn(x, layer, i, *rest)

    return jax.lax.scan(body, x, (sliced, jnp.arange(n), *per_layer))


#: a decode-shaped call's tile holds this many times the mean pairs an expert
TILE_SPREAD = 4


def expert_tile(n_tokens: int, top_k: int, n_held: int) -> int:
    """Rows of one tile of ``moe_swiglu_sparse``, from the shapes of the call
    (its tokens, the experts each chooses, the experts held here).

    A call of 128 tokens or more (a prefill bucket or chunk): 128 rows, where
    an int8 expert matrix's read and a tile's matmul take a v5e about as
    long, so the weights an expert's next tile reads again stream under its
    matmuls, and what a larger tile adds is padding, half a tile an expert.
    Measured on the chip, one Mixtral layer (8 experts of 4096 x 14336 int8,
    2 a token, PR 28): 2048 tokens 12.5 / 13.4 / 15.1 / 18.1 ms at 128 / 256 /
    512 / 1024 rows, 8192 tokens 39.6 / 40.2 / 43.7 / 53.7 ms at 256 / 512 /
    1024 / 2048, 128 also ahead at 512 and 1024 tokens; DeepSeek-V2's 77 pairs
    an expert a chunk sit in one such tile.

    A decode-shaped call (fewer than 128 tokens) reads each reached expert's
    matrices for a handful of rows, and its tile is what holds ``TILE_SPREAD``
    times the mean pairs an expert, ``tokens x top_k / held``, in whole
    16-row vregs of bf16 (the smallest the MXU's operand layout takes), at
    most every token: 16 rows at LFM2's 64 x 4 / 64 and at Mixtral's
    16 x 2 / 8, where the 64 rows of "every token in one tile" were 94%
    padding. An expert with more pairs than a tile takes several tiles (with
    F in one block its matrices are read once for them). Measured on the
    chip, one LFM2 layer (62 tokens, 4 of 64 experts of 2048 x 1536 int8, the
    most pairs on one expert 13, PR 40): the grouped matmul 840 / 854 / 878 us
    at 16 / 32 / 64 rows, XLA's loop 1224 / 1313 at 16 / 64."""
    if n_tokens >= 128:
        return 128
    mean = -(-n_tokens * top_k // n_held)
    return min(_round_up(n_tokens, 16), _round_up(TILE_SPREAD * mean, 16))


def expert_scan_form(n_tokens: int, d_model: int, d_ff: int, dtype) -> str:
    """Which form of the tile loop ``moe_swiglu_sparse`` runs, from what can
    be seen here and by no option: ``"pallas"`` (ops.expert_swiglu: one
    grouped matmul whose pipeline fetches the next tile's expert under this
    tile's products) for a decode-shaped call (fewer than 128 tokens) on a
    TPU whose expert shapes the kernel takes (``expert_swiglu_shapes_ok``);
    ``"xla"`` (the ``fori_loop`` of a trip a tile) for every other call: a
    prefill bucket or chunk at 128-row tiles, the CPU, where the kernel would
    run in the interpreter, and any shape the kernel refuses. The families'
    ``paged_impl_plan`` names a decode step's form with this function
    (``expert_scan``)."""
    ok = (
        jax.default_backend() == "tpu" and n_tokens < 128
        and expert_swiglu_shapes_ok(d_model, d_ff, dtype)
    )
    return "pallas" if ok else "xla"


def expert_dtype(params):
    """The dtype of the experts' matrices in a tree of parameters (a
    QuantizedWeight's is its ``q``'s), or None where the tree holds none:
    what the engine hands a family's ``paged_impl_plan``."""
    if not isinstance(params, dict):
        return None
    if EXPERT_LEAVES[0] in params:
        return params[EXPERT_LEAVES[0]].dtype
    return next((d for d in map(expert_dtype, params.values()) if d is not None), None)


def moe_swiglu_routed(
    router: jax.Array,  # [D, E_router]
    w_gate,  # [E_held, D, F] or, with ``layer``, [L, E_held, D, F]
    w_up,
    w_down,
    x: jax.Array,  # [T, D]
    top_k: int,
    *,
    layer: jax.Array | None = None,
    expert_offset: int = 0,
    token_mask: jax.Array | None = None,
    scan: str | None = None,  # moe_swiglu_sparse's
    score: str = "softmax",  # or "sigmoid": each expert scored on its own
    **routing,  # route_group_limited's: n_group, topk_group, scale, renormalize, bias
) -> tuple[jax.Array, jax.Array]:
    """A routed SwiGLU layer as the serving programs run it: the router's
    softmax (or sigmoid) over its whole width in f32, ``route_group_limited``
    (plain top-k renormalised is Mixtral's, group-limited and scaled
    DeepSeek-V2's, sigmoid scores chosen with a bias GLM-5.2's),
    then only the chosen (token, expert) pairs through
    ``moe_swiglu_sparse``: activations in ``x``'s dtype into the tile
    matmuls, f32 accumulation, the combine in f32. Nothing is dropped.
    Returns (out [T, D] f32, counts [2] int32) as ``moe_swiglu_sparse``."""
    with jax.named_scope(ROUTER):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
        if score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        weights, ids = route_group_limited(scores, top_k, **routing)
    return moe_swiglu_sparse(
        w_gate, w_up, w_down, x, ids, weights,
        expert_offset=expert_offset, token_mask=token_mask, layer=layer, scan=scan,
    )


def moe_swiglu_sparse(
    w_gate,  # [E_held, D, F] — plain or QuantizedWeight
    w_up,
    w_down,  # [E_held, F, D]
    x: jax.Array,  # [T, D]
    ids: jax.Array,  # [T, k] int32 — expert ids out of the router's full width
    weights: jax.Array,  # [T, k] f32 — their combine weights
    *,
    expert_offset: int = 0,  # id of the first expert held here
    token_mask: jax.Array | None = None,  # [T] bool — tokens that count
    tile: int | None = None,
    layer: jax.Array | None = None,  # weights are [L, E_held, ...]: this layer
    scan: str | None = None,  # "pallas" / "xla"; unset: expert_scan_form's choice
    activation: str = "silu",  # the gate's: "silu" (SwiGLU) or "relu" (ReGLU)
) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of a routed SwiGLU (or ReGLU) layer, computing only the
    (token, expert) pairs that land on them: exact, no capacity, no dropped
    pair, work proportional to the pairs up to a tile's padding.

    "Experts held here": the weights hold experts ``expert_offset ..
    expert_offset + E_held - 1`` of a router that is wider; pairs routed
    elsewhere add nothing (another chip's share would). The pairs are sorted
    by expert, each expert's run padded to whole tiles of ``tile`` rows, and
    the tiles *in use* are computed one by one: a tile's token rows through
    the three matmuls against that one expert's weights (indexed out of the
    stack, so an expert no pair reaches is never read). The combine gathers
    each token's ``k`` rows back (a pair not computed here adds zeros) and
    sums them under the weights in f32. Static shapes throughout: the rows
    hold the worst case, all ``T * k`` pairs held plus a partial tile an
    expert; only the rows in use are touched.

    Two forms of the tile loop, one arithmetic (``scan``; unset:
    ``expert_scan_form``): ``"xla"``, a ``fori_loop`` whose trip gathers a
    tile's rows, runs three matmul fusions and writes the tile into a float32
    row buffer; ``"pallas"``, for a decode-shaped call on the chip, one
    grouped matmul over the rows gathered once (ops.expert_swiglu), which
    writes each live tile once and needs no buffer zeroed.

    With ``layer`` (a traced scalar) the weights keep a leading layer axis
    and a tile indexes ``[layer, expert]`` out of the whole stack: a layer
    scan that sliced the stack per layer instead would copy every expert of
    the layer, reached or not, before the loop ran (``scan_layers``).

    ``tile`` defaults to ``expert_tile(T, k, E_held)``.

    Returns (out [T, D] f32, counts [2] int32: the pairs of counted tokens
    that landed on held experts, and all their pairs).
    """
    from .layers import mm

    T, D = x.shape
    k = ids.shape[1]
    E, F = w_gate.shape[-3], w_gate.shape[-1]
    TM = tile or expert_tile(T, k, E)
    scan = scan or expert_scan_form(T, D, F, w_gate.dtype)
    M = T * k
    M_pad = _round_up(M + E * (TM - 1), TM)  # rows; row M_pad stays zero
    with jax.named_scope(EXPERT_DISPATCH):
        local = ids - expert_offset
        held = (local >= 0) & (local < E)
        if token_mask is not None:
            held = held & token_mask[:, None]
            n_all = jnp.sum(token_mask.astype(jnp.int32)) * k
        else:
            n_all = jnp.asarray(M, jnp.int32)
        counts_out = jnp.stack([jnp.sum(held.astype(jnp.int32)), n_all])
        eid = jnp.where(held, local, E).reshape(M)  # E: not computed here
        order = jnp.argsort(eid, stable=True)
        eid_sorted = eid[order]
        counts = jnp.bincount(eid, length=E + 1)[:E]
        padded = (counts + TM - 1) // TM * TM
        ends = jnp.cumsum(padded)  # [E] — row where each expert's tiles end
        n_tiles = ends[-1] // TM
        e_of = jnp.minimum(eid_sorted, E - 1)
        rank = jnp.arange(M) - (jnp.cumsum(counts) - counts)[e_of]
        row_sorted = jnp.where(
            eid_sorted < E, (ends - padded)[e_of] + rank, M_pad
        )
        # which token each row of the buffer computes; T: a row of zeros
        row_token = jnp.full((M_pad,), T, jnp.int32).at[row_sorted].set(
            (order // k).astype(jnp.int32), mode="drop"
        )
        row_of_pair = jnp.zeros((M,), jnp.int32).at[order].set(
            row_sorted.astype(jnp.int32)
        ).reshape(T, k)
        tile_expert = jnp.minimum(
            jnp.searchsorted(ends, jnp.arange(M_pad // TM) * TM, side="right"),
            E - 1,
        ).astype(jnp.int32)
        x_rows = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)

    if scan == "pallas":
        with jax.named_scope(EXPERT_DISPATCH):
            rows = x_rows[row_token]  # [M_pad, D]: every tile's rows, gathered once
        with jax.named_scope(EXPERT_SCAN):
            whole = (lambda a: a) if layer is not None else (lambda a: a[None])
            buf = expert_swiglu(
                *(jax.tree.map(whole, w) for w in (w_gate, w_up, w_down)),
                rows, tile_expert, n_tiles, 0 if layer is None else layer, tile=TM,
                activation=activation,
            )
        with jax.named_scope(EXPERT_DISPATCH):
            # a tile past n_tiles was never written: no held pair points there
            picked = jnp.where(
                held[..., None], buf[jnp.minimum(row_of_pair, M_pad - 1)], 0.0
            )
            return jnp.einsum("tk,tkd->td", weights, picked), counts_out

    def one(w, e):
        """Expert ``e``'s matrix, sliced out of the stack where it is used."""
        lead = (e,) if layer is None else (layer, e)

        def pick(a):
            rest = a.shape[len(lead):]
            start = (*lead, *(0 for _ in rest))
            return jax.lax.dynamic_slice(a, start, (1,) * len(lead) + rest).reshape(rest)

        return jax.tree.map(pick, w)

    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation]

    def body(i, buf):
        with jax.named_scope(EXPERT_DISPATCH):
            rows = jax.lax.dynamic_slice_in_dim(row_token, i * TM, TM)
            xt = x_rows[rows]  # [TM, D]
        with jax.named_scope(EXPERT_SCAN):
            e = tile_expert[i]
            a = mm(xt, one(w_gate, e))
            b = mm(xt, one(w_up, e))
            y = mm((act(a) * b).astype(x.dtype), one(w_down, e))
        with jax.named_scope(EXPERT_DISPATCH):
            return jax.lax.dynamic_update_slice_in_dim(buf, y, i * TM, 0)

    buf = jax.lax.fori_loop(
        0, n_tiles, body, jnp.zeros((M_pad + 1, D), jnp.float32)
    )
    with jax.named_scope(EXPERT_DISPATCH):
        out = jnp.einsum("tk,tkd->td", weights, buf[row_of_pair])
    return out, counts_out


def moe_swiglu_capacity(
    router: jax.Array,  # [D, E]
    w_gate: jax.Array,  # [E, D, F]
    w_up: jax.Array,  # [E, D, F]
    w_down: jax.Array,  # [E, F, D]
    x: jax.Array,  # [T, D]
    top_k: int,
    capacity_factor: float,
) -> tuple[jax.Array, jax.Array]:
    """Capacity-routed SwiGLU experts (GShard dispatch): each expert computes
    only its capacity slots, ~top_k/E of the no-drop cost — the right
    formulation for compute-bound prefill/training at scale (tokens over
    capacity are dropped, so it is NOT bit-identical to the no-drop serving
    path). Returns (out [T, D] float32, aux load-balance loss)."""
    from .quantize import QuantizedWeight, dequantize_weight

    # the capacity path is compute-bound (training/prefill scale): int8
    # weights buy nothing here, so materialize bf16 instead of threading
    # QuantizedWeight through the batched dispatch einsums
    w_gate, w_up, w_down = (
        dequantize_weight(w) if isinstance(w, QuantizedWeight) else w
        for w in (w_gate, w_up, w_down)
    )
    E, D, F = w_gate.shape
    cfg = MoEConfig(
        n_experts=E, top_k=top_k, capacity_factor=capacity_factor,
        d_model=D, d_ff=F,
    )
    xf = x.astype(jnp.float32)
    cap = cfg.capacity(x.shape[0])
    with jax.named_scope(ROUTER):
        dispatch, combine, aux = _route(
            xf, router.astype(jnp.float32), cfg, cap
        )
    with jax.named_scope(EXPERT_SCAN):
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xf)  # [E, C, D]
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
        ) * jnp.einsum("ecd,edf->ecf", expert_in, w_up)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, aux


def moe_mlp_ep(
    params: dict, x: jax.Array, cfg: MoEConfig, mesh, *, axis: str = "expert"
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: tokens AND experts sharded over ``axis``; the
    dispatched activations cross shards via all_to_all (ICI), compute runs
    on each shard's local experts, results ride all_to_all back."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    E_loc = cfg.n_experts // n_shards
    T = x.shape[0]
    cap = cfg.capacity(T // n_shards)

    def shard_fn(router, w_in, w_out, x_loc):
        D = x_loc.shape[-1]
        dispatch, combine, aux = _route(x_loc, router, cfg, cap)  # [t, E, C]
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x_loc)  # [E, C, D]
        # global expert e = owner_shard * E_loc + e_loc (blocked layout):
        # send each owner its slice, receive every shard's tokens for OUR
        # local experts. untiled all_to_all on dim 0: consumed, and the
        # received blocks stack as a new leading dim of size S.
        send = expert_in.reshape(n_shards, E_loc, cap, D)
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)  # [S, E_loc, C, D]
        h = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * cap, D)
        out_loc = jax.vmap(_expert_ffn)(w_in, w_out, h)  # [E_loc, S*C, D]
        # return every shard's results to it, then reassemble global E order
        back = jax.lax.all_to_all(
            out_loc.reshape(E_loc, n_shards, cap, D).transpose(1, 0, 2, 3),
            axis, 0, 0, tiled=False,
        )  # [S, E_loc, C, D] — block j = my tokens through shard j's experts
        expert_out = back.reshape(cfg.n_experts, cap, D)
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        return out, aux[None]  # rank-1 so shards concatenate over the axis

    out, aux = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )(params["router"], params["w_in"], params["w_out"], x)
    return out, jnp.mean(aux)
