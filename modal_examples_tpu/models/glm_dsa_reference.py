"""GLM-5.2's forward pass in plain float32 ``jax.numpy``: the reference
``models/glm_dsa.py`` is tested against. No cache, no kernels, no batching:
one sequence, a Python loop over the layers, the dense ``[T, T]`` index
scores, ``lax.top_k``, a masked softmax, every matmul at ``highest``
precision (on a TPU a float32 matmul is otherwise computed in bf16).

It follows the catalog's ``glm_moe_dsa`` config and, for the forms that
config does not spell, the published DeepSeek sparse-attention indexer
(docs/sparse_attention.md has the equations). Departures, each of which
leaves the result what the published layout gives:

- the rotated slices (of the attention's q and k, of the indexer's q and k)
  rotate the published pairs ``(2i, 2i+1)`` in place, as the weights are laid
  out (``rope_interleave`` / ``indexer_rope_interleave`` true); the program
  stores the same values de-interleaved on both sides of each dot product;
- the indexer's two scales (``index_n_heads^-0.5`` on the heads' weights,
  ``index_head_dim^-0.5`` on the scores) are one factor on the weights;
- a tie at the ``index_topk``-th score goes to the lowest position
  (``lax.top_k``'s order); the published kernel leaves it unspecified;
- the multi-token-prediction block is not run (``num_nextn_predict_layers``
  0): the next-token logits do not depend on it;
- "experts held here": the sum runs over the chosen experts inside the share
  ``expert_offset .. +n_held_experts``; what the others would add is left out.

Shapes: ``h`` [T, dim]; a layer is one slice of the program's stacked tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .deepseek_v2_reference import _f32, _rms_norm, _rope_pairs, layers_of, swiglu


def _layer_norm(x, weight, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(
        jnp.float32
    )


def _rope_head(x, positions, cfg):
    """Rotate the first ``qk_rope_head_dim`` values of x [T, heads, width]."""
    rope = cfg.qk_rope_head_dim
    return jnp.concatenate([_rope_pairs(x[..., :rope], positions, cfg), x[..., rope:]], axis=-1)


def index_scores(h, c_q, ip, cfg):
    """``I[t, s]`` [T, T] of one indexer ``ip`` (no causal mask yet)."""
    T = h.shape[0]
    pos = jnp.arange(T)
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    q = _rope_head((c_q @ _f32(ip["wq_idx"])).reshape(T, Hi, Di), pos, cfg)
    k = _layer_norm(h @ _f32(ip["wk_idx"]), ip["k_norm"], ip["k_norm_bias"], cfg.norm_eps)
    k = _rope_head(k[:, None, :], pos, cfg)[:, 0]
    w = (h @ _f32(ip["w_idx"])) * (Hi ** -0.5 * Di ** -0.5)
    return jnp.einsum("tj,tjs->ts", w, jax.nn.relu(jnp.einsum("tjd,sd->tjs", q, k)))


def select(scores, k: int):
    """[T, T] index scores -> the selection as a mask [T, T]: row t keeps
    the ``k`` positions ``s <= t`` of largest score (all while ``t < k``)."""
    T = scores.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    if T <= k:
        return causal
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    chosen = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return chosen & causal


def attention(h, layer, cfg, *, indexer=None, selected=None):
    """h: [T, dim] (normed) -> ([T, dim], the selection mask [T, T] used).
    ``indexer``: this layer's (a full layer), else ``selected`` is what the
    nearest full layer before it chose. A ``selected`` given beside an
    indexer overrides the layer's own choice (a test hands the program's)."""
    T = h.shape[0]
    H, nope, vd, rank = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pos = jnp.arange(T)
    c_q = _rms_norm(h @ _f32(layer["wq_a"]), layer["q_norm"], cfg.norm_eps)
    q = (c_q @ _f32(layer["wq_b"])).reshape(T, H, nope + cfg.qk_rope_head_dim)
    kv_a = h @ _f32(layer["wkv_a"])
    c_kv = _rms_norm(kv_a[:, :rank], layer["kv_norm"], cfg.norm_eps)
    k_pe = _rope_pairs(kv_a[:, None, rank:], pos, cfg)[:, 0]
    kv = (c_kv @ _f32(layer["wkv_b"])).reshape(T, H, nope + vd)
    q_pe = _rope_pairs(q[..., nope:], pos, cfg)
    if selected is None:
        selected = select(index_scores(h, c_q, indexer, cfg), cfg.index_topk)
    scores = jnp.einsum("shd,thd->hst", q[..., :nope], kv[..., :nope])
    scores = (scores + jnp.einsum("shr,tr->hst", q_pe, k_pe)) * cfg.qk_head_dim ** -0.5
    scores = jnp.where(selected[None], scores, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return o.reshape(T, H * vd) @ _f32(layer["wo"]), selected


def route(p, bias, cfg):
    """``p`` [T, router width] sigmoid scores. Returns (weights [T, k],
    expert ids [T, k], margin [T]): chosen by ``p + bias``, weighted by the
    chosen ``p`` renormalised times the scale; the margin is the gap of the
    biased scores between the last chosen and the first left out."""
    k = cfg.top_k_experts
    ranked, ids = jax.lax.top_k(p + bias.astype(jnp.float32), k + 1)
    weights = jnp.take_along_axis(p, ids[:, :k], axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, ids[:, :k], ranked[:, k - 1] - ranked[:, k]


def routed_mlp(h, layer, cfg, *, share: tuple[int, int] | None = None, shared: bool = True):
    """The routed layer's output and the routing margin. ``share``: (first
    expert, count) of the experts ``layer`` holds (default: the config's);
    ``shared=False`` leaves the shared expert out (for adding shares up)."""
    offset, count = share if share is not None else (cfg.expert_offset, cfg.n_held_experts)
    p = jax.nn.sigmoid(h @ layer["router"].astype(jnp.float32))
    weights, ids, margin = route(p, layer["router_bias"], cfg)
    out = jnp.zeros_like(h)
    for e in range(count):
        weight = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), axis=-1)
        one = [jax.tree.map(lambda a: a[e], layer[n]) for n in ("moe_gate", "moe_up", "moe_down")]
        out = out + weight[:, None] * swiglu(h, *one)
    if shared and cfg.n_shared_experts:
        out = out + swiglu(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return out, margin


def forward(params: dict, tokens, cfg, *, selected=None, select_all: bool = False):
    """tokens: [T] int -> (logits [T, vocab] float32, routing margin [T],
    the selection mask of each layer [L, T, T]). ``selected`` [L, T, T]:
    attend to these positions instead of the reference's own choice (what a
    test hands over to tell "the program selected other positions at a
    near-tie" from "the attention over the same positions differs").
    ``select_all``: selection off, every layer attends to every cached
    position (the control a check has to tell from the model)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        T = x.shape[0]
        margin = jnp.full((T,), jnp.inf)
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        kinds = cfg.layer_kinds
        n_full, carried, used = 0, None, []
        for li, (layer, dense) in enumerate(layers_of(params, cfg)):
            indexer = None
            if kinds[li] == "full":
                indexer = jax.tree.map(lambda a: a[n_full], params["indexer_layers"])
                n_full += 1
                carried = None  # a full layer replaces the carry
            given = causal if select_all else (selected[li] if selected is not None else carried)
            out, carried = attention(
                _rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer, cfg,
                indexer=indexer, selected=given,
            )
            used.append(carried)
            x = x + out
            h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            if dense:
                x = x + swiglu(h, layer["gate"], layer["up"], layer["down"])
            else:
                out, m = routed_mlp(h, layer, cfg)
                x, margin = x + out, jnp.minimum(margin, m)
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ _f32(params["lm_head"]), margin, jnp.stack(used)
