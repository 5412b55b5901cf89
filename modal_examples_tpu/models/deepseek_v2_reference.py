"""DeepSeek-V2's forward pass, plain: float32, ``highest`` matmul precision,
no kernel, no cache, no batching. What ``models/deepseek_v2.py`` (the
program: latent paged cache, expanded prefill, absorbed decode, sparse expert
dispatch) is held to in the tests, written from the published ``config.json``
and ``modeling_deepseek``; each departure of the program is noted there, none
here. The serving benchmark keeps a copy of its own that imports nothing from
the program (``benchmarks/serving/families/deepseek_v2.py``);
``tests/test_deepseek_v2.py`` holds the two equal.

Per layer, with ``h = RMSNorm(x)``: ``c_q = RMSNorm(h W_qa)``, ``q = c_q
W_qb`` as heads of ``[q_nope, q_pe]``; ``[c_kv, k_pe] = h W_kva``, ``c_kv =
RMSNorm(c_kv)``, ``k_pe`` one key for all heads; ``[k_nope, v]`` per head ``=
c_kv W_kvb``; rotary on ``q_pe`` and ``k_pe`` only, pairs ``(2i, 2i+1)``,
yarn frequencies; scores ``(q_nope . k_nope + q_pe . k_pe) * softmax_scale``,
causal, softmax in float32. Feed-forward: SwiGLU in the leading dense
layers; after them a softmax router, group-limited greedy top-k, weights not
renormalised and scaled, plus the shared experts. With a share of the experts
(``cfg.expert_offset``, ``cfg.n_held_experts``) the routed sum runs over the
chosen experts inside the share only.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rope_dim: int, theta: float, scaling: dict | None) -> list[float]:
    """The ``rope_dim / 2`` inverse frequencies as Python floats: each a
    blend of ``f / factor`` and ``f`` by the linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow``."""
    extra = [theta ** (-2.0 * i / rope_dim) for i in range(rope_dim // 2)]
    if not scaling:
        return extra
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return rope_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), rope_dim - 1)
    span = (high - low) or 0.001
    ramps = [min(max((i - low) / span, 0.0), 1.0) for i in range(rope_dim // 2)]
    return [f / factor * r + f * (1.0 - r) for f, r in zip(extra, ramps)]


def rope_mscale(scaling: dict | None) -> float:
    """What cos and sin are multiplied by: 1 for the published config."""
    if not scaling:
        return 1.0
    f = float(scaling["factor"])
    return yarn_mscale(f, float(scaling["mscale"])) / yarn_mscale(
        f, float(scaling["mscale_all_dim"])
    )


def softmax_scale(cfg) -> float:
    scaling = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    m = yarn_mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"])) if scaling else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _f32(w):
    """A program weight (plain or QuantizedWeight) in float32."""
    if hasattr(w, "scale"):
        return w.q.astype(jnp.float32) * w.scale
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope_pairs(x, positions, cfg):
    """x: [S, heads, rope]; pairs (2i, 2i+1), as published."""
    scaling = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    inv_freq = jnp.asarray(
        yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, scaling), jnp.float32
    )
    angle = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    m = rope_mscale(scaling)
    cos, sin = (jnp.cos(angle) * m)[:, None, :], (jnp.sin(angle) * m)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def attention(h, layer, cfg):
    """h: [S, dim] (normed) -> [S, dim]."""
    S = h.shape[0]
    H, nope, rope, vd, rank = (
        cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        cfg.kv_lora_rank,
    )
    pos = jnp.arange(S)
    c_q = _rms_norm(h @ _f32(layer["wq_a"]), layer["q_norm"], cfg.norm_eps)
    q = (c_q @ _f32(layer["wq_b"])).reshape(S, H, nope + rope)
    kv_a = h @ _f32(layer["wkv_a"])
    c_kv = _rms_norm(kv_a[:, :rank], layer["kv_norm"], cfg.norm_eps)
    k_pe = _rope_pairs(kv_a[:, None, rank:], pos, cfg)[:, 0]
    kv = (c_kv @ _f32(layer["wkv_b"])).reshape(S, H, nope + vd)
    q_pe = _rope_pairs(q[..., nope:], pos, cfg)
    scores = jnp.einsum("shd,thd->hst", q[..., :nope], kv[..., :nope])
    scores = (scores + jnp.einsum("shr,tr->hst", q_pe, k_pe)) * softmax_scale(cfg)
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return o.reshape(S, H * vd) @ _f32(layer["wo"])


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def route(scores, cfg):
    """``scores`` [S, router width], a softmax. Returns (weights [S, k],
    expert ids [S, k], margin [S]): group-limited greedy top-k, and the
    narrowest relative margin ``1 - next / last`` over both selections."""
    S, E = scores.shape
    G, k = cfg.n_group, cfg.top_k_experts
    margin = jnp.full((S,), jnp.inf)
    masked = scores
    if G > 1:
        group_scores = scores.reshape(S, G, E // G).max(axis=-1)
        ranked, group_ids = jax.lax.top_k(group_scores, min(cfg.topk_group + 1, G))
        if cfg.topk_group < G:
            margin = 1.0 - ranked[:, -1] / ranked[:, -2]
        keep = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], group_ids[:, : cfg.topk_group]
        ].set(True)
        masked = jnp.where(jnp.repeat(keep, E // G, axis=1), scores, 0.0)
    ranked, ids = jax.lax.top_k(masked, k + 1)
    margin = jnp.minimum(margin, 1.0 - ranked[:, k] / ranked[:, k - 1])
    weights = ranked[:, :k]
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, ids[:, :k], margin


def routed_mlp(h, layer, cfg, *, share: tuple[int, int] | None = None, shared: bool = True):
    """The routed layer's output and the routing margin. ``share``: (first
    expert, count) of the experts ``layer`` holds (default: the config's);
    ``shared=False`` leaves the shared experts out (for adding shares up)."""
    offset, count = share if share is not None else (cfg.expert_offset, cfg.n_held_experts)
    scores = jax.nn.softmax(h @ layer["router"].astype(jnp.float32), axis=-1)
    weights, ids, margin = route(scores, cfg)
    out = jnp.zeros_like(h)
    for e in range(count):
        weight = jnp.sum(jnp.where(ids == e + offset, weights, 0.0), axis=-1)
        one = [
            jax.tree.map(lambda a: a[e], layer[n]) for n in ("moe_gate", "moe_up", "moe_down")
        ]
        out = out + weight[:, None] * swiglu(h, *one)
    if shared and cfg.n_shared_experts:
        out = out + swiglu(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
    return out, margin


def layers_of(params: dict, cfg):
    """The layers in order, each as (its pytree, whether it is dense)."""
    for name, dense in (("dense_layers", True), ("moe_layers", False)):
        stack = params.get(name)
        if stack is not None:
            n = jax.tree.leaves(stack)[0].shape[0]
            for i in range(n):
                yield jax.tree.map(lambda a: a[i], stack), dense


def forward(params: dict, tokens, cfg):
    """tokens: [S] int -> (logits [S, vocab] float32, margin [S])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        margin = jnp.full((x.shape[0],), jnp.inf)
        for layer, dense in layers_of(params, cfg):
            x = x + attention(_rms_norm(x, layer["attn_norm"], cfg.norm_eps), layer, cfg)
            h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            if dense:
                x = x + swiglu(h, layer["gate"], layer["up"], layer["down"])
            else:
                out, m = routed_mlp(h, layer, cfg)
                x, margin = x + out, jnp.minimum(margin, m)
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ _f32(params["lm_head"]), margin
