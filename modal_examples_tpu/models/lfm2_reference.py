"""LFM2's forward pass (``lfm2_moe``), plain: float32, ``highest`` matmul
precision, the convolution as shifted sums over the whole sequence, dense
causal attention, every expert's output for every token masked by the route,
no kernel, no cache, no tiles, no batching. What ``models/lfm2.py`` (the
program: a per-slot window beside a paged cache, a prefill form and a
one-token form of the convolution, routed pairs through tiles) is held to in
the tests, written from the published ``config.json`` (``model_type``
``lfm2_moe``) and the family's published modelling code. The serving
benchmark keeps a copy of its own that imports nothing from the program
(``benchmarks/serving/families/lfm2.py``);
``tests/bench_serving/test_lfm2_cell.py`` holds the two equal.

With ``x`` the residual stream and ``RMS(x) = w * x / sqrt(mean(x^2) + eps)``:

- ``x = E[token]``; layer ``l``: ``h = x + Mixer_l(RMS_op(x))``, ``y = h +
  FFN_l(RMS_ffn(h))``; after the last layer ``logits = RMS_out(x) E^T``, ``E``
  tied.
- convolution mixer (``layer_types[l] == "conv"``): ``[B, C, u] = split3(W_in
  x_t)``, no bias; ``g_t = B_t * u_t``; ``c_t = k_0 g_{t-2} + k_1 g_{t-1} +
  k_2 g_t`` per channel for ``conv_L_cache`` 3 (depthwise, causal, ``g``
  before the sequence's start 0, no bias, **no activation**); ``out_t = W_out
  (C_t * c_t)``. The state a sequence carries is ``(g_{t-2}, g_{t-1})``.
- attention mixer: bias-free ``q, k, v``; ``q <- RMS_q(q)``, ``k <- RMS_k(k)``
  over the width of each head, then rotary embedding over the whole head
  (half-split rotation: ``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``,
  frequencies ``theta^(-2i / head)``); causal softmax at ``head^-0.5``; GQA;
  bias-free output projection.
- FFN, the first ``num_dense_layers`` layers: ``W_2 (silu(W_1 x) * W_3 x)``.
  The others: ``s = sigmoid(W_g x)`` over all experts; the
  ``num_experts_per_tok`` experts of largest ``s + b`` (``b`` the layer's
  selection bias); their weights ``s_e`` (without ``b``) over ``(their sum +
  1e-6)`` times ``routed_scaling_factor``; ``sum_e w_e Expert_e(x)``, each
  expert the same SwiGLU at its own width.

Departures from the published code: none in the mathematics. The published
cache keeps ``conv_L_cache`` columns of ``g`` (the current one among them);
the state needed is one fewer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RENORM_EPS = 1e-6  # the published route's: routing_weights / (sum + 1e-6)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def conv_mixer(layer, u, window0=None):
    """u: [S, D] (normed) -> (out [S, D], the last ``K - 1`` rows of ``g``).
    ``window0`` [K - 1, D]: what came before the first position (zeros)."""
    S, D = u.shape
    K = layer["conv_w"].shape[0]
    bcx = u @ layer["in_proj"]
    B, C, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    g = B * x
    if window0 is None:
        window0 = jnp.zeros((K - 1, D), jnp.float32)
    ext = jnp.concatenate([window0, g], axis=0)  # [S + K - 1, D]
    c = sum(layer["conv_w"][j] * ext[j:j + S] for j in range(K))
    return (C * c) @ layer["out_proj"], ext[S:]


def rope(x, theta: float):
    """x: [S, heads, hd] at positions 0..S-1, the half-split rotation."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_mixer(layer, u, cfg):
    """u: [S, D] (normed) -> [S, D]: dense causal GQA over normed, rotated
    queries and keys."""
    S = u.shape[0]
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = rms_norm((u @ layer["wq"]).reshape(S, Hq, hd), layer["q_norm"], cfg.norm_eps)
    k = rms_norm((u @ layer["wk"]).reshape(S, Hkv, hd), layer["k_norm"], cfg.norm_eps)
    v = (u @ layer["wv"]).reshape(S, Hkv, hd)
    q = rope(q, cfg.rope_theta).reshape(S, Hkv, Hq // Hkv, hd)
    k = rope(k, cfg.rope_theta)
    scores = jnp.einsum("shgd,thd->hgst", q, k) * hd**-0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgst,thd->shgd", probs, v).reshape(S, Hq * hd)
    return o @ layer["wo"]


def swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def route(layer, u, cfg):
    """u: [S, D] -> the combine weights [S, E], zero off the chosen experts:
    chosen by ``s + b``, weighted by ``s``, renormalised, scaled."""
    s = jax.nn.sigmoid(u @ layer["router"])
    _, ids = jax.lax.top_k(s + layer["router_bias"], cfg.top_k_experts)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    w = w * cfg.routed_scaling_factor
    return jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], ids].set(w)


def routed(layer, u, cfg):
    """Every expert's output for every token, weighed by the route."""
    weights = route(layer, u, cfg)  # [S, E]
    out = jnp.zeros_like(u)
    for e in range(cfg.n_experts):
        y = swiglu(u, layer["moe_gate"][e], layer["moe_up"][e], layer["moe_down"][e])
        out = out + weights[:, e:e + 1] * y
    return out


def layer_at(params: dict, cfg, index: int) -> tuple[str, bool, dict]:
    """(mixer kind, dense feed-forward?, the float32 weights of layer
    ``index``) out of the program's tree, which stacks the layers of a kind:
    a row of a mixer's stack and a row of a feed-forward's."""
    kind = cfg.layer_types[index]
    dense = index < cfg.n_dense_layers
    mixer_row = sum(1 for t in cfg.layer_types[:index] if t == kind)
    mixer = "conv_layers" if kind == "conv" else "attention_layers"
    ffn, ffn_row = ("dense_layers", index) if dense else ("moe_layers", index - cfg.n_dense_layers)
    rows = {
        **jax.tree.map(lambda a: a[mixer_row], params[mixer]),
        **jax.tree.map(lambda a: a[ffn_row], params[ffn]),
    }
    return kind, dense, _f32(rows)


def forward(params: dict, tokens, cfg):
    """tokens [S] -> logits [S, vocab] in float32."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        x = embed[tokens]
        for index in range(cfg.n_layers):
            kind, dense, layer = layer_at(params, cfg, index)
            u = rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
            x = x + (conv_mixer(layer, u)[0] if kind == "conv" else attention_mixer(layer, u, cfg))
            u = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            if dense:
                x = x + swiglu(u, layer["gate"], layer["up"], layer["down"])
            else:
                x = x + routed(layer, u, cfg)
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x @ embed.T
