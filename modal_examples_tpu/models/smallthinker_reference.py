"""SmallThinker's forward pass (``smallthinker``), plain: float32, ``highest``
matmul precision, a dense ``[S, S]`` mask built from positions (scored a K/V
head at a time, so that 8k positions at the published widths fit), every
expert's output for every token weighed by the route, no kernel, no cache, no
pages, no tiles, no batching. What ``models/smallthinker.py`` (the program:
two page groups, a ring of pages for the window layers, a flash kernel whose k grid
starts at the window, routed pairs through tiles) is held to in the tests,
written from the published ``config.json`` (``model_name``
``smallthinker_21b_instruct``), its catalog row's description ("SWA(4096);
NoPE global", "sparse ReGLU; router placed before attention") and the
family's published modelling code. The serving benchmark keeps a copy of its
own that imports nothing from the program
(``benchmarks/serving/families/smallthinker.py``);
``tests/bench_serving/test_smallthinker_cell.py`` holds the two equal.

With ``x`` the residual stream and ``RMS(x) = w * x / sqrt(mean(x^2) + eps)``:

- ``x = E[token]``; layer ``l``: ``r = W_r x`` (the router reads the layer's
  input as it enters, **before** ``RMS_in``); ``h = x + Attn_l(RMS_in(x))``;
  ``y = h + MoE(RMS_post(h); r)``; after the last layer ``logits = RMS_out(x)
  W_head``, a head of its own.
- attention: bias-free ``q, k, v`` to ``n_heads`` / ``n_kv_heads`` heads of
  ``head_dim``; no q/k norm; where ``rope_layout[l] == 1`` rotary embedding
  over the whole head (half-split rotation: ``[x1, x2] -> [x1 cos - x2 sin,
  x2 cos + x1 sin]``, frequencies ``theta^(-2i / head)``, no scaling), where
  0 nothing: the layer is position-free. Scores ``q . k / sqrt(head)``,
  softmax in float32, query head ``i`` reads K/V head ``i // group``. Mask:
  causal, and where ``sliding_window_layout[l] == 1`` also ``t - s <
  window`` (a query sees itself and the ``window - 1`` before it: the
  ``transformers`` convention). Bias-free output projection.
- routed layer, every layer: the ``top_k`` largest of ``r`` (a tie to the
  lower index), ``w = softmax`` over those logits (``norm_topk_prob`` then
  changes nothing), ``sum_e w_e W_down,e (relu(W_gate,e z) * W_up,e z)`` with
  ``z = RMS_post(h)``: ReGLU, no bias, no shared expert, nothing dropped.

Assumed, where the row's keys do not settle it: the router's input (the
un-normed layer input), the window's convention, no "secondary" experts (the
row has a key for none), ReGLU (from the description).

``control`` (tests and the benchmark's controls): ``"no-window"`` lets the
window layers attend to everything, ``"rope-everywhere"`` rotates the
position-free layers too; a program that forgets the window, rotates a global
layer or reads a recycled page lands as far off as these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CONTROLS = ("no-window", "rope-everywhere")


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, theta: float):
    """x: [S, heads, hd] at positions 0..S-1, the half-split rotation."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(layer, u, cfg, *, window: int | None, rotate: bool):
    """u: [S, D] (normed) -> [S, D]: dense masked GQA."""
    S = u.shape[0]
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ layer["wq"]).reshape(S, Hq, hd)
    k = (u @ layer["wk"]).reshape(S, Hkv, hd)
    v = (u @ layer["wv"]).reshape(S, Hkv, hd)
    if rotate:
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    q = q.reshape(S, Hkv, Hq // Hkv, hd)
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = s <= t
    if window is not None:
        seen = seen & (t - s < window)

    def group(args):  # a K/V head and its query heads at a time: 8k positions fit
        qg, kg, vg = args  # [S, G, hd], [S, hd], [S, hd]
        scores = jnp.einsum("sgd,td->gst", qg, kg) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", probs, vg)

    o = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(S, Hq * hd) @ layer["wo"]


def routed(layer, z, logits, cfg):
    """z: [S, D], logits [S, E] -> every expert's ReGLU for every token, an
    expert at a time, weighed by a softmax over the chosen logits (zero off
    the chosen)."""
    top, ids = jax.lax.top_k(logits, cfg.top_k_experts)  # a tie: the lower index
    w = jax.nn.softmax(top, axis=-1)
    weights = jnp.zeros_like(logits).at[jnp.arange(z.shape[0])[:, None], ids].set(w)
    out = jnp.zeros_like(z)
    for e in range(cfg.n_experts):
        y = (jax.nn.relu(z @ layer["moe_gate"][e]) * (z @ layer["moe_up"][e])) @ layer["moe_down"][e]
        out = out + weights[:, e:e + 1] * y
    return out


def forward(params: dict, tokens, cfg, *, control: str | None = None):
    """tokens [S] -> logits [S, vocab] in float32."""
    if control not in (None, *CONTROLS):
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        for l in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[l].astype(jnp.float32), params["layers"])
            logits = x @ layer["router"]
            windowed = bool(cfg.window_layout[l]) and control != "no-window"
            rotate = bool(cfg.rope_layout[l]) or control == "rope-everywhere"
            h = x + attention(
                layer, rms_norm(x, layer["attn_norm"], cfg.norm_eps), cfg,
                window=cfg.sliding_window if windowed else None, rotate=rotate,
            )
            x = h + routed(layer, rms_norm(h, layer["mlp_norm"], cfg.norm_eps), logits, cfg)
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)
