"""Granite-4.0-H's forward pass, plain: float32, ``highest`` matmul precision,
the Mamba-2 recurrence as a ``lax.scan`` over tokens (the sequential form: the
program computes the chunked one, so the two forms check each other), dense
causal attention, no kernel, no cache, no batching. What
``models/granite_hybrid.py`` (the program: per-slot recurrent state beside a
paged cache, a chunked scan for prefill, a state step for decode) is held to
in the tests, written from the published ``config.json`` (``model_type``
``granitemoehybrid``) and ``modeling_granitemoehybrid``. The serving benchmark
keeps a copy of its own that imports nothing from the program
(``benchmarks/serving/families/granite_hybrid.py``);
``tests/bench_serving/test_granite_hybrid_cell.py`` holds the two equal.

With ``x`` the residual stream:

- ``x = E[token] * embedding_multiplier``; ``logits = (RMSNorm(x) E^T) /
  logits_scaling``, ``E`` tied.
- every layer: ``x += residual_multiplier * Mixer(RMSNorm(x))``, then
  ``x += residual_multiplier * MLP(RMSNorm(x))``, ``MLP(u) = W_out (silu(g) *
  v)`` with ``[g | v] = W_in u`` (the tree keeps ``W_in``'s halves as ``gate``
  and ``up``, and the mixer's ``in_proj`` as ``in_z``, ``in_xbc``, ``in_dt``).
- a routed model (``num_local_experts`` > 0, Granite-4.0-H-Small's 72): that
  MLP is the **shared expert** (width ``shared_intermediate_size``) and the
  second half is ``x += residual_multiplier * (Shared(u) + Routed(u))``,
  ``Routed(u) = sum_{e in top_k} p_e W_out^e (silu(g_e) * v_e)``, ``[g_e |
  v_e] = W_in^e u`` (width ``intermediate_size``), ``l = W_r u`` over all the
  experts, ``top_k`` the ``num_experts_per_tok`` largest ``l`` (a tie to the
  lower id), ``p = softmax(l[top_k])``: top-k of the logits, then a softmax
  over the chosen. Every held expert's product is written out for every
  token and weighed by ``p`` or by zero: no sort, no tiles. ``held=(first,
  count)``: the tree's expert matrices are experts ``first .. first + count -
  1`` of a router that is wider (one chip's share of an expert-parallel
  layer); what the absent experts would add is left out, as the program
  leaves it out.
- attention mixer: causal softmax over ``q . k * attention_multiplier``, GQA,
  no bias, no rotary embedding (``position_embedding_type`` ``nope``).
- Mamba-2 mixer: ``[z | xBC | dt] = W_in u``; ``xBC_t = silu(b + sum_j w_j
  xBC_{t-3+j})`` (depthwise, causal, ``d_conv`` 4); ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t (x_t outer B_t)``; ``y_t = h_t C_t + D x_t``;
  ``out = W_out (RMSNorm(y * silu(z)) * w_norm)``, the norm over all of
  ``d_inner`` for ``n_groups`` 1 (over each group's share otherwise).

Departures from the published code: none in the mathematics.
``time_step_limit`` is ``(0, inf)`` there, a no-op clamp, and is left out;
the published routed layer gathers each expert's tokens
(``GraniteMoeHybridParallelExperts`` over ``index_sorted_experts``) where
this one multiplies every token by every held expert and weighs the unchosen
by zero: the same sum; ``block_sparse_moe.input_linear`` is kept as its
halves (``moe_gate``, ``moe_up``), as ``shared_mlp.input_linear`` is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def gated_rms_norm(y, z, weight, eps, n_groups: int):
    """``RMSNorm(y * silu(z)) * weight``, the mean square taken over each of
    ``n_groups`` equal shares of the last axis."""
    y = y * jax.nn.silu(z)
    grouped = y.reshape(*y.shape[:-1], n_groups, -1)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    return (grouped * jax.lax.rsqrt(var + eps)).reshape(y.shape) * weight


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def mamba_mixer(layer, u, cfg, h0=None, tail0=None):
    """u: [S, D] (normed) -> (out [S, D], final state [H, P, N], the last
    ``d_conv - 1`` rows of xBC [d_conv - 1, conv_dim]). ``h0`` / ``tail0``:
    the state and convolution tail to start from (zeros)."""
    S = u.shape[0]
    H, P, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    di, K = cfg.d_inner, cfg.mamba_d_conv
    # the tree keeps in_proj's three column blocks [z | xBC | dt] a leaf each
    z, xbc, dt = u @ layer["in_z"], u @ layer["in_xbc"], u @ layer["in_dt"]
    if tail0 is None:
        tail0 = jnp.zeros((K - 1, cfg.conv_dim), jnp.float32)
    ext = jnp.concatenate([tail0, xbc], axis=0)  # [S + K - 1, conv_dim]
    conv = sum(layer["conv_w"][j] * ext[j:j + S] for j in range(K)) + layer["conv_b"]
    conv = jax.nn.silu(conv)
    x = conv[:, :di].reshape(S, H, P)
    B = jnp.repeat(conv[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)  # [S, H, N]
    C = jnp.repeat(conv[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])  # [S, H]
    A = -jnp.exp(layer["A_log"])  # [H]

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t) + layer["D"][:, None] * x_t

    if h0 is None:
        h0 = jnp.zeros((H, P, N), jnp.float32)
    h, y = jax.lax.scan(step, h0, (x, B, C, dt))
    y = gated_rms_norm(y.reshape(S, di), z, layer["gate_norm"], cfg.norm_eps, G)
    return y @ layer["out_proj"], h, ext[S:]


def attention_mixer(layer, u, cfg):
    """u: [S, D] (normed) -> [S, D]: dense causal GQA, no positions."""
    S = u.shape[0]
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ layer["wq"]).reshape(S, Hkv, Hq // Hkv, hd)
    k = (u @ layer["wk"]).reshape(S, Hkv, hd)
    v = (u @ layer["wv"]).reshape(S, Hkv, hd)
    scores = jnp.einsum("shgd,thd->hgst", q, k) * cfg.attention_multiplier
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgst,thd->shgd", probs, v).reshape(S, Hq * hd)
    return o @ layer["wo"]


def mlp(layer, u):
    return (jax.nn.silu(u @ layer["gate"]) * (u @ layer["up"])) @ layer["down"]


def route(logits, top_k: int):
    """``logits`` [S, experts] -> (ids [S, k], weights [S, k]): the ``top_k``
    largest logits (a tie to the lower id), a softmax over those."""
    top, ids = jax.lax.top_k(logits, top_k)
    return ids, jax.nn.softmax(top, axis=-1)


def routed(moe, u, top_k: int, held=None):
    """The routed experts over u [S, D] (normed). ``moe``: one layer's
    ``router`` [D, experts] and ``moe_gate`` / ``moe_up`` [E, D, F],
    ``moe_down`` [E, F, D], the matrices of experts ``held = (first, count)``
    of the router's width (unset: all of it, from 0)."""
    n = moe["router"].shape[-1]
    first, count = held or (0, moe["moe_gate"].shape[0])
    ids, p = route(u @ moe["router"], top_k)
    combine = (jax.nn.one_hot(ids, n, dtype=u.dtype) * p[..., None]).sum(axis=1)  # [S, experts]
    out = jnp.zeros_like(u)
    for j in range(count):  # every held expert's product, written out
        y = (jax.nn.silu(u @ moe["moe_gate"][j]) * (u @ moe["moe_up"][j])) @ moe["moe_down"][j]
        out = out + combine[:, first + j, None] * y
    return out


def layer_at(params: dict, cfg, index: int) -> tuple[str, dict]:
    """(kind, the float32 weights of layer ``index``) out of the program's
    tree, which stacks the layers of a kind."""
    kind = cfg.layer_types[index]
    row = sum(1 for t in cfg.layer_types[:index] if t == kind)
    return kind, _f32(jax.tree.map(lambda a: a[row], params[f"{kind}_layers"]))


def moe_at(params: dict, index: int) -> dict | None:
    """The float32 router and expert matrices of layer ``index`` (the
    program's tree stacks them by layer), or None for a dense model."""
    if "moe_layers" not in params:
        return None
    return _f32(jax.tree.map(lambda a: a[index], params["moe_layers"]))


def forward(params: dict, tokens, cfg, *, top_k: int | None = None, shared: bool = True):
    """tokens [S] -> logits [S, vocab] in float32. A routed model's tree
    holds experts ``cfg.expert_offset .. + cfg.held_experts - 1``. ``top_k``
    other than the configuration's and ``shared=False`` (the shared expert
    left out) are what the serving benchmark's controls compute."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        x = embed[tokens] * cfg.embedding_multiplier
        for index in range(cfg.n_layers):
            kind, layer = layer_at(params, cfg, index)
            u = rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
            if kind == "mamba":
                mixed, _, _ = mamba_mixer(layer, u, cfg)
            else:
                mixed = attention_mixer(layer, u, cfg)
            x = x + cfg.residual_multiplier * mixed
            u = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            out = mlp(layer, u) if shared else jnp.zeros_like(u)
            moe = moe_at(params, index)
            if moe is not None:
                held = (cfg.expert_offset, cfg.held_experts)
                out = out + routed(moe, u, top_k or cfg.top_k, held)
            x = x + cfg.residual_multiplier * out
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return (x @ embed.T) / cfg.logits_scaling
