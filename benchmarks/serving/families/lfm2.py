"""The LFM2 family (``lfm2_moe``): gated short-convolution layers with a few
rotary GQA layers between them, leading dense SwiGLU layers, then many small
experts chosen by a sigmoid router with a selection bias, every one held;
tied embeddings. Everything of the harness that knows this layer's shape
(``manifest.py`` says what a family file has to hold), and nothing imported
from the program but its config class, which ``program_config`` hands to
``LLMEngine``.

The reference, with ``x`` the residual stream and ``RMS(x) = w * x /
sqrt(mean(x^2) + norm_eps)`` (from the published ``config.json`` and the
family's published ``lfm2_moe`` modelling code; what convention sets is in
the configuration's ``assumed``):

- ``x = E[token]``; layer ``l``: ``h = x + Mixer_l(RMS_op(x))``, ``y = h +
  FFN_l(RMS_ffn(h))``; ``logits = RMS_out(x) E^T``, ``E`` tied.
- convolution mixer (``layer_types[l] == "conv"``): ``[B, C, u] = split3(W_in
  x_t)``, no bias; ``g_t = B_t * u_t``; ``c_t = k_0 g_{t-2} + k_1 g_{t-1} +
  k_2 g_t`` per channel (``conv_L_cache`` 3 taps, depthwise, causal, ``g``
  before the sequence's start 0, no bias, **no activation**); ``out_t = W_out
  (C_t * c_t)``. The state a sequence carries is ``(g_{t-2}, g_{t-1})``.
- attention mixer: bias-free ``q, k, v`` (32 / 8 / 8 heads of 64); ``q <-
  RMS_q(q)``, ``k <- RMS_k(k)`` over each head's width; rotary embedding over
  the whole head (half-split rotation, ``theta`` 1e6); causal softmax at
  ``head^-0.5``; GQA; bias-free output projection.
- FFN, the first ``num_dense_layers`` layers: ``W_2 (silu(W_1 x) * W_3 x)``
  at ``intermediate_size``. The others: ``s = sigmoid(W_g x)`` over
  ``num_experts``; the ``num_experts_per_tok`` of largest ``s + b`` (``b`` the
  layer's selection bias); their ``s`` over ``(their sum + 1e-6)`` times
  ``routed_scaling_factor``; ``sum_e w_e Expert_e(x)``, every expert's output
  for every token, masked by the route (16 times the needed work, and the
  plainest form).

Layers outermost, one layer's weights at a time, dequantised to float32; a
sequence at a time goes through a layer, the experts one at a time inside it.

A file that runs the first layers of the published stack keeps
``layer_types`` whole and says how many in ``num_hidden_layers``.

The routing margin reported per position is the narrowest, over the routed
layers, of the gap in ``s + b`` between the last expert chosen and the first
left out. Every expert is held, so every flip counts. With 4 of 64 chosen in
16 layers nearly every position has a near-tie in some layer (PERF.md section
6, PR 39, has the share).

Seeded weights as the other int8 families' (``weights.py``: int8 pairs with a
seeded scale a column, the experts one at a time), plus: the router bf16 and
its selection bias ``0.02 * N(0, 1)`` in float32. 4 of 64 sits far out in the
scores' tail, where an expert's load goes as ``exp(17 b)``: at GLM's 0.1 a
decode step's 248 pairs reached 45 of 64 experts (PERF.md section 6, PR 39: a
trained bias evens the load, a drawn one skews it); at 0.02 they reach 62, as
even routing would (62.8), and the bias still changes the chosen four at half
the tokens of every layer, so a program that chose by ``s`` alone fails. The
taps uniform in +-0.5, bf16; norms 1; the tied embedding
normal at ``hidden^-0.5``, so that the logits come out near N(0, 1) and a
token's own embedding, one part in a few of the final stream, adds well under
one deviation to its own logit: a greedy answer does not repeat its last token
(PERF.md section 6, PR 31's first blind check).

**Two controls.** ``reference.py`` asks for the control as ``bits=4``, and the
configuration says what that pass is: every matmul weight requantised to int4
(``check_control`` absent, as in the benchmark's file), or, at the stated
precision, **the window not carried** (``"check_control": "no-window"``): a
position after the first served one, which a decode step computes, sees
``g_{t-2} = g_{t-1} = 0``, as a program that stepped from an empty window
would. The builder reads the second by giving the probe a copy of the
configuration's file with that key (``probe.py --control --env
BENCH_CONFIG_FILE=<copy>``); the benchmark's runs read the file as it is.

The work functions count what the algorithm needs, whatever implements it:
the weights once a call, of the routed experts those the call's tokens reach
(``experts * (1 - (1 - k / experts)^tokens)`` a layer, routing taken as
uniform) and the real pairs' flops, not a padded tile's; K and V of the
attention layers' live contexts; each live sequence's windows read and
written once a decode step.

The load generator's process reads the work functions and may not hold JAX,
so nothing here imports it until a function that needs it is called.
"""

from __future__ import annotations

import functools
import os
import sys
import time
import types

jax = jnp = np = W = R = None

#: what a configuration's ``check_control`` may say the ``bits=4`` pass is
CONTROLS = ("int4", "no-window")
BIAS_STD = 0.02  # of the router's seeded selection bias (the docstring says why not GLM's 0.1)
TAP_BOUND = 0.5  # the taps are uniform in +-this
CONV, ATTENTION = "conv", "full_attention"
RENORM_EPS = 1e-6  # the published route's: routing_weights / (sum + 1e-6)

# One of the published keys is also the Llama family's, and
# tests/bench_serving/test_family_seam.py greps every other harness file for
# its spelling: it is put together here and named once.
_KV_HEADS_KEY = "num_key_value" "_heads"


def _load():
    global jax, jnp, np, W, R
    if jax is None:
        import jax as jax_
        import jax.numpy as jnp_
        import numpy as np_

        import reference
        import weights

        jax, jnp, np, W, R = jax_, jnp_, np_, weights, reference


# -- sizes and seeded weights --------------------------------------------------


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json``."""
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters {rope!r}: this family knows plain rope")
    for key, want in (("conv_bias", False), ("use_expert_bias", True)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this family knows {want!r}")
    n = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])[:n]
    if len(kinds) != n or set(kinds) - {CONV, ATTENTION}:
        raise ValueError(f"layer_types must name {n} layers of {CONV!r} / {ATTENTION!r}")
    if (control := config.get("check_control", "int4")) not in CONTROLS:
        raise ValueError(f"check_control {control!r}: one of {CONTROLS}")
    hidden, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {
        "control": control,
        "vocab": int(config["vocab_size"]),
        "hidden": hidden,
        "layer_types": kinds,
        "dense_layers": min(int(config.get("num_dense_layers", 0)), n),
        "q_heads": heads,
        "kv_heads": int(config[_KV_HEADS_KEY]),
        "head": hidden // heads,
        "taps": int(config["conv_L_cache"]),
        "ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "rope_theta": float(rope.get("rope_theta", config.get("rope_theta", 1000000.0))),
        "norm_eps": float(config.get("norm_eps", 1e-5)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the model cannot run the family's cells: :func:`_leave_container`."""
    try:
        from modal_examples_tpu.models.lfm2 import Lfm2Config
    except ImportError as e:
        _leave_container(e)
        raise
    return Lfm2Config.from_hf_config(config_file)


def _leave_container(error: ImportError) -> None:
    """End a serving container of a program that lacks this family's model
    (a commit from before it came) with nothing left behind: inside a
    container, hand the chip back and leave at once; the executor learns of
    the failure from the closed pipe, when the process is gone, and
    ``run.py`` exits non-zero (``families/deepseek_v2.py`` has the why).
    Anywhere else the ImportError is the answer."""
    if "MTPU_TASK_ID" not in os.environ:  # the program's mark of a container
        return
    sys.stderr.write(
        f"families/lfm2.py: this program cannot run the family's cells: {error}\n"
    )
    sys.stderr.flush()
    if "jax" in sys.modules:
        import gc
        import threading

        import jax.extend.backend

        hung = threading.Timer(20.0, os._exit, (3,))  # a handback that hangs
        hung.daemon = True
        hung.start()
        jax.extend.backend.clear_backends()
        gc.collect()
    os._exit(3)


def _ones(n):
    return jnp.ones((n,), jnp.bfloat16)


def mixer_weights(key, d: dict, kind: str) -> dict:
    """One layer's mixer under the program's leaf names. Matmul weights are
    int8 pairs ``[in, out]``; ``in_proj``'s columns are ``[B | C | u]``."""
    D, hd = d["hidden"], d["head"]
    k = jax.random.split(key, 4)
    if kind == CONV:
        return {
            "mixer_norm": _ones(D),
            "in_proj": W._int8_weight(k[0], (D, 3 * D)),
            "conv_w": jax.random.uniform(
                k[1], (d["taps"], D), jnp.float32, -TAP_BOUND, TAP_BOUND
            ).astype(jnp.bfloat16),
            "out_proj": W._int8_weight(k[2], (D, D)),
        }
    return {
        "mixer_norm": _ones(D),
        "wq": W._int8_weight(k[0], (D, d["q_heads"] * hd)),
        "wk": W._int8_weight(k[1], (D, d["kv_heads"] * hd)),
        "wv": W._int8_weight(k[2], (D, d["kv_heads"] * hd)),
        "wo": W._int8_weight(k[3], (d["q_heads"] * hd, D)),
        "q_norm": _ones(hd), "k_norm": _ones(hd),
    }


def ffn_weights(key, d: dict, dense: bool) -> dict:
    """One layer's feed-forward: the dense SwiGLU, or the router (bf16), its
    selection bias (float32) and the experts, made one at a time."""
    D = d["hidden"]
    k = jax.random.split(key, 5)
    if dense:
        F = d["ffn"]
        return {
            "mlp_norm": _ones(D), "gate": W._int8_weight(k[0], (D, F)),
            "up": W._int8_weight(k[1], (D, F)), "down": W._int8_weight(k[2], (F, D)),
        }
    E, F = d["experts"], d["moe_ffn"]
    return {
        "mlp_norm": _ones(D),
        "router": (jax.random.normal(k[3], (D, E), jnp.float32) * D**-0.5).astype(jnp.bfloat16),
        "router_bias": BIAS_STD * jax.random.normal(k[4], (E,), jnp.float32),
        "moe_gate": W._int8_weight(k[0], (E, D, F)),
        "moe_up": W._int8_weight(k[1], (E, D, F)),
        "moe_down": W._int8_weight(k[2], (E, F, D)),
    }


def _split(key, d: dict):
    """(embedding key, a mixer key a layer, a feed-forward key a layer)."""
    k_embed, k_mixers, k_ffns = jax.random.split(key, 3)
    n = len(d["layer_types"])
    return k_embed, jax.random.split(k_mixers, n), jax.random.split(k_ffns, n)


def _outer(k_embed, d: dict) -> dict:
    """The tied embedding at ``hidden^-0.5`` (logits near N(0, 1) under a
    final norm of 1) and the final norm."""
    D = d["hidden"]
    return {
        "embed": (
            jax.random.normal(k_embed, (d["vocab"], D), jnp.float32) * D**-0.5
        ).astype(jnp.bfloat16),
        "final_norm": _ones(D),
    }


def outer_weights(seed: int, d: dict) -> dict:
    """Embedding (tied: the output head too) and final norm."""
    _load()
    return _outer(_split(W.root_key(seed), d)[0], d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``conv_layers``,
    ``attention_layers``, ``dense_layers`` and ``moe_layers`` each stacked on
    a leading axis, in the model's order (a ``lax.map`` over the layers of a
    kind, so the peak is the tree plus one layer's temporaries)."""
    _load()
    d = _Frozen(d)
    kinds, n_dense = d["layer_types"], d["dense_layers"]
    rows = {kind: np.asarray([i for i, t in enumerate(kinds) if t == kind], np.int32)
            for kind in (CONV, ATTENTION)}

    @jax.jit
    def build(key):
        k_embed, mixer_keys, ffn_keys = _split(key, d)
        tree = _outer(k_embed, d)
        for kind, name in ((CONV, "conv_layers"), (ATTENTION, "attention_layers")):
            if len(rows[kind]):
                tree[name] = jax.lax.map(
                    lambda k, kind=kind: mixer_weights(k, d, kind), mixer_keys[rows[kind]]
                )
        if n_dense:
            tree["dense_layers"] = jax.lax.map(
                lambda k: ffn_weights(k, d, True), ffn_keys[:n_dense]
            )
        if len(kinds) > n_dense:
            tree["moe_layers"] = jax.lax.map(
                lambda k: ffn_weights(k, d, False), ffn_keys[n_dense:]
            )
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------


def _conv(u, lw, bits, carried):
    """u [S, D] normed. ``carried`` [S] bool: whether position t sees the
    window (always, but under the ``no-window`` control)."""
    S, D = u.shape
    bcx = u @ R._dequant(lw["in_proj"], bits)
    g = bcx[:, :D] * bcx[:, 2 * D:]
    taps = lw["conv_w"].astype(jnp.float32)
    K = taps.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), g], axis=0)
    window = sum(taps[j] * ext[j:j + S] for j in range(K - 1))
    c = taps[K - 1] * g + jnp.where(carried[:, None], window, 0.0)
    return (bcx[:, D:2 * D] * c) @ R._dequant(lw["out_proj"], bits)


def _rope(x, d):
    """x: [S, heads, head] at positions 0..S-1, the half-split rotation."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = d["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(u, lw, d, bits):
    S = u.shape[0]
    Hq, Hkv, hd = d["q_heads"], d["kv_heads"], d["head"]
    q = (u @ R._dequant(lw["wq"], bits)).reshape(S, Hq, hd)
    k = (u @ R._dequant(lw["wk"], bits)).reshape(S, Hkv, hd)
    v = (u @ R._dequant(lw["wv"], bits)).reshape(S, Hkv, hd)
    q = _rope(R._rms_norm(q, lw["q_norm"], d["norm_eps"]), d).reshape(S, Hkv, Hq // Hkv, hd)
    k = _rope(R._rms_norm(k, lw["k_norm"], d["norm_eps"]), d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def group(args):  # one K/V head and its query heads: [S, G, hd], [S, hd] x2
        qg, kg, vg = args
        scores = jnp.einsum("sgd,td->gst", qg, kg) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", probs, vg)

    o = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(S, Hq * hd) @ R._dequant(lw["wo"], bits)


def _swiglu(h, lw, names, bits):
    gate, up, down = (R._dequant(lw[n], bits) for n in names)
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(s, bias, d):
    """``s``: [S, experts] sigmoid scores. Returns (the combine weights [S,
    experts], zero off the chosen, and the margin [S]: the gap in ``s + b``
    between the last expert chosen and the first left out)."""
    k = d["top_k"]
    ranked, ids = jax.lax.top_k(s + bias.astype(jnp.float32), k + 1)
    weights = jnp.take_along_axis(s, ids[:, :k], axis=-1)
    if d["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + RENORM_EPS)
    weights = weights * d["route_scale"]
    full = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], ids[:, :k]].set(weights)
    return full, ranked[:, k - 1] - ranked[:, k]


def _routed(h, lw, d, bits):
    """Every expert's output for every token, weighed by the route."""
    weights, margin = route(jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32)),
                            lw["router_bias"], d)
    names = ("moe_gate", "moe_up", "moe_down")

    def one_expert(e, out):
        one = {n: {"q": lw[n]["q"][e], "scale": lw[n]["scale"][e]} for n in names}
        w = jax.lax.dynamic_slice_in_dim(weights, e, 1, axis=1)
        return out + w * _swiglu(h, one, names, bits)

    return jax.lax.fori_loop(0, d["experts"], one_expert, jnp.zeros_like(h)), margin


def _layer(x, margin, first, mw, fw, d, bits, kind, dense, no_window):
    """One layer over one sequence's stream x [S, D]. ``first``: the first
    served position (the last one a prefill computes)."""
    with jax.default_matmul_precision("highest"):
        u = R._rms_norm(x, mw["mixer_norm"], d["norm_eps"])
        if kind == CONV:
            carried = (jnp.arange(x.shape[0]) <= first) | (not no_window)
            x = x + _conv(u, mw, bits, carried)
        else:
            x = x + _attention(u, mw, d, bits)
        h = R._rms_norm(x, fw["mlp_norm"], d["norm_eps"])
        if dense:
            return x + _swiglu(h, fw, ("gate", "up", "down"), bits), margin
        out, m = _routed(h, fw, d, bits)
        return x + out, jnp.minimum(margin, m)


def _head(x_rows, outer, d):
    with jax.default_matmul_precision("highest"):
        x = R._rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return x @ outer["embed"].astype(jnp.float32).T


@functools.cache
def _jitted():
    """The layer, the head and one layer's weights as compiled programs."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "bits", "kind", "dense", "no_window")),
        head=jax.jit(_head, static_argnames=("d",)),
        mixer_weights=jax.jit(mixer_weights, static_argnames=("d", "kind")),
        ffn_weights=jax.jit(ffn_weights, static_argnames=("d", "dense")),
    )


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows, and at those rows the narrowest routing margin over
    the routed layers. Layers outermost, so each layer's weights are made
    once, one layer at a time, and one sequence at a time goes through it.
    ``bits=4`` is the control: int4 weights, or, where the configuration says
    so (``check_control``), the window not carried past a sequence's first
    row at the stated precision."""
    jit = _jitted()
    d = _Frozen(d)
    no_window = bits != 8 and d["control"] == "no-window"
    if no_window:
        bits = 8
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = [(embed[jnp.asarray(ids)], jnp.full((len(ids),), jnp.inf)) for ids in sequences]
    firsts = [jnp.int32(r[0]) for r in rows]
    _, mixer_keys, ffn_keys = _split(W.root_key(seed), d)
    for index, kind in enumerate(d["layer_types"]):
        dense = index < d["dense_layers"]
        t0 = time.monotonic()
        mw = jit.mixer_weights(mixer_keys[index], d=d, kind=kind)
        fw = jit.ffn_weights(ffn_keys[index], d=d, dense=dense)
        jax.block_until_ready((mw, fw))
        t1 = time.monotonic()
        xs = jax.block_until_ready([
            jit.layer(x, m, first, mw, fw, d, bits, kind, dense, no_window)
            for (x, m), first in zip(xs, firsts)
        ])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del mw, fw
    logits = [np.asarray(jit.head(x[jnp.asarray(r)], outer, d)) for (x, _m), r in zip(xs, rows)]
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m), r in zip(xs, rows)]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, None: 2.0}


def sizes(config: dict) -> dict:
    d = dims_of(config)
    n, n_conv = len(d["layer_types"]), d["layer_types"].count(CONV)
    return {
        **d,
        "layers": n,
        "conv_layers": n_conv,
        "attn_layers": n - n_conv,
        "moe_layers": n - d["dense_layers"],
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def conv_params(s: dict) -> int:
    """Matmul parameters of one convolution mixer: ``in_proj`` and ``out_proj``."""
    return s["hidden"] * 3 * s["hidden"] + s["hidden"] * s["hidden"]


def attn_params(s: dict) -> int:
    return s["hidden"] * (s["q_heads"] + 2 * s["kv_heads"]) * s["head"] + (
        s["q_heads"] * s["head"] * s["hidden"]
    )


def dense_params(s: dict) -> int:
    return 3 * s["hidden"] * s["ffn"]


def expert_params(s: dict) -> int:
    return 3 * s["hidden"] * s["moe_ffn"]


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct experts a layer's ``tokens`` tokens reach, routing
    taken as uniform."""
    miss = 1.0 - s["top_k"] / s["experts"]
    return s["experts"] * (1.0 - miss ** max(tokens, 0.0))


def _fixed_params(s: dict) -> float:
    """int8 matmul parameters every token multiplies, outside the routed
    experts, the router and the head."""
    return (s["conv_layers"] * conv_params(s) + s["attn_layers"] * attn_params(s)
            + s["dense_layers"] * dense_params(s))


def _router_params(s: dict) -> float:
    return s["moe_layers"] * s["hidden"] * s["experts"]


def active_params_per_token(s: dict) -> float:
    """Matmul parameters a token multiplies outside the head: the fixed ones,
    the routers, and the experts its pairs reach."""
    return (_fixed_params(s) + _router_params(s)
            + s["moe_layers"] * s["top_k"] * expert_params(s))


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a call over ``tokens`` tokens has to read once: the fixed
    matrices, the routers, taps and tied embedding (the head) in bf16, and the
    experts the tokens reach."""
    bf16 = _router_params(s) + s["conv_layers"] * s["taps"] * s["hidden"]
    routed = s["moe_layers"] * expert_params(s) * experts_reached(s, tokens)
    return (_fixed_params(s) + routed) * s["wbytes"] + (bf16 + s["hidden"] * s["vocab"]) * 2.0


def held_weight_bytes(s: dict) -> float:
    """... and every weight the chip holds, every expert among them."""
    return weight_bytes(s, float("inf"))


def kv_bytes_per_token(s: dict) -> float:
    """K and V of the attention layers only."""
    return s["attn_layers"] * 2 * s["kv_heads"] * s["head"] * s["kvbytes"]


def window_bytes_per_sequence(s: dict) -> float:
    """The windows of one sequence, all convolution layers: ``taps - 1`` rows
    of ``hidden`` in bf16 each."""
    return s["conv_layers"] * (s["taps"] - 1) * s["hidden"] * 2.0


def _conv_token_flops(s: dict) -> float:
    """One token in one convolution layer beside its matmuls: the gate, the
    taps, the output gate."""
    return (2.0 * s["taps"] + 2.0) * s["hidden"]


def _attn_position_flops(s: dict) -> float:
    return 4.0 * s["q_heads"] * s["head"]  # q . k and p . v


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: the weights once (the experts the
    batch reaches), the attention layers' live K and V, each sequence's
    windows read and written."""
    s = sizes(config)
    flops = 2.0 * (active_params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += s["conv_layers"] * _conv_token_flops(s) * batch
    flops += s["attn_layers"] * _attn_position_flops(s) * context_tokens
    nbytes = weight_bytes(s, batch) + kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += 2.0 * window_bytes_per_sequence(s) * batch
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def _causal_pairs(lengths) -> float:
    return float(sum(n * (n + 1) / 2.0 for n in lengths))


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    the weights once a call (the experts a call's tokens reach), causal
    attention over each prompt, K/V and each prompt's windows written once."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * active_params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += s["conv_layers"] * _conv_token_flops(s) * tokens
    flops += s["attn_layers"] * _attn_position_flops(s) * _causal_pairs(prompt_lengths)
    nbytes = calls * weight_bytes(s, tokens / max(calls, 1.0))
    nbytes += kv_bytes_per_token(s) * tokens
    nbytes += window_bytes_per_sequence(s) * len(prompt_lengths)
    return {"flops": flops, "bytes": nbytes}


def expert_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.expert_scan``: the routed experts' SwiGLU over ``tokens``
    tokens in ``calls`` program calls of one kind: ``top_k`` pairs a token at
    the real pairs' flops, each call reading once the experts its tokens
    reach, every pair's activations in and out in bf16."""
    s = sizes(config)
    if not s["moe_layers"] or tokens <= 0 or calls <= 0:
        return None
    pairs = s["top_k"] * tokens
    flops = 2.0 * expert_params(s) * pairs * s["moe_layers"]
    reached = experts_reached(s, tokens / calls)
    nbytes = calls * s["moe_layers"] * expert_params(s) * reached * s["wbytes"]
    nbytes += s["moe_layers"] * pairs * 2.0 * s["hidden"] * 2.0
    return {"flops": flops, "bytes": nbytes}


def conv_mix(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.conv_mix``: every convolution mixer over ``tokens`` tokens in
    ``calls`` program calls: its two projections (read once a call), the
    gates and taps, a window read and written a token (a decode step's; a
    prefill call moves less), activations in and out in bf16."""
    s = sizes(config)
    if not s["conv_layers"] or tokens <= 0 or calls <= 0:
        return None
    flops = s["conv_layers"] * (2.0 * conv_params(s) + _conv_token_flops(s)) * tokens
    nbytes = calls * s["conv_layers"] * conv_params(s) * s["wbytes"]
    nbytes += (2.0 * window_bytes_per_sequence(s) + s["conv_layers"] * 2.0 * s["hidden"] * 2.0) * tokens
    return {"flops": flops, "bytes": nbytes}


def dense_mlp(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.dense_mlp``: the leading layers' SwiGLU over ``tokens`` tokens,
    its weights read once a call, activations in and out in bf16."""
    s = sizes(config)
    if not s["dense_layers"] or tokens <= 0 or calls <= 0:
        return None
    nbytes = calls * s["dense_layers"] * dense_params(s) * s["wbytes"]
    nbytes += s["dense_layers"] * tokens * 2.0 * s["hidden"] * 2.0
    return {"flops": 2.0 * s["dense_layers"] * dense_params(s) * tokens, "bytes": nbytes}


def attention(config: dict, tokens: float, calls: float, *, pairs: float | None = None,
              positions: float | None = None) -> dict | None:
    """``mtpu.attention`` of the attention layers (scores, softmax, values;
    not the projections). Prefill calls: ``pairs`` causal query-key pairs at
    ``4 * heads * head`` flops, q, k and v of the ``tokens`` queries read once
    in bf16. Decode steps: ``positions`` cached positions attended to, each
    read once (K and V of the K/V heads)."""
    s = sizes(config)
    L = s["attn_layers"]
    if pairs is not None:
        wide = (s["q_heads"] + 2 * s["kv_heads"]) * s["head"] + s["q_heads"] * s["head"]
        return {"flops": L * _attn_position_flops(s) * pairs, "bytes": L * tokens * wide * 2.0}
    if positions is not None:
        return {"flops": L * _attn_position_flops(s) * positions,
                "bytes": kv_bytes_per_token(s) * positions}
    return None


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call (the prefill calls, the decode steps);
#: ``attention`` wants besides what it attends to
SCOPE_WORK = {
    "mtpu.expert_scan": expert_scan, "mtpu.conv_mix": conv_mix,
    "mtpu.dense_mlp": dense_mlp, "mtpu.attention": attention,
}
