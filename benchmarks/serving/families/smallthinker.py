"""The SmallThinker family (``smallthinker``, SmallThinker-21BA3B-Instruct):
sliding-window rotary layers and global position-free layers in one model, a
router that reads the layer's input before attention, many small ReGLU
experts, every one held; a head of its own. Everything of the harness that
knows this layer's shape (``manifest.py`` says what a family file has to
hold), and nothing imported from the program but its config class, which
``program_config`` hands to ``LLMEngine``.

The reference, with ``x`` the residual stream and ``RMS(x) = w * x /
sqrt(mean(x^2) + rms_norm_eps)`` (from the published ``config.json``, its
catalog row's description, "SWA(4096); NoPE global", "sparse ReGLU; router
placed before attention", and the family's published modelling code; what
the keys do not settle is in the configuration's ``assumed``):

- ``x = E[token]``; layer ``l``: ``r = W_r x`` in float32: **the router reads
  the layer's input as it enters, before ``RMS_in``**; ``h = x +
  Attn_l(RMS_in(x))``; ``y = h + MoE(RMS_post(h); r)``; ``logits = RMS_out(x)
  W_head``, the head untied.
- attention: bias-free ``q, k, v`` (28 / 4 / 4 heads of 128), no q/k norm;
  where ``rope_layout[l] == 1`` rotary embedding over the whole head
  (half-split rotation, ``theta`` 1.5e6, no scaling), where 0 nothing: the
  layer is position-free; scores ``q . k / sqrt(128)``, float32 softmax,
  query head ``i`` reads K/V head ``i // 7``; mask: causal, and where
  ``sliding_window_layout[l] == 1`` also ``t - s < sliding_window_size`` (a
  query sees itself and the 4095 before it); bias-free output projection. A
  dense mask built from positions, a K/V head and a block of 1024 queries at
  a time, so that 8k positions fit.
- routed layer, every layer: the 6 largest of ``r`` (a tie to the lower
  index), ``w`` a softmax over those six logits, ``sum_e w_e W_down,e
  (relu(W_gate,e z) * W_up,e z)``, ``z = RMS_post(h)``: ReGLU at width 768,
  no bias, no shared expert, nothing dropped. Only the chosen experts' rows
  are computed: the (token, expert) pairs sorted by expert into tiles of 256
  rows, a tile at a time against its expert's dequantised matrices (the
  sums are the plain form's; every expert for every token would be ten times
  the work at 8k tokens).

Layers outermost, one layer's weights at a time, dequantised to float32; a
sequence at a time goes through a layer. A file that runs the first layers
of the published stack keeps the two layouts whole and says how many in
``num_hidden_layers``.

The routing margin reported per position is the narrowest, over the layers,
of the gap in softmax probability (over all 64) between the last expert
chosen and the first left out.

Seeded weights as the other int8 families' (``weights.py``: int8 pairs with
a seeded scale a column, the experts one at a time); the router, the
embedding (normal, unit size: the router reads the stream as it is), the
head (normal at ``hidden^-0.5``: logits near N(0, 1)) and the norms (1)
bf16. **``W_q`` and ``W_k`` are drawn ``QK_GAIN`` times the unit scale**, so
that a score is ``QK_GAIN^2`` standard deviations wide and not one: with unit
projections a softmax over 4-8k seeded positions is nearly flat, its output
the mean of thousands of random rows, next to nothing beside the stream, and
a window of 4096 or the whole context would serve the same tokens; at
``QK_GAIN^2 = 3`` a query's weight sits on its few best positions, a third
of which lie beyond the window at this cell's contexts, and the two controls
below land outside the limits (the configuration's ``check_why`` has the
readings).

**Three controls.** ``reference.py`` asks for the control as ``bits=4``, and
the configuration says what that pass is (``check_control``): every matmul
weight requantised to int4 (absent, as in the benchmark's file);
``"no-window"``: the window layers attend to their whole context, as a
program that forgot the window's mask, or read a page the ring had not yet
written over where it should have, would; ``"rope-everywhere"``: the
position-free layers rotate too. The builder reads the second and third by
giving the probe a copy of the configuration's file with that key (``probe.py
--control --env BENCH_CONFIG_FILE=<copy>``); the benchmark's runs read the
file as it is.

The work functions count what the algorithm needs, whatever implements it:
the weights once a call, of the experts those the call's tokens reach
(``experts * (1 - (1 - k / experts)^tokens)`` a layer, routing taken as
uniform) and the real pairs' flops; a window layer attends to ``min(context,
window)`` positions a sequence, a global layer to all of them.

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
CONTROLS = ("int4", "no-window", "rope-everywhere")
QK_GAIN = 3.0**0.5  # of W_q and of W_k over the unit scale (the docstring says why)
TILE = 256  # rows of one tile of the reference's routed layer
Q_BLOCK = 1024  # queries the reference's attention scores at a time

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
    for key, want in (
        ("moe_primary_router_apply_softmax", True), ("norm_topk_prob", True),
        ("tie_word_embeddings", False), ("rope_scaling", None),
    ):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this family knows {want!r}")
    n = int(config["num_hidden_layers"])
    windows = tuple(int(v) for v in config["sliding_window_layout"])[:n]
    ropes = tuple(int(v) for v in config.get("rope_layout", config["sliding_window_layout"]))[:n]
    if len(windows) != n or len(ropes) != n or not set(windows) | set(ropes) <= {0, 1}:
        raise ValueError(f"the two layouts must name {n} layers of 0 / 1")
    if (control := config.get("check_control", "int4")) not in CONTROLS:
        raise ValueError(f"check_control {control!r}: one of {CONTROLS}")
    return {
        "control": control,
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "windows": windows,
        "ropes": ropes,
        "window": int(config["sliding_window_size"]),
        "q_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config[_KV_HEADS_KEY]),
        "head": int(config["head_dim"]),
        "moe_ffn": int(config["moe_ffn_hidden_size"]),
        "experts": int(config["moe_num_primary_experts"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "rope_theta": float(config.get("rope_theta", 1500000.0)),
        "norm_eps": float(config.get("rms_norm_eps", 1e-6)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the model cannot run the family's cells: :func:`_leave_container`."""
    try:
        from modal_examples_tpu.models.smallthinker import SmallThinkerConfig
    except ImportError as e:
        _leave_container(e)
        raise
    return SmallThinkerConfig.from_hf_config(config_file)


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
        f"families/smallthinker.py: this program cannot run the family's cells: {error}\n"
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


def _gained(weight: dict, gain: float) -> dict:
    return {"q": weight["q"], "scale": weight["scale"] * gain}


def layer_weights(key, d: dict) -> dict:
    """One layer under the program's leaf names. Matmul weights are int8
    pairs ``[in, out]`` (``W_q`` and ``W_k`` at ``QK_GAIN``), the experts made
    one at a time; the router and the norms bf16."""
    D, hd, E, F = d["hidden"], d["head"], d["experts"], d["moe_ffn"]
    k = jax.random.split(key, 8)
    return {
        "attn_norm": _ones(D),
        "wq": _gained(W._int8_weight(k[0], (D, d["q_heads"] * hd)), QK_GAIN),
        "wk": _gained(W._int8_weight(k[1], (D, d["kv_heads"] * hd)), QK_GAIN),
        "wv": W._int8_weight(k[2], (D, d["kv_heads"] * hd)),
        "wo": W._int8_weight(k[3], (d["q_heads"] * hd, D)),
        "mlp_norm": _ones(D),
        "router": (jax.random.normal(k[4], (D, E), jnp.float32) * D**-0.5).astype(jnp.bfloat16),
        "moe_gate": W._int8_weight(k[5], (E, D, F)),
        "moe_up": W._int8_weight(k[6], (E, D, F)),
        "moe_down": W._int8_weight(k[7], (E, F, D)),
    }


def _split(key, d: dict):
    """(embedding key, head key, a key a layer)."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_layers, len(d["windows"]))


def _outer(k_embed, k_head, d: dict) -> dict:
    """The embedding at unit size (the first router reads it as it is), the
    head at ``hidden^-0.5`` (logits near N(0, 1) under a final norm of 1) and
    the final norm, all bf16."""
    D, V = d["hidden"], d["vocab"]
    return {
        "embed": jax.random.normal(k_embed, (V, D), jnp.float32).astype(jnp.bfloat16),
        "lm_head": (jax.random.normal(k_head, (D, V), jnp.float32) * D**-0.5).astype(jnp.bfloat16),
        "final_norm": _ones(D),
    }


def outer_weights(seed: int, d: dict) -> dict:
    _load()
    k_embed, k_head, _ = _split(W.root_key(seed), d)
    return _outer(k_embed, k_head, d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``layers`` stacked on a leading
    axis (a ``lax.map`` over the layers, so the peak is the tree plus one
    layer's temporaries)."""
    _load()
    d = _Frozen(d)

    @jax.jit
    def build(key):
        k_embed, k_head, keys = _split(key, d)
        tree = _outer(k_embed, k_head, d)
        tree["layers"] = jax.lax.map(lambda k: layer_weights(k, d), keys)
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------


def _rope(x, d):
    """x: [S, heads, head] at positions 0..S-1, the half-split rotation."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = d["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(u, lw, d, bits, window, rotate):
    """u [S, D] normed -> [S, D]. ``window``: None, or the positions a query
    sees (itself among them)."""
    S = u.shape[0]
    Hq, Hkv, hd = d["q_heads"], d["kv_heads"], d["head"]
    q = (u @ R._dequant(lw["wq"], bits)).reshape(S, Hq, hd)
    k = (u @ R._dequant(lw["wk"], bits)).reshape(S, Hkv, hd)
    v = (u @ R._dequant(lw["wv"], bits)).reshape(S, Hkv, hd)
    if rotate:
        q, k = _rope(q, d), _rope(k, d)
    block = min(Q_BLOCK, S)
    q = q.reshape(S // block, block, Hkv, Hq // Hkv, hd)
    s = jnp.arange(S)[None, :]

    def group(args):  # one K/V head: its query heads [blocks, block, G, hd], k and v [S, hd]
        qg, kg, vg = args

        def rows(args):  # one block of queries against every position
            qb, first = args
            t = first + jnp.arange(block)[:, None]
            seen = s <= t
            if window is not None:
                seen = seen & (t - s < window)
            scores = jnp.einsum("sgd,td->gst", qb, kg) * hd**-0.5
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gst,td->sgd", probs, vg)

        return jax.lax.map(rows, (qg, jnp.arange(S // block) * block))

    o = jax.lax.map(group, (q.transpose(2, 0, 1, 3, 4), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [Hkv, blocks, block, G, hd] -> [S, Hq * hd]
    return o.transpose(1, 2, 0, 3, 4).reshape(S, Hq * hd) @ R._dequant(lw["wo"], bits)


def route(logits, d):
    """``logits`` [S, experts] -> (the chosen ids [S, k], their weights [S,
    k]: a softmax over the chosen logits; the margin [S]: the gap in softmax
    probability over all the experts between the last chosen and the first
    left out)."""
    k = d["top_k"]
    ranked, ids = jax.lax.top_k(logits, k + 1)  # a tie: the lower index
    probs = jax.nn.softmax(logits, axis=-1)
    p = jnp.take_along_axis(probs, ids, axis=-1)
    return ids[:, :k], jax.nn.softmax(ranked[:, :k], axis=-1), p[:, k - 1] - p[:, k]


def _routed(z, logits, lw, d, bits):
    """The chosen experts' ReGLUs over z [S, D], weighed by the route. The
    pairs are sorted by expert and padded to whole tiles of ``TILE`` rows; a
    tile goes through its one expert's matrices."""
    S, D = z.shape
    E, k = d["experts"], d["top_k"]
    ids, w, margin = route(logits, d)
    pair_e = ids.reshape(-1)  # [S * k]
    order = jnp.argsort(pair_e, stable=True)
    counts = jnp.bincount(pair_e, length=E)
    padded = (counts + TILE - 1) // TILE * TILE
    ends = jnp.cumsum(padded)
    e_sorted = pair_e[order]
    rank = jnp.arange(S * k) - (jnp.cumsum(counts) - counts)[e_sorted]
    row = (ends - padded)[e_sorted] + rank  # where each sorted pair sits
    n_rows = -(-(S * k + E * (TILE - 1)) // TILE) * TILE
    row_token = jnp.full((n_rows,), S, jnp.int32).at[row].set((order // k).astype(jnp.int32))
    row_weight = jnp.zeros((n_rows,), jnp.float32).at[row].set(w.reshape(-1)[order])
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_rows // TILE) * TILE, side="right"), E - 1
    )
    z_rows = jnp.concatenate([z, jnp.zeros((1, D), z.dtype)], axis=0)
    names = ("moe_gate", "moe_up", "moe_down")

    def tile(args):
        tokens, weights, e = args
        gate, up, down = (
            R._dequant({"q": lw[n]["q"][e], "scale": lw[n]["scale"][e]}, bits) for n in names
        )
        x = z_rows[tokens]
        return weights[:, None] * ((jax.nn.relu(x @ gate) * (x @ up)) @ down)

    ys = jax.lax.map(
        tile, (row_token.reshape(-1, TILE), row_weight.reshape(-1, TILE), tile_expert)
    )
    out = jnp.zeros((S + 1, D), jnp.float32).at[row_token].add(ys.reshape(n_rows, D))
    return out[:S], margin


def _layer(x, margin, lw, d, bits, windowed, rotate):
    """One layer over one sequence's stream x [S, D]."""
    with jax.default_matmul_precision("highest"):
        logits = x @ lw["router"].astype(jnp.float32)
        u = R._rms_norm(x, lw["attn_norm"], d["norm_eps"])
        h = x + _attention(u, lw, d, bits, d["window"] if windowed else None, rotate)
        out, m = _routed(R._rms_norm(h, lw["mlp_norm"], d["norm_eps"]), logits, lw, d, bits)
        return h + out, jnp.minimum(margin, m)


def _head(x_rows, outer, d):
    with jax.default_matmul_precision("highest"):
        x = R._rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return x @ outer["lm_head"].astype(jnp.float32)


@functools.cache
def _jitted():
    """The layer, the head and one layer's weights as compiled programs."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "bits", "windowed", "rotate")),
        head=jax.jit(_head, static_argnames=("d",)),
        layer_weights=jax.jit(layer_weights, static_argnames=("d",)),
    )


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows, and at those rows the narrowest routing margin over
    the layers. Layers outermost, so each layer's weights are made once, one
    layer at a time, and one sequence at a time goes through it. ``bits=4``
    is the control: int4 weights, or, where the configuration says so
    (``check_control``), at the stated precision, the window layers seeing
    everything or the position-free layers rotating."""
    jit = _jitted()
    d = _Frozen(d)
    control = d["control"] if bits != 8 else None
    if control in ("no-window", "rope-everywhere"):
        bits = 8
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = [(embed[jnp.asarray(ids)], jnp.full((len(ids),), jnp.inf)) for ids in sequences]
    _, _, keys = _split(W.root_key(seed), d)
    for index, (window, rope) in enumerate(zip(d["windows"], d["ropes"])):
        t0 = time.monotonic()
        lw = jax.block_until_ready(jit.layer_weights(keys[index], d=d))
        t1 = time.monotonic()
        windowed = bool(window) and control != "no-window"
        rotate = bool(rope) or control == "rope-everywhere"
        xs = jax.block_until_ready([
            jit.layer(x, m, lw, d, bits, windowed, rotate) for x, m in xs
        ])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw
    logits = [np.asarray(jit.head(x[jnp.asarray(r)], outer, d)) for (x, _m), r in zip(xs, rows)]
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m), r in zip(xs, rows)]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, None: 2.0}


def sizes(config: dict) -> dict:
    d = dims_of(config)
    n, n_window = len(d["windows"]), sum(d["windows"])
    return {
        **d,
        "layers": n,
        "window_layers": n_window,
        "global_layers": n - n_window,
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def attn_params(s: dict) -> int:
    return s["hidden"] * (s["q_heads"] + 2 * s["kv_heads"]) * s["head"] + (
        s["q_heads"] * s["head"] * s["hidden"]
    )


def expert_params(s: dict) -> int:
    return 3 * s["hidden"] * s["moe_ffn"]


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct experts a layer's ``tokens`` tokens reach, routing
    taken as uniform."""
    miss = 1.0 - s["top_k"] / s["experts"]
    return s["experts"] * (1.0 - miss ** max(tokens, 0.0))


def _router_params(s: dict) -> float:
    return s["layers"] * s["hidden"] * s["experts"]


def active_params_per_token(s: dict) -> float:
    """Matmul parameters a token multiplies outside the head."""
    return (s["layers"] * (attn_params(s) + s["top_k"] * expert_params(s))
            + _router_params(s))


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a call over ``tokens`` tokens has to read once: the
    attention projections, the experts the tokens reach, and in bf16 the
    routers and the head."""
    routed = s["layers"] * expert_params(s) * experts_reached(s, tokens)
    return (s["layers"] * attn_params(s) + routed) * s["wbytes"] + (
        _router_params(s) + s["hidden"] * s["vocab"]
    ) * 2.0


def held_weight_bytes(s: dict) -> float:
    """... and every weight the chip holds, every expert among them."""
    return weight_bytes(s, float("inf")) + s["hidden"] * s["vocab"] * 2.0  # and the embedding


def kv_bytes_per_position(s: dict) -> float:
    """K and V of one position in one layer."""
    return 2 * s["kv_heads"] * s["head"] * s["kvbytes"]


def _attn_position_flops(s: dict) -> float:
    return 4.0 * s["q_heads"] * s["head"]  # q . k and p . v


def _seen(s: dict, context: float) -> float:
    """Positions a window layer's query attends to at a context."""
    return min(context, float(s["window"]))


def window_pairs(s: dict, lengths) -> float:
    """Query-key pairs of a window layer over whole prompts: ``min(t + 1,
    window)`` a query."""
    W = s["window"]
    total = 0.0
    for n in lengths:
        m = min(n, W)
        total += m * (m + 1) / 2.0 + max(n - W, 0) * float(W)
    return total


def _causal_pairs(lengths) -> float:
    return float(sum(n * (n + 1) / 2.0 for n in lengths))


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: the weights once (the experts the
    batch reaches), every position's K and V in the global layers, the
    window's in the window layers (each sequence taken at the mean context)."""
    s = sizes(config)
    seen = _seen(s, context_tokens / batch) * batch if batch else 0.0
    attended = s["global_layers"] * context_tokens + s["window_layers"] * seen
    flops = 2.0 * (active_params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += _attn_position_flops(s) * attended
    nbytes = weight_bytes(s, batch) + kv_bytes_per_position(s) * (attended + s["layers"] * batch)
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    the weights once a call (the experts a call's tokens reach), causal
    attention over each prompt in the global layers and under the window in
    the others, K and V written once."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * active_params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += _attn_position_flops(s) * (
        s["global_layers"] * _causal_pairs(prompt_lengths)
        + s["window_layers"] * window_pairs(s, prompt_lengths)
    )
    nbytes = calls * weight_bytes(s, tokens / max(calls, 1.0))
    nbytes += s["layers"] * kv_bytes_per_position(s) * tokens
    return {"flops": flops, "bytes": nbytes}


def expert_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.expert_scan``: the routed experts' ReGLU over ``tokens`` tokens
    in ``calls`` program calls of one kind: ``top_k`` pairs a token at the
    real pairs' flops, each call reading once the experts its tokens reach,
    every pair's activations in and out in bf16."""
    s = sizes(config)
    if tokens <= 0 or calls <= 0:
        return None
    pairs = s["top_k"] * tokens
    flops = 2.0 * expert_params(s) * pairs * s["layers"]
    reached = experts_reached(s, tokens / calls)
    nbytes = calls * s["layers"] * expert_params(s) * reached * s["wbytes"]
    nbytes += s["layers"] * pairs * 2.0 * s["hidden"] * 2.0
    return {"flops": flops, "bytes": nbytes}


def _attention_work(s: dict, n_layers: int, tokens: float, pairs, positions) -> dict | None:
    """Scores, softmax and values of ``n_layers`` layers (not the
    projections). Prefill calls: ``pairs`` query-key pairs at ``4 * heads *
    head`` flops, q, k and v of the ``tokens`` queries read and the output
    written once in bf16. Decode steps: ``positions`` cached positions
    attended to, K and V of each read once."""
    if not n_layers:
        return None
    if pairs is not None:
        wide = (s["q_heads"] + 2 * s["kv_heads"]) * s["head"] + s["q_heads"] * s["head"]
        return {"flops": n_layers * _attn_position_flops(s) * pairs,
                "bytes": n_layers * tokens * wide * 2.0}
    if positions is not None:
        return {"flops": n_layers * _attn_position_flops(s) * positions,
                "bytes": n_layers * kv_bytes_per_position(s) * positions}
    return None


def attention(config: dict, tokens: float, calls: float, *, pairs: float | None = None,
              positions: float | None = None) -> dict | None:
    """``mtpu.attention``: the global layers', over the causal ``pairs`` of
    prefilled prompts or the ``positions`` decode steps attended to."""
    s = sizes(config)
    return _attention_work(s, s["global_layers"], tokens, pairs, positions)


def window_attention(config: dict, tokens: float, calls: float, *, lengths=None,
                     contexts=None, steps: float = 1.0) -> dict | None:
    """``mtpu.window_attention``: the window layers'. Prefill calls: the
    prompts' ``lengths``, ``min(t + 1, window)`` pairs a query. Decode:
    ``steps`` steps over sequences at ``contexts``, ``min(context, window)``
    positions a sequence and step."""
    s = sizes(config)
    if lengths is not None:
        return _attention_work(s, s["window_layers"], tokens, window_pairs(s, lengths), None)
    if contexts is not None:
        seen = sum(_seen(s, c) for c in contexts) * steps
        return _attention_work(s, s["window_layers"], tokens, None, seen)
    return None


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call (the prefill calls, the decode steps); the two
#: attentions want besides what they attend to
SCOPE_WORK = {
    "mtpu.expert_scan": expert_scan, "mtpu.attention": attention,
    "mtpu.window_attention": window_attention,
}
