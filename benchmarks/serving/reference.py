"""The plain reference: the architecture's forward pass in float32.

RMSNorm, rotary embedding (split-half pairing, as the published Mistral and
Mixtral weights use), grouped-query causal attention, SwiGLU, dense or routed
to the top-k of E experts with the softmax taken over all experts and the
top-k weights renormalised, as ``modeling_mixtral`` does. Straightforward
``jax.numpy`` at ``highest`` matmul precision: no kernels, no cache, no
batching, nothing imported from the program and nothing the program made.
The weights come again from the seed (``weights.py``), one layer at a time,
dequantised to float32: the whole model does not fit in float32.

What is compared (:func:`served_gaps`): for a request the timed window
served greedily, one pass over its prompt and its served tokens; at each
served position, how far the served token's reference logit lies below the
reference's best. A sound program serves the reference's own first choice
or one that rounding made a near-tie; a token from broken or coarser
arithmetic lies well below.

The control is the same pass with every matmul weight requantised to int4,
the nearest precision under the int8 the configurations state. It need not
decode: at each position it reads the gap of the token that int4 puts first.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

PAD_TO = 1024  # sequences are padded to a multiple: few shapes, few compiles
ROWS_TO = 256  # and so are the rows whose logits are read
WIDE_GAP = 0.5  # a gap no near-tie of sound arithmetic explains ...
DECIDED_MARGIN = 0.01  # ... at a position whose routing is no near-tie either


def _dequant(w: dict, bits: int):
    full = w["q"].astype(jnp.float32) * w["scale"]
    if bits == 8:
        return full
    # the control: symmetric per-output-channel int4 of the same weight
    amax = jnp.max(jnp.abs(full), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    return jnp.clip(jnp.round(full / scale), -7, 7) * scale


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: [S, heads, head_dim]; pairs (i, i + head_dim/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(x, lw, d, bits):
    S = x.shape[0]
    H, KV, hd = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(S)
    q = _rope((x @ _dequant(lw["wq"], bits)).reshape(S, H, hd), pos, d["rope_theta"])
    k = _rope((x @ _dequant(lw["wk"], bits)).reshape(S, KV, hd), pos, d["rope_theta"])
    v = (x @ _dequant(lw["wv"], bits)).reshape(S, KV, hd)
    group = H // KV
    outs = []
    for g in range(KV):  # one key/value head and its query heads at a time
        qg = q[:, g * group:(g + 1) * group]  # [S, group, hd]
        scores = jnp.einsum("sgd,td->gst", qg, k[:, g]) / hd**0.5
        scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
        outs.append(jnp.einsum("gst,td->sgd", jax.nn.softmax(scores, axis=-1), v[:, g]))
    o = jnp.concatenate(outs, axis=1).reshape(S, H * hd)
    return o @ _dequant(lw["wo"], bits)


def _mlp(h, lw, d, bits):
    """Returns (output, routing margin per token: how far the last expert
    chosen leads the first one left out; inf for a dense layer)."""
    if not d["experts"]:
        a = jax.nn.silu(h @ _dequant(lw["gate"], bits)) * (h @ _dequant(lw["up"], bits))
        return a @ _dequant(lw["down"], bits), jnp.full(h.shape[:1], jnp.inf)
    probs = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    ranked, _ = jax.lax.top_k(probs, d["top_k"] + 1)
    margin = ranked[:, -2] - ranked[:, -1]
    top_p, top_i = jax.lax.top_k(probs, d["top_k"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(d["experts"]):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)  # [S]
        one = lambda name: _dequant(  # noqa: E731
            {"q": lw[name]["q"][e], "scale": lw[name]["scale"][e]}, bits
        )
        a = jax.nn.silu(h @ one("moe_gate")) * (h @ one("moe_up"))
        out = out + weight[:, None] * (a @ one("moe_down"))
    return out, margin


@functools.partial(jax.jit, static_argnames=("d", "bits"))
def _layer(x, margin, lw, d, bits):
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_rms_norm(x, lw["attn_norm"], d["norm_eps"]), lw, d, bits)
        out, m = _mlp(_rms_norm(x, lw["mlp_norm"], d["norm_eps"]), lw, d, bits)
        return x + out, jnp.minimum(margin, m)


@functools.partial(jax.jit, static_argnames=("d", "bits"))
def _head(x_rows, outer, d, bits):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return x @ _dequant(outer["lm_head"], bits)


_layer_weights = jax.jit(W.layer_weights, static_argnames=("d",))


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list[list[int]], rows: list[list[int]],
              bits: int = 8) -> tuple[list[np.ndarray], list[np.ndarray], dict]:
    """Reference logits of each sequence at the given rows, and at those rows
    the narrowest routing margin over the layers. Layers outermost, so each
    layer's weights are made once. ``bits=4`` is the control."""
    d = _Frozen(d)
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = W.outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = []
    for seq in sequences:
        padded = -(-len(seq) // PAD_TO) * PAD_TO
        ids = np.zeros((padded,), np.int32)
        ids[: len(seq)] = seq
        xs.append((embed[jnp.asarray(ids)], jnp.full((padded,), jnp.inf)))
    for index in range(d["layers"]):
        t0 = time.monotonic()
        lw = jax.block_until_ready(_layer_weights(W.layer_key(seed, d, index), d=d))
        t1 = time.monotonic()
        xs = jax.block_until_ready([_layer(x, m, lw, d, bits) for x, m in xs])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw
    logits = []
    for (x, _m), r in zip(xs, rows):
        padded = r + [r[-1]] * (-len(r) % ROWS_TO)
        logits.append(np.asarray(_head(x[jnp.asarray(padded)], outer, d, bits))[: len(r)])
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m), r in zip(xs, rows)]
    return logits, margins, clock


def gap_stats(gaps: np.ndarray, margins: np.ndarray, prefix: str) -> dict:
    """The numbers a configuration's limits are set on: the widest gap, the
    90th percentile, the mean, and the share of positions whose gap is wide
    (over ``WIDE_GAP``) although every layer's routing there was decided (the
    last expert chosen led the next by ``DECIDED_MARGIN`` of probability or
    more; always so in a dense model). A routed model's widest gap is no
    use: where a layer's routing is a near-tie, bf16 settles it either way
    and the logits move by whole units. The share leaves those positions
    out and counts the rest one by one, so it sees a fault that touches a
    few tokens in a hundred."""
    wide = (gaps > WIDE_GAP) & (margins >= DECIDED_MARGIN)
    return {
        f"{prefix}_gap_max": float(gaps.max()),
        f"{prefix}_gap_p90": float(np.quantile(gaps, 0.9)),
        f"{prefix}_gap_mean": float(gaps.mean()),
        f"{prefix}_wide_decided_pct": 100.0 * float(wide.mean()),
    }


def served_gaps(seed: int, d: dict, samples: list[dict], control: bool = False,
                detail: bool = False) -> dict:
    """``samples``: [{"prompt": ids as the engine saw them, "served": ids}].
    At every served position of every sample, the gap by which the served
    token's reference logit lies below the reference's best, reduced by
    :func:`gap_stats` (which of the numbers a configuration is held to is in
    its file). With ``control``, the same for the tokens int4 puts first."""
    sequences = [s["prompt"] + s["served"][:-1] for s in samples]
    rows = [
        list(range(len(s["prompt"]) - 1, len(s["prompt"]) - 1 + len(s["served"])))
        for s in samples
    ]
    ref, margins, clock = logits_at(seed, d, sequences, rows, bits=8)
    best = [lg.max(axis=-1) for lg in ref]
    gaps = np.concatenate([
        b - lg[np.arange(len(s["served"])), np.asarray(s["served"])]
        for s, lg, b in zip(samples, ref, best)
    ])
    margins = np.concatenate(margins)
    out = {"served_tokens": int(len(gaps)), **gap_stats(gaps, margins, "served"), **clock}
    if detail:
        out["gaps"] = gaps.tolist()
        out["margins"] = [float(min(m, 1.0)) for m in margins]
    if control:
        low, _m, _c = logits_at(seed, d, sequences, rows, bits=4)
        cgaps = np.concatenate([
            b - lg[np.arange(len(lo)), lo.argmax(axis=-1)]
            for lg, b, lo in zip(ref, best, low)
        ])
        out.update(gap_stats(cgaps, margins, "control"))
        if detail:
            out["control_gaps"] = cgaps.tolist()
    return out
