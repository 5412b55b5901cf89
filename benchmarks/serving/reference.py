"""The comparison with the plain reference: what is shared by every family.

A family's file (``families/<family>.py``) holds the architecture's forward
pass in float32, ``logits_at``: straightforward ``jax.numpy`` at ``highest``
matmul precision, no kernels, no cache, no batching, nothing imported from
the program and nothing the program made; the weights come again from the
seed, one layer at a time. Here is what does not know a layer's shape: the
dequantisation of a seeded int8 weight (and its int4 control), RMSNorm, the
padding of sequences and rows to few shapes, and the gap statistics.

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

import jax
import jax.numpy as jnp
import numpy as np

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


def logits_at(family, seed: int, d: dict, sequences: list[list[int]],
              rows: list[list[int]], bits: int = 8):
    """The family's reference logits of each sequence at the given rows, the
    narrowest routing margin there, and its clock. Sequences and rows are
    padded to a multiple (few shapes, few compiles) and the padding cut off
    again. ``bits=4`` is the control."""
    padded = []
    for seq in sequences:
        ids = np.zeros((-(-len(seq) // PAD_TO) * PAD_TO,), np.int32)
        ids[: len(seq)] = seq
        padded.append(ids)
    padded_rows = [r + [r[-1]] * (-len(r) % ROWS_TO) for r in rows]
    logits, margins, clock = family.logits_at(seed, d, padded, padded_rows, bits)
    return (
        [lg[: len(r)] for lg, r in zip(logits, rows)],
        [m[: len(r)] for m, r in zip(margins, rows)],
        clock,
    )


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


def served_gaps(family, seed: int, d: dict, samples: list[dict], control: bool = False,
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
    ref, margins, clock = logits_at(family, seed, d, sequences, rows, bits=8)
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
        low, _m, _c = logits_at(family, seed, d, sequences, rows, bits=4)
        cgaps = np.concatenate([
            b - lg[np.arange(len(lo)), lo.argmax(axis=-1)]
            for lg, b, lo in zip(ref, best, low)
        ])
        out.update(gap_stats(cgaps, margins, "control"))
        if detail:
            out["control_gaps"] = cgaps.tolist()
    return out
