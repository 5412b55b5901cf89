"""The DeepSeek-V2 family: RMSNorm, multi-head *latent* attention (MLA) with
a yarn-scaled rotary slice, a leading dense SwiGLU layer, then layers of two
shared experts beside routed ones chosen by a group-limited greedy top-k over
a softmax, the weights not renormalised and scaled. Everything of the
harness that knows this layer's shape (``manifest.py`` says what a family
file has to hold), and nothing imported from the program but its config
class, which ``program_config`` hands to ``LLMEngine``.

The reference, per layer, with ``h = RMSNorm(x)``:

- ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` as heads of ``[q_nope, q_pe]``;
  ``[c_kv, k_pe] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; ``k_pe`` one key for
  all heads; ``[k_nope, v]`` per head ``= c_kv W_kvb``. Rotary on ``q_pe`` and
  ``k_pe`` only, pairs ``(2i, 2i+1)`` as the published weights are laid out,
  frequencies by yarn (each inverse frequency a blend of ``f / factor`` and
  ``f`` by a linear ramp between the correction dims). Scores ``(q_nope .
  k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2`` with ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``, causal, softmax in float32; ``o =
  concat_heads(P v) W_o``.
- feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; after them ``s = softmax(h W_g)`` over the router's
  published width; a group's score is its largest ``s``; the ``topk_group``
  best groups stay, the rest read zero; the top ``num_experts_per_tok`` of
  what is left; the weights are those ``s``, *not* renormalised, times
  ``routed_scaling_factor``; ``y = sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)``.

The chip's share (``model-configs`` section 4): the configuration's
``n_routed_experts`` counts the experts *held here*, ``expert_share`` gives
the router's published width and the first held expert. The sum runs over
the chosen experts inside the share only; what the absent experts would add
is left out, in the program and here alike, and that partial result goes on.

The routing margin reported per position is the narrowest over the layers
and over *both* selections (last group kept against first group dropped,
last expert chosen against first left out), *relative*: ``1 - s_next /
s_last``. The scores of a 160-way softmax are about 1/160 each, so the
absolute 0.01 of ``reference.DECIDED_MARGIN`` would call every position a
near-tie; a relative 0.01 is a gap of 0.01 in the router's logits.

The work functions count what the algorithm needs: the weights once a call
(of the routed experts, the held ones the call's tokens reach), the latent
cache at ``(kv_lora_rank + qk_rope_head_dim)`` values a token and layer read
once for all heads, expanded attention at ``2 * heads * (qk + v)`` flops a
query-key pair in prefill and absorbed attention at ``2 * heads * ((rank +
rope) + rank)`` a position in decode.

The load generator's process reads the work functions and may not hold JAX,
so nothing here imports it until a function that needs it is called.
"""

from __future__ import annotations

import functools
import math
import os
import sys
import time
import types

jax = jnp = np = W = R = None


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

_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
              "mscale", "mscale_all_dim")


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json`` (and ``expert_share`` for the cut)."""
    scaling = config.get("rope_scaling") or None
    if scaling is not None and scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {scaling!r}: this family knows yarn")
    for key, want in (("scoring_func", "softmax"), ("topk_method", "group_limited_greedy")):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this family knows {want!r}")
    held = int(config["n_routed_experts"])
    share = config.get("expert_share") or {}
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "first_dense": int(config.get("first_k_dense_replace", 0)),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vdim": int(config["v_head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "experts": held,
        "router": int(share.get("of", held)),
        "expert_offset": int(share.get("offset", 0)),
        "shared": int(config.get("n_shared_experts") or 0),
        "top_k": int(config["num_experts_per_tok"]),
        "groups": int(config.get("n_group", 1)),
        "top_groups": int(config.get("topk_group", 1)),
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", False)),
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "yarn": tuple(float(scaling[k]) for k in _YARN_KEYS) if scaling else None,
        "norm_eps": float(config.get("rms_norm_eps", 1e-6)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the model cannot run the family's cells: :func:`_leave_container`."""
    try:
        from modal_examples_tpu.models.deepseek_v2 import DeepseekV2Config
    except ImportError as e:
        _leave_container(e)
        raise
    return DeepseekV2Config.from_hf_config(config_file)


def _leave_container(error: ImportError) -> None:
    """End a serving container of a program that lacks this family's model
    (a commit from before it came) with nothing left behind. Raising alone
    does not: that program's executor drops a container the moment it
    reports a boot error, ``run.py`` exits on the error without its wait for
    the container, and the process is still handing the chip back (0.3-0.7 s
    on one v5e chip: PERF.md section 6) when the run has ended: the check
    counts that as a process left running. So, inside a container, hand the
    chip back first and leave at once: the executor then learns of the
    failure from the closed pipe, when the process is gone, and ``run.py``
    exits non-zero as before. Anywhere else the ImportError is the answer."""
    if "MTPU_TASK_ID" not in os.environ:  # the program's mark of a container
        return
    sys.stderr.write(
        f"families/deepseek_v2.py: this program cannot run the family's cells: {error}\n"
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


def layer_weights(key, d: dict, dense: bool) -> dict:
    """One decoder layer, the leading dense kind or the routed kind. Matmul
    weights are int8 pairs, ``[in, out]``; ``wkv_b``'s columns are, head by
    head, ``[k_nope, v]``, and ``wq_b``'s ``[q_nope, q_pe]``, as published."""
    D, H = d["hidden"], d["heads"]
    k = jax.random.split(key, 12)
    out = {
        "attn_norm": jnp.ones((D,), jnp.bfloat16),
        "q_norm": jnp.ones((d["q_rank"],), jnp.bfloat16),
        "kv_norm": jnp.ones((d["kv_rank"],), jnp.bfloat16),
        "mlp_norm": jnp.ones((D,), jnp.bfloat16),
        "wq_a": W._int8_weight(k[0], (D, d["q_rank"])),
        "wq_b": W._int8_weight(k[1], (d["q_rank"], H * (d["nope"] + d["rope"]))),
        "wkv_a": W._int8_weight(k[2], (D, d["kv_rank"] + d["rope"])),
        "wkv_b": W._int8_weight(k[3], (d["kv_rank"], H * (d["nope"] + d["vdim"]))),
        "wo": W._int8_weight(k[4], (H * d["vdim"], D)),
    }
    if dense:
        out["gate"] = W._int8_weight(k[5], (D, d["ffn"]))
        out["up"] = W._int8_weight(k[6], (D, d["ffn"]))
        out["down"] = W._int8_weight(k[7], (d["ffn"], D))
        return out
    E, F, S = d["experts"], d["moe_ffn"], d["shared"] * d["moe_ffn"]
    out["router"] = (
        jax.random.normal(k[8], (D, d["router"]), jnp.float32) * D**-0.5
    ).astype(jnp.bfloat16)
    out["moe_gate"] = W._int8_weight(k[5], (E, D, F))
    out["moe_up"] = W._int8_weight(k[6], (E, D, F))
    out["moe_down"] = W._int8_weight(k[7], (E, F, D))
    out["shared_gate"] = W._int8_weight(k[9], (D, S))
    out["shared_up"] = W._int8_weight(k[10], (D, S))
    out["shared_down"] = W._int8_weight(k[11], (S, D))
    return out


def _split(key, d: dict):
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return k_embed, k_head, jax.random.split(k_layers, d["layers"])


def _outer(k_embed, k_head, d: dict) -> dict:
    return {
        "embed": (
            jax.random.normal(k_embed, (d["vocab"], d["hidden"]), jnp.float32) * 0.02
        ).astype(jnp.bfloat16),
        "final_norm": jnp.ones((d["hidden"],), jnp.bfloat16),
        "lm_head": W._int8_weight(k_head, (d["hidden"], d["vocab"])),
    }


def layer_key(seed: int, d: dict, index: int):
    _load()
    return _split(W.root_key(seed), d)[2][index]


def outer_weights(seed: int, d: dict) -> dict:
    """Embedding, final norm and output head."""
    _load()
    k_embed, k_head, _ = _split(W.root_key(seed), d)
    return _outer(k_embed, k_head, d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``dense_layers`` and ``moe_layers``
    each stacked on a leading axis (a ``lax.map`` over the layers of a kind,
    so the peak is the tree plus one layer's temporaries)."""
    _load()
    n_dense = min(d["first_dense"], d["layers"])

    @jax.jit
    def build(key):
        k_embed, k_head, keys = _split(key, d)
        tree = _outer(k_embed, k_head, d)
        if n_dense:
            tree["dense_layers"] = jax.lax.map(
                lambda k: layer_weights(k, d, True), keys[:n_dense]
            )
        if d["layers"] > n_dense:
            tree["moe_layers"] = jax.lax.map(
                lambda k: layer_weights(k, d, False), keys[n_dense:]
            )
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rope: int, theta: float, yarn) -> list[float]:
    """The ``rope / 2`` inverse frequencies, in plain Python floats."""
    extra = [theta ** (-2.0 * i / rope) for i in range(rope // 2)]
    if yarn is None:
        return extra
    factor, original, beta_fast, beta_slow = yarn[:4]

    def correction_dim(rotations):
        return rope * math.log(original / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rope - 1)
    span = (high - low) or 0.001
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / span, 0.0), 1.0)  # 0: keep f, 1: f / factor
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def softmax_scale(d: dict) -> float:
    m = yarn_mscale(d["yarn"][0], d["yarn"][5]) if d["yarn"] else 1.0
    return (d["nope"] + d["rope"]) ** -0.5 * m * m


def _rope_pairs(x, positions, d):
    """x: [S, heads, rope]; pairs (2i, 2i+1)."""
    inv_freq = jnp.asarray(yarn_inv_freq(d["rope"], d["rope_theta"], d["yarn"]), jnp.float32)
    angle = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    m = yarn_mscale(d["yarn"][0], d["yarn"][4]) / yarn_mscale(d["yarn"][0], d["yarn"][5]) \
        if d["yarn"] else 1.0
    cos, sin = (jnp.cos(angle) * m)[:, None, :], (jnp.sin(angle) * m)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


_HEAD_BLOCK = 8  # heads whose [S, S] scores are alive at once


def _attention(h, lw, d, bits):
    S = h.shape[0]
    H, nope, rope, vdim, rank = d["heads"], d["nope"], d["rope"], d["vdim"], d["kv_rank"]
    pos = jnp.arange(S)
    c_q = R._rms_norm(h @ R._dequant(lw["wq_a"], bits), lw["q_norm"], d["norm_eps"])
    q = (c_q @ R._dequant(lw["wq_b"], bits)).reshape(S, H, nope + rope)
    kv_a = h @ R._dequant(lw["wkv_a"], bits)
    c_kv = R._rms_norm(kv_a[:, :rank], lw["kv_norm"], d["norm_eps"])
    k_pe = _rope_pairs(kv_a[:, None, rank:], pos, d)[:, 0]  # [S, rope]: all heads' key
    kv = (c_kv @ R._dequant(lw["wkv_b"], bits)).reshape(S, H, nope + vdim)
    q_nope, q_pe = q[..., :nope], _rope_pairs(q[..., nope:], pos, d)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(d)
    causal = pos[:, None] >= pos[None, :]
    block = math.gcd(H, _HEAD_BLOCK)

    def heads(args):  # a block of heads at a time: [S, block, ...]
        qn, qp, kn, vv = args
        scores = (jnp.einsum("shd,thd->hst", qn, kn) + jnp.einsum("shr,tr->hst", qp, k_pe))
        scores = jnp.where(causal[None], scores * scale, -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), vv)

    def blocks(x):  # [S, H, w] -> [H / block, S, block, w]
        return x.reshape(S, H // block, block, x.shape[-1]).transpose(1, 0, 2, 3)

    o = jax.lax.map(heads, (blocks(q_nope), blocks(q_pe), blocks(k_nope), blocks(v)))
    o = o.transpose(1, 0, 2, 3).reshape(S, H * vdim)
    return o @ R._dequant(lw["wo"], bits)


def _swiglu(h, lw, names, bits):
    gate, up, down = (R._dequant(lw[n], bits) for n in names)
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(scores, d):
    """``scores``: [S, router] softmax. Returns (weights [S, k], expert ids
    [S, k], relative margin [S]) of the group-limited greedy top-k."""
    S, E = scores.shape
    G, k = d["groups"], d["top_k"]
    margin = jnp.full((S,), jnp.inf)
    masked = scores
    if G > 1:
        group_scores = scores.reshape(S, G, E // G).max(axis=-1)
        ranked, group_ids = jax.lax.top_k(group_scores, min(d["top_groups"] + 1, G))
        if d["top_groups"] < G:
            margin = 1.0 - ranked[:, -1] / ranked[:, -2]
        keep = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], group_ids[:, : d["top_groups"]]
        ].set(True)
        masked = jnp.where(jnp.repeat(keep, E // G, axis=1), scores, 0.0)
    ranked, ids = jax.lax.top_k(masked, k + 1)
    margin = jnp.minimum(margin, 1.0 - ranked[:, k] / ranked[:, k - 1])
    weights = ranked[:, :k]
    if d["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * d["route_scale"], ids[:, :k], margin


def _routed(h, lw, d, bits):
    """The held experts' part of the routed sum, the shared experts once, and
    the routing margin."""
    scores = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    weights, ids, margin = route(scores, d)

    def one_expert(e, out):
        weight = jnp.sum(jnp.where(ids == e + d["expert_offset"], weights, 0.0), axis=-1)
        one = {n: {"q": lw[n]["q"][e], "scale": lw[n]["scale"][e]}
               for n in ("moe_gate", "moe_up", "moe_down")}
        return out + weight[:, None] * _swiglu(h, one, ("moe_gate", "moe_up", "moe_down"), bits)

    out = jax.lax.fori_loop(0, d["experts"], one_expert, jnp.zeros_like(h))
    if d["shared"]:
        out = out + _swiglu(h, lw, ("shared_gate", "shared_up", "shared_down"), bits)
    return out, margin


def _layer(x, margin, lw, d, bits, dense):
    with jax.default_matmul_precision("highest"):
        x = x + _attention(R._rms_norm(x, lw["attn_norm"], d["norm_eps"]), lw, d, bits)
        h = R._rms_norm(x, lw["mlp_norm"], d["norm_eps"])
        if dense:
            return x + _swiglu(h, lw, ("gate", "up", "down"), bits), margin
        out, m = _routed(h, lw, d, bits)
        return x + out, jnp.minimum(margin, m)


def _head(x_rows, outer, d, bits):
    with jax.default_matmul_precision("highest"):
        x = R._rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return x @ R._dequant(outer["lm_head"], bits)


@functools.cache
def _jitted():
    """The layer, the head and one layer's weights as compiled programs."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "bits", "dense")),
        head=jax.jit(_head, static_argnames=("d", "bits")),
        layer_weights=jax.jit(layer_weights, static_argnames=("d", "dense")),
    )


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows, and at those rows the narrowest routing margin over
    the layers. Layers outermost, so each layer's weights are made once, one
    layer at a time, dequantised to float32. ``bits=4`` is the control."""
    jit = _jitted()
    d = _Frozen(d)
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = [(embed[jnp.asarray(ids)], jnp.full((len(ids),), jnp.inf)) for ids in sequences]
    for index in range(d["layers"]):
        dense = index < d["first_dense"]
        t0 = time.monotonic()
        lw = jax.block_until_ready(
            jit.layer_weights(layer_key(seed, d, index), d=d, dense=dense)
        )
        t1 = time.monotonic()
        xs = jax.block_until_ready([jit.layer(x, m, lw, d, bits, dense) for x, m in xs])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw
    logits = [
        np.asarray(jit.head(x[jnp.asarray(r)], outer, d, bits)) for (x, _m), r in zip(xs, rows)
    ]
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m), r in zip(xs, rows)]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, None: 2.0}


def sizes(config: dict) -> dict:
    d = dims_of(config)
    return {
        **d,
        "moe_layers": max(d["layers"] - d["first_dense"], 0),
        "dense_layers": min(d["first_dense"], d["layers"]),
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def attn_params(s: dict) -> int:
    H = s["heads"]
    return (s["hidden"] * s["q_rank"] + s["q_rank"] * H * (s["nope"] + s["rope"])
            + s["hidden"] * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * H * (s["nope"] + s["vdim"]) + H * s["vdim"] * s["hidden"])


def expert_params(s: dict) -> int:
    return 3 * s["hidden"] * s["moe_ffn"]


def held_share(s: dict) -> float:
    """The share of a token's routed pairs that land on held experts,
    routing taken as uniform."""
    return s["experts"] / s["router"]


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct *held* experts per layer that ``tokens`` tokens
    reach, routing taken as uniform over the router's width."""
    miss = 1.0 - s["top_k"] / s["router"]
    return s["experts"] * (1.0 - miss ** max(tokens, 0.0))


def _dense_params_per_token(s: dict) -> float:
    """Matmul parameters every token multiplies, outside the routed experts
    and the head: attention, the dense layers' SwiGLU, shared experts, router."""
    shared = 3 * s["hidden"] * s["shared"] * s["moe_ffn"] + s["hidden"] * s["router"]
    return (s["layers"] * attn_params(s) + s["dense_layers"] * 3 * s["hidden"] * s["ffn"]
            + s["moe_layers"] * shared)


def active_params_per_token(s: dict) -> float:
    """... and with the routed experts a token's pairs reach here."""
    routed = s["moe_layers"] * expert_params(s) * s["top_k"] * held_share(s)
    return _dense_params_per_token(s) + routed


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a call over ``tokens`` tokens has to read once."""
    fixed = (_dense_params_per_token(s) - s["moe_layers"] * s["hidden"] * s["router"]) * s["wbytes"]
    fixed += s["moe_layers"] * s["hidden"] * s["router"] * 2.0  # the router, bf16
    routed = s["moe_layers"] * expert_params(s) * experts_reached(s, tokens) * s["wbytes"]
    return fixed + routed + s["hidden"] * s["vocab"] * s["wbytes"]


def kv_bytes_per_token(s: dict) -> float:
    """The latent and the rotated key, every layer: read once for all heads."""
    return s["layers"] * (s["kv_rank"] + s["rope"]) * s["kvbytes"]


def _expanded_pair_flops(s: dict) -> float:
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vdim"])


def _absorbed_position_flops(s: dict) -> float:
    return 2.0 * s["heads"] * ((s["kv_rank"] + s["rope"]) + s["kv_rank"])


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: absorbed attention over the latents."""
    s = sizes(config)
    flops = 2.0 * (active_params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += s["layers"] * _absorbed_position_flops(s) * context_tokens
    nbytes = weight_bytes(s, batch) + kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def _causal_pairs(lengths) -> float:
    return float(sum(n * (n + 1) / 2.0 for n in lengths))


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    expanded causal attention over each prompt, the weights read once a call,
    the latents written once."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * active_params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += s["layers"] * _expanded_pair_flops(s) * _causal_pairs(prompt_lengths)
    per_call = tokens / max(calls, 1.0)
    nbytes = calls * weight_bytes(s, per_call) + kv_bytes_per_token(s) * tokens
    return {"flops": flops, "bytes": nbytes}


def expert_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """The held routed experts' SwiGLU over ``tokens`` tokens in ``calls``
    program calls of one kind: ``top_k * held / router`` pairs a token, each
    call reading once the held experts its tokens reach, every pair's
    activations in and out in bf16."""
    s = sizes(config)
    if not s["experts"] or tokens <= 0 or calls <= 0:
        return None
    pairs = s["top_k"] * held_share(s) * tokens
    flops = 2.0 * expert_params(s) * pairs * s["moe_layers"]
    reached = experts_reached(s, tokens / calls)
    nbytes = calls * s["moe_layers"] * expert_params(s) * reached * s["wbytes"]
    nbytes += s["moe_layers"] * pairs * 2.0 * s["hidden"] * 2.0
    return {"flops": flops, "bytes": nbytes}


def attention(config: dict, tokens: float, calls: float, *, pairs: float | None = None,
              positions: float | None = None) -> dict | None:
    """The attention proper (scores, softmax, values; not the projections)
    of one kind of program call. Prefill calls: ``pairs`` causal query-key
    pairs, each at the expanded ``2 * heads * (qk + v)`` flops, reading q, k
    and v of the pairs' ``tokens`` queries once in bf16 (expanded keys and
    values: what the kernel is handed). Decode steps: ``positions`` cached
    positions attended to, each at the absorbed ``2 * heads * (576 + 512)``
    flops and read once, ``(kv_lora_rank + rope)`` values, for all heads."""
    s = sizes(config)
    L = s["layers"]
    if pairs is not None:
        wide = s["heads"] * (2 * (s["nope"] + s["rope"]) + 2 * s["vdim"])
        return {"flops": L * _expanded_pair_flops(s) * pairs,
                "bytes": L * tokens * wide * 2.0}
    if positions is not None:
        return {"flops": L * _absorbed_position_flops(s) * positions,
                "bytes": kv_bytes_per_token(s) * positions}
    return None


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call (the prefill calls, the decode steps);
#: ``attention`` wants besides what it attends to (``layers/latent.py``)
SCOPE_WORK = {"mtpu.expert_scan": expert_scan, "mtpu.attention": attention}
