"""The GLM-5.2 family (``glm_moe_dsa``): RMSNorm, multi-head *latent*
attention (MLA) restricted to the positions a learned indexer selects (a
lightning indexer, an exact top-k, IndexShare), leading dense SwiGLU layers,
then layers of one shared expert beside routed ones chosen by a sigmoid
router with a selection bias. Everything of the harness that knows this
layer's shape (``manifest.py`` says what a family file has to hold), and
nothing imported from the program but its config class, which
``program_config`` hands to ``LLMEngine``.

The reference, per layer, with ``h = RMSNorm(x)`` (forms the published config
does not spell follow the published DeepSeek sparse-attention indexer that
``glm_moe_dsa`` names; the configuration's file lists them under ``assumed``):

- MLA: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` as heads of ``[q_nope,
  q_pe]``; ``[c_kv, k_pe] = h W_kva``; ``c_kv = RMSNorm(c_kv)``; ``k_pe`` one
  key for all heads; ``[k_nope, v]`` per head ``= c_kv W_kvb``. Plain rotary
  (``rope_theta``, no scaling) on ``q_pe`` and ``k_pe``, pairs ``(2i, 2i+1)``
  as the published weights are laid out. Scores ``(q_nope . k_nope + q_pe .
  k_pe) * (nope + rope)^-0.5``, softmax in float32 **over the selected
  positions** ``S_t`` only; ``o = concat_heads(P v) W_o``.
- the indexer, in a layer ``indexer_types`` calls ``full``: ``qI_j = (c_q
  W_Iq)_j`` (``index_n_heads`` of ``index_head_dim``), ``kI_s = LayerNorm(h_s
  W_Ik)``, both with their first ``qk_rope_head_dim`` values rotated; ``w = h
  W_Iw * heads^-0.5 * head_dim^-0.5``; ``I[t, s] = sum_j w_j relu(qI_j .
  kI_s)``; ``S_t`` the ``index_topk`` positions ``s <= t`` of largest ``I[t,
  s]`` (all of them while ``t < index_topk``; a tie goes to the lowest
  position: ``lax.top_k``'s order). A ``shared`` layer has no indexer and
  attends to the ``S_t`` of the nearest ``full`` layer before it.
- feed-forward: the layers ``mlp_layer_types`` calls ``dense`` a SwiGLU of
  ``intermediate_size``; the others ``p = sigmoid(h W_r)`` over the router's
  published width, chosen the top ``num_experts_per_tok`` of ``p + b`` (``b``
  the layer's selection bias), weights the chosen ``p`` renormalised times
  ``routed_scaling_factor``; ``y = sum_i g_i SwiGLU_i(h) + SwiGLU_shared(h)``.

The pass is computed in blocks so that four sequences of 9-17k tokens fit the
chip beside nothing else: layers outermost (one layer's weights at a time),
queries 256 at a time (their index scores ``[256, heads, S]``, their top-k,
their selection as a mask, kept for the ``shared`` layers that follow), the
attention's heads 16 at a time.

The chip's share (``model-configs`` section 4): the configuration's
``n_routed_experts`` counts the experts *held here*, ``expert_share`` gives
the router's published width and the first held expert. The sum runs over
the chosen experts inside the share only; what the absent experts would add
is left out, in the program and here alike, and that partial result goes on.

The routing margin reported per position is the narrowest, over the layers
and over the *held* experts, of the gap in ``p + b`` by which a held expert
is inside the chosen set (above the first one left out) or outside it (below
the last one chosen): a flip among experts no chip here holds moves the
result only through the renormalisation (a few percent of the held experts'
part). With 8 of 256 chosen a token some pair of experts is a near-tie at
nearly every position in some layer; one that involves the 16 held experts
at about two positions in five.

Seeded weights as the other int8 families' (``weights.py``), plus: the
selection bias ``0.1 * N(0, 1)`` (a tenth of a sigmoid's range: it moves the
chosen set at most positions, so a program that chose by ``p`` alone fails);
the indexer's ``W_Iq`` and ``W_Ik`` int8 pairs, ``W_Iw`` bf16 as the router.

**Two controls.** ``reference.py`` asks for the control as ``bits=4``, and
the configuration says what that pass is: every matmul weight requantised to
int4 (``check_control`` absent, as in the benchmark's file), or selection off
at the stated precision, every layer attending to every cached position
(``"check_control": "select-all"``). The builder reads the second by giving
the probe a copy of the configuration's file with that key (``probe.py
--control --env BENCH_CONFIG_FILE=<copy>``); the benchmark's runs read the
file as it is.

The work functions count what the algorithm needs, whatever implements it:
the weights once a call (of the routed experts, the held ones the call's
tokens reach), attention over ``min(t + 1, index_topk)`` positions a query
(expanded ``2 * heads * (qk + v)`` flops a pair in prefill, absorbed ``2 *
heads * ((rank + rope) + rank)`` a position in decode, the selected latents
read once), the indexer's ``2 * index_n_heads * index_head_dim`` flops a
scored pair in the ``full`` layers and its keys read once.

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

#: what a configuration's ``check_control`` may say the ``bits=4`` pass is
CONTROLS = ("int4", "select-all")
BIAS_STD = 0.1  # of the router's seeded selection bias


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


def layer_pattern(config: dict) -> tuple[tuple, tuple]:
    """("full" | "shared", ...) and ("dense" | "sparse", ...) a layer run:
    the published stack's kinds (``indexer_types`` / ``mlp_layer_types``
    where the file has them, else the published rule), then the stretch the
    file runs: ``layer_range`` ``[first, past the last]``, absent for a whole
    stack."""
    n = int(config["num_hidden_layers"])
    first, last = config.get("layer_range") or (0, n)
    if last - first != n or first < 0:
        raise ValueError(f"layer_range {[first, last]} is not {n} layers")
    kinds = config.get("indexer_types")
    if kinds is None:
        off = int(config.get("index_skip_topk_offset", 0))
        freq = int(config.get("index_topk_freq", 1))
        kinds = ["full" if i < off or (i - off) % freq == freq - 1 else "shared" for i in range(last)]
    mlps = config.get("mlp_layer_types")
    if mlps is None:
        k = min(int(config.get("first_k_dense_replace", 0)), last)
        mlps = ["dense"] * k + ["sparse"] * (last - k)
    for name, got in (("indexer_types", kinds), ("mlp_layer_types", mlps)):
        if len(got) < last or ("layer_range" not in config and len(got) != n):
            raise ValueError(f"{name} names {len(got)} layers; the file runs layers {first}..{last - 1}")
    kinds, mlps = tuple(kinds[first:last]), tuple(mlps[first:last])
    if kinds[0] != "full":
        raise ValueError("the first layer run must have an indexer ('full')")
    if "sparse" in mlps and "dense" in mlps[mlps.index("sparse"):]:
        raise ValueError("a dense layer after a routed one: this family stacks dense first")
    return kinds, mlps


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json`` (and ``expert_share`` for the cut)."""
    rope = config.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters {rope!r}: this family knows plain rope")
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("n_group", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this family knows {want!r}")
    if config.get("num_nextn_predict_layers", 0):
        raise ValueError("the multi-token-prediction block is not modelled: run it at 0")
    held = int(config["n_routed_experts"])
    share = config.get("expert_share") or {}
    kinds, mlps = layer_pattern(config)
    if (control := config.get("check_control", "int4")) not in CONTROLS:
        raise ValueError(f"check_control {control!r}: one of {CONTROLS}")
    return {
        "control": control,
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "kinds": kinds,
        "mlps": mlps,
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "vdim": int(config["v_head_dim"]),
        "idx_heads": int(config["index_n_heads"]),
        "idx_dim": int(config["index_head_dim"]),
        "topk": int(config["index_topk"]),
        "ffn": int(config["intermediate_size"]),
        "moe_ffn": int(config["moe_intermediate_size"]),
        "experts": held,
        "router": int(share.get("of", held)),
        "expert_offset": int(share.get("offset", 0)),
        "shared": int(config.get("n_shared_experts") or 0),
        "top_k": int(config["num_experts_per_tok"]),
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "rope_theta": float(rope.get("rope_theta", config.get("rope_theta", 10000.0))),
        "norm_eps": float(config.get("rms_norm_eps", 1e-5)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the model cannot run the family's cells: :func:`_leave_container`."""
    try:
        from modal_examples_tpu.models.glm_dsa import GlmDsaConfig
    except ImportError as e:
        _leave_container(e)
        raise
    return GlmDsaConfig.from_hf_config(config_file)


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
        f"families/glm_dsa.py: this program cannot run the family's cells: {error}\n"
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
    """One decoder layer without its indexer, the dense kind or the routed
    kind. Matmul weights are int8 pairs, ``[in, out]``; ``wkv_b``'s columns
    are, head by head, ``[k_nope, v]``, and ``wq_b``'s ``[q_nope, q_pe]``."""
    D, H = d["hidden"], d["heads"]
    k = jax.random.split(key, 13)
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
    out["router_bias"] = BIAS_STD * jax.random.normal(k[12], (d["router"],), jnp.float32)
    out["moe_gate"] = W._int8_weight(k[5], (E, D, F))
    out["moe_up"] = W._int8_weight(k[6], (E, D, F))
    out["moe_down"] = W._int8_weight(k[7], (E, F, D))
    if S:
        out["shared_gate"] = W._int8_weight(k[9], (D, S))
        out["shared_up"] = W._int8_weight(k[10], (D, S))
        out["shared_down"] = W._int8_weight(k[11], (S, D))
    return out


def indexer_weights(key, d: dict) -> dict:
    """One ``full`` layer's indexer: the index queries' and the key's
    projections int8 pairs, the heads' weights bf16 (as the router), the
    key's LayerNorm at its identity."""
    D, Hi, Di = d["hidden"], d["idx_heads"], d["idx_dim"]
    k = jax.random.split(key, 3)
    return {
        "wq_idx": W._int8_weight(k[0], (d["q_rank"], Hi * Di)),
        "wk_idx": W._int8_weight(k[1], (D, Di)),
        "k_norm": jnp.ones((Di,), jnp.bfloat16),
        "k_norm_bias": jnp.zeros((Di,), jnp.bfloat16),
        "w_idx": (jax.random.normal(k[2], (D, Hi), jnp.float32) * D**-0.5).astype(jnp.bfloat16),
    }


def _split(key, d: dict):
    k_embed, k_head, k_layers, k_index = jax.random.split(key, 4)
    return (k_embed, k_head, jax.random.split(k_layers, d["layers"]),
            jax.random.split(k_index, d["layers"]))


def _outer(k_embed, k_head, d: dict) -> dict:
    return {
        "embed": (
            jax.random.normal(k_embed, (d["vocab"], d["hidden"]), jnp.float32) * 0.02
        ).astype(jnp.bfloat16),
        "final_norm": jnp.ones((d["hidden"],), jnp.bfloat16),
        "lm_head": W._int8_weight(k_head, (d["hidden"], d["vocab"])),
    }


def _n_dense(d: dict) -> int:
    return sum(1 for m in d["mlps"] if m == "dense")


def _full_layers(d: dict) -> list[int]:
    return [i for i, kind in enumerate(d["kinds"]) if kind == "full"]


def outer_weights(seed: int, d: dict) -> dict:
    """Embedding, final norm and output head."""
    _load()
    k_embed, k_head, _, _ = _split(W.root_key(seed), d)
    return _outer(k_embed, k_head, d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``dense_layers`` and ``moe_layers``
    each stacked on a leading axis, ``indexer_layers`` the ``full`` layers'
    indexers stacked in order (a ``lax.map`` over the layers of a kind, so
    the peak is the tree plus one layer's temporaries)."""
    _load()
    n_dense, full = _n_dense(d), np.asarray(_full_layers(d))

    @jax.jit
    def build(key):
        k_embed, k_head, keys, index_keys = _split(key, d)
        tree = _outer(k_embed, k_head, d)
        if n_dense:
            tree["dense_layers"] = jax.lax.map(
                lambda k: layer_weights(k, d, True), keys[:n_dense]
            )
        if d["layers"] > n_dense:
            tree["moe_layers"] = jax.lax.map(
                lambda k: layer_weights(k, d, False), keys[n_dense:]
            )
        tree["indexer_layers"] = jax.lax.map(lambda k: indexer_weights(k, d), index_keys[full])
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------

_QUERY_BLOCK = 256  # queries whose index scores [block, heads, S] are alive at once
_HEAD_BLOCK = 16  # heads whose attention scores [heads, block, S] are alive at once


def _rope_pairs(x, positions, d):
    """x: [S, heads, rope]; pairs (2i, 2i+1), plain frequencies."""
    rope = d["rope"]
    inv_freq = jnp.asarray(
        [d["rope_theta"] ** (-2.0 * i / rope) for i in range(rope // 2)], jnp.float32
    )
    angle = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _rope_head(x, positions, d):
    """Rotate the first ``rope`` values of x [S, heads, width]."""
    r = d["rope"]
    return jnp.concatenate([_rope_pairs(x[..., :r], positions, d), x[..., r:]], axis=-1)


def _layer_norm(x, weight, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(
        jnp.float32
    )


def _blocks(x, size):
    """[S, ...] -> [S / size, size, ...]."""
    return x.reshape(x.shape[0] // size, size, *x.shape[1:])


def select(h, c_q, iw, d, bits):
    """The selection of one ``full`` layer as a mask [S, S]: row t keeps the
    ``topk`` positions ``s <= t`` of largest index score (all of them in a
    sequence of at most ``topk``), a block of queries at a time."""
    S = h.shape[0]
    pos = jnp.arange(S)
    if S <= d["topk"]:
        return pos[:, None] >= pos[None, :]
    Hi, Di = d["idx_heads"], d["idx_dim"]
    q = _rope_head((c_q @ R._dequant(iw["wq_idx"], bits)).reshape(S, Hi, Di), pos, d)
    k = _layer_norm(h @ R._dequant(iw["wk_idx"], bits), iw["k_norm"], iw["k_norm_bias"],
                    d["norm_eps"])
    k = _rope_head(k[:, None, :], pos, d)[:, 0]
    w = (h @ iw["w_idx"].astype(jnp.float32)) * (Hi**-0.5 * Di**-0.5)
    block = math.gcd(S, _QUERY_BLOCK)

    def rows(args):
        qb, wb, tb = args  # [block, Hi, Di], [block, Hi], [block]
        scores = jnp.einsum("tj,tjs->ts", wb, jax.nn.relu(jnp.einsum("tjd,sd->tjs", qb, k)))
        causal = tb[:, None] >= pos[None, :]
        _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), d["topk"])
        chosen = jnp.zeros((block, S), bool).at[jnp.arange(block)[:, None], idx].set(True)
        return chosen & causal

    return jax.lax.map(rows, (_blocks(q, block), _blocks(w, block), _blocks(pos, block))).reshape(S, S)


def _attention(h, sel, lw, iw, d, bits, select_all):
    """h [S, D] normed -> (the attention's output [S, D], the selection mask
    [S, S] it used: this layer's own where it has an indexer ``iw``, else
    ``sel`` as carried)."""
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
    if select_all:
        sel = pos[:, None] >= pos[None, :]
    elif iw is not None:
        sel = select(h, c_q, iw, d, bits)
    scale = (nope + rope) ** -0.5
    qblock, hblock = math.gcd(S, _QUERY_BLOCK), math.gcd(H, _HEAD_BLOCK)

    def heads_of(x):  # [n, H, w] -> [H / hblock, n, hblock, w]
        return x.reshape(x.shape[0], H // hblock, hblock, x.shape[-1]).transpose(1, 0, 2, 3)

    kn, vv = heads_of(k_nope), heads_of(v)

    def rows(args):
        qn, qp, keep = args  # [qblock, H, nope], [qblock, H, rope], [qblock, S]

        def heads(a):
            qn_h, qp_h, kn_h, v_h = a
            scores = jnp.einsum("shd,thd->hst", qn_h, kn_h) + jnp.einsum("shr,tr->hst", qp_h, k_pe)
            scores = jnp.where(keep[None], scores * scale, -jnp.inf)
            return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v_h)

        o = jax.lax.map(heads, (heads_of(qn), heads_of(qp), kn, vv))  # [H/hb, qblock, hb, vdim]
        return o.transpose(1, 0, 2, 3).reshape(qblock, H * vdim)

    o = jax.lax.map(rows, (_blocks(q_nope, qblock), _blocks(q_pe, qblock), _blocks(sel, qblock)))
    return o.reshape(S, H * vdim) @ R._dequant(lw["wo"], bits), sel


def _swiglu(h, lw, names, bits):
    gate, up, down = (R._dequant(lw[n], bits) for n in names)
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(p, bias, d):
    """``p``: [S, router] sigmoid scores. Returns (weights [S, k], expert ids
    [S, k], margin [S]): chosen by ``p + bias``, weighted by the chosen ``p``
    renormalised times the scale; the margin is the narrowest gap, over the
    held experts, between a held expert's biased score and the score it would
    have to pass to change sides (the first left out for a chosen one, the
    last chosen for one left out)."""
    k = d["top_k"]
    biased = p + bias.astype(jnp.float32)
    ranked, ids = jax.lax.top_k(biased, k + 1)
    weights = jnp.take_along_axis(p, ids[:, :k], axis=-1)
    if d["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    held = biased[:, d["expert_offset"]: d["expert_offset"] + d["experts"]]
    last_in, first_out = ranked[:, k - 1: k], ranked[:, k: k + 1]
    gap = jnp.where(held >= last_in, held - first_out, last_in - held)
    return weights * d["route_scale"], ids[:, :k], jnp.min(gap, axis=-1)


def _routed(h, lw, d, bits):
    """The held experts' part of the routed sum, the shared expert once, and
    the routing margin."""
    p = jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32))
    weights, ids, margin = route(p, lw["router_bias"], d)

    def one_expert(e, out):
        weight = jnp.sum(jnp.where(ids == e + d["expert_offset"], weights, 0.0), axis=-1)
        one = {n: {"q": lw[n]["q"][e], "scale": lw[n]["scale"][e]}
               for n in ("moe_gate", "moe_up", "moe_down")}
        return out + weight[:, None] * _swiglu(h, one, ("moe_gate", "moe_up", "moe_down"), bits)

    out = jax.lax.fori_loop(0, d["experts"], one_expert, jnp.zeros_like(h))
    if d["shared"]:
        out = out + _swiglu(h, lw, ("shared_gate", "shared_up", "shared_down"), bits)
    return out, margin


def _layer(x, margin, sel, lw, iw, d, bits, dense, select_all):
    with jax.default_matmul_precision("highest"):
        out, sel = _attention(
            R._rms_norm(x, lw["attn_norm"], d["norm_eps"]), sel, lw, iw, d, bits, select_all
        )
        x = x + out
        h = R._rms_norm(x, lw["mlp_norm"], d["norm_eps"])
        if dense:
            return x + _swiglu(h, lw, ("gate", "up", "down"), bits), margin, sel
        out, m = _routed(h, lw, d, bits)
        return x + out, jnp.minimum(margin, m), sel


def _head(x_rows, outer, d, bits):
    with jax.default_matmul_precision("highest"):
        x = R._rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return x @ R._dequant(outer["lm_head"], bits)


@functools.cache
def _jitted():
    """The layer, the head and one layer's weights as compiled programs."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "bits", "dense", "select_all")),
        head=jax.jit(_head, static_argnames=("d", "bits")),
        layer_weights=jax.jit(layer_weights, static_argnames=("d", "dense")),
        indexer_weights=jax.jit(indexer_weights, static_argnames=("d",)),
    )


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows, and at those rows the narrowest routing margin over
    the layers. Layers outermost, so each layer's weights are made once, one
    layer at a time, dequantised to float32; each sequence carries its
    selection mask from a ``full`` layer to the ``shared`` ones after it.
    ``bits=4`` is the control: int4 weights, or, where the configuration
    says so (``check_control``), selection off at the stated precision."""
    jit = _jitted()
    d = _Frozen(d)
    select_all = bits != 8 and d["control"] == "select-all"
    if select_all:
        bits = 8
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = [
        (embed[jnp.asarray(ids)], jnp.full((len(ids),), jnp.inf),
         jnp.zeros((len(ids), len(ids)), bool))
        for ids in sequences
    ]
    _, _, keys, index_keys = _split(W.root_key(seed), d)
    for index in range(d["layers"]):
        dense = d["mlps"][index] == "dense"
        t0 = time.monotonic()
        lw = jit.layer_weights(keys[index], d=d, dense=dense)
        iw = jit.indexer_weights(index_keys[index], d=d) if d["kinds"][index] == "full" else None
        jax.block_until_ready((lw, iw))
        t1 = time.monotonic()
        xs = [jit.layer(x, m, sel, lw, iw, d, bits, dense, select_all) for x, m, sel in xs]
        jax.block_until_ready(xs)
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw, iw
    logits = [
        np.asarray(jit.head(x[jnp.asarray(r)], outer, d, bits)) for (x, _m, _s), r in zip(xs, rows)
    ]
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m, _s), r in zip(xs, rows)]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, None: 2.0}


def sizes(config: dict) -> dict:
    d = dims_of(config)
    return {
        **d,
        "moe_layers": d["layers"] - _n_dense(d),
        "dense_layers": _n_dense(d),
        "full_layers": len(_full_layers(d)),
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def attn_params(s: dict) -> int:
    H = s["heads"]
    return (s["hidden"] * s["q_rank"] + s["q_rank"] * H * (s["nope"] + s["rope"])
            + s["hidden"] * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * H * (s["nope"] + s["vdim"]) + H * s["vdim"] * s["hidden"])


def indexer_params(s: dict) -> int:
    """One indexer's matmul parameters: int8 ones, and the heads' weights (bf16)."""
    return s["q_rank"] * s["idx_heads"] * s["idx_dim"] + s["hidden"] * s["idx_dim"]


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
    and the head: attention, the indexers, the dense layers' SwiGLU, the
    shared expert, the router."""
    shared = 3 * s["hidden"] * s["shared"] * s["moe_ffn"] + s["hidden"] * s["router"]
    index = indexer_params(s) + s["hidden"] * s["idx_heads"]
    return (s["layers"] * attn_params(s) + s["full_layers"] * index
            + s["dense_layers"] * 3 * s["hidden"] * s["ffn"] + s["moe_layers"] * shared)


def active_params_per_token(s: dict) -> float:
    """... and with the routed experts a token's pairs reach here."""
    routed = s["moe_layers"] * expert_params(s) * s["top_k"] * held_share(s)
    return _dense_params_per_token(s) + routed


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a call over ``tokens`` tokens has to read once."""
    bf16 = s["moe_layers"] * s["hidden"] * s["router"] + s["full_layers"] * s["hidden"] * s["idx_heads"]
    fixed = (_dense_params_per_token(s) - bf16) * s["wbytes"] + bf16 * 2.0
    routed = s["moe_layers"] * expert_params(s) * experts_reached(s, tokens) * s["wbytes"]
    return fixed + routed + s["hidden"] * s["vocab"] * s["wbytes"]


def kv_bytes_per_token(s: dict) -> float:
    """The latent and the rotated key, every layer: read once for all heads."""
    return s["layers"] * (s["kv_rank"] + s["rope"]) * s["kvbytes"]


def index_key_bytes_per_token(s: dict) -> float:
    """The indexer's key, the ``full`` layers."""
    return s["full_layers"] * s["idx_dim"] * s["kvbytes"]


def _expanded_pair_flops(s: dict) -> float:
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vdim"])


def _absorbed_position_flops(s: dict) -> float:
    return 2.0 * s["heads"] * ((s["kv_rank"] + s["rope"]) + s["kv_rank"])


def _scored_pair_flops(s: dict) -> float:
    return 2.0 * s["idx_heads"] * s["idx_dim"]


def selected_pairs(first: int, last: int, topk: int) -> float:
    """(query, position) pairs the queries at positions ``first .. last - 1``
    attend to: ``min(t + 1, topk)`` each."""
    full = max(last - max(first, topk - 1), 0)  # queries with t + 1 >= topk
    short_first, short_last = first, min(last, topk - 1)
    short = max(short_last - short_first, 0)
    return full * float(topk) + short * (short_first + short_last + 1) / 2.0


def causal_pairs(first: int, last: int) -> float:
    """... and that an indexer scores: ``t + 1`` each."""
    n = max(last - first, 0)
    return n * (first + last + 1) / 2.0


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: every indexer scores each context
    whole, absorbed attention runs over the selected latents alone."""
    s = sizes(config)
    selected = batch * min(context_tokens / max(batch, 1e-9), float(s["topk"]))
    flops = 2.0 * (active_params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += s["layers"] * _absorbed_position_flops(s) * selected
    flops += s["full_layers"] * _scored_pair_flops(s) * context_tokens
    nbytes = weight_bytes(s, batch) + kv_bytes_per_token(s) * (selected + batch)
    nbytes += index_key_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    every indexer scores the causal pairs, expanded attention runs over the
    selected ones, the weights are read once a call, the latents and the
    index keys written once."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * active_params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += s["layers"] * _expanded_pair_flops(s) * sum(
        selected_pairs(0, n, s["topk"]) for n in prompt_lengths
    )
    flops += s["full_layers"] * _scored_pair_flops(s) * sum(
        causal_pairs(0, n) for n in prompt_lengths
    )
    per_call = tokens / max(calls, 1.0)
    nbytes = calls * weight_bytes(s, per_call)
    nbytes += (kv_bytes_per_token(s) + index_key_bytes_per_token(s)) * tokens
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


def attention(config: dict, tokens: float, calls: float, *, selected: float | None = None,
              phase: str | None = None, pairs=None, positions=None) -> dict | None:
    """The attention proper (scores, softmax, values; not the projections)
    over ``selected`` (query, position) pairs inside the queries' selections
    (``layers/sparse.py`` counts them: ``min(t + 1, index_topk)`` a query).
    ``phase="prefill"``: each pair at the expanded ``2 * heads * (qk + v)``
    flops, q, k and v of the calls' ``tokens`` queries read once in bf16.
    ``phase="decode"``: each at the absorbed ``2 * heads * ((rank + rope) +
    rank)`` flops, its latent and rotated key read once for all heads. A
    reader that hands the dense ``pairs`` or ``positions`` of a full
    attention (``layers/latent.py``) gets None: that count is not this
    model's."""
    if selected is None:
        return None
    s = sizes(config)
    L = s["layers"]
    if phase == "prefill":
        wide = s["heads"] * (2 * (s["nope"] + s["rope"]) + 2 * s["vdim"])
        return {"flops": L * _expanded_pair_flops(s) * selected, "bytes": L * tokens * wide * 2.0}
    return {"flops": L * _absorbed_position_flops(s) * selected,
            "bytes": kv_bytes_per_token(s) * selected}


def indexer(config: dict, tokens: float, calls: float, *, scored: float | None = None,
            keys: float | None = None) -> dict | None:
    """The indexers of the ``full`` layers over ``tokens`` queries in
    ``calls`` program calls: their projections (weights read once a call),
    ``scored`` (query, position) pairs at ``2 * index_n_heads *
    index_head_dim`` flops each, ``keys`` cached index keys read once."""
    if scored is None:
        return None
    s = sizes(config)
    n = s["full_layers"]
    params = indexer_params(s) + s["hidden"] * s["idx_heads"]
    flops = n * (2.0 * params * tokens + _scored_pair_flops(s) * scored)
    nbytes = calls * n * (indexer_params(s) * s["wbytes"] + s["hidden"] * s["idx_heads"] * 2.0)
    nbytes += index_key_bytes_per_token(s) * (keys or 0.0)
    return {"flops": flops, "bytes": nbytes}


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call (the prefill calls, the decode steps);
#: ``attention`` and ``indexer`` want besides the pairs they run over
#: (``layers/sparse.py`` counts them from the requests' positions)
SCOPE_WORK = {"mtpu.expert_scan": expert_scan, "mtpu.attention": attention,
              "mtpu.indexer": indexer}
