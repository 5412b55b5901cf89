"""The Llama family: RMSNorm, rotary grouped-query attention, SwiGLU, dense
or routed to the top-k of E experts (Mistral, Mixtral, Llama). Everything of
the harness that knows this layer's shape: the sizes read from the published
``config.json`` keys, the seeded weights, what the program's engine is given,
the plain reference's forward pass, and the operations and bytes the
rooflines divide by. ``manifest.py`` says what a family file has to hold.

The reference: rotary embedding with split-half pairing (as the published
Mistral and Mixtral weights use), grouped-query causal attention, SwiGLU,
dense or routed with the softmax taken over all experts and the top-k
weights renormalised, as ``modeling_mixtral`` does. The work functions count
what the algorithm needs, not what the program happens to move: the weights
once (for a routed model, the experts the batch's tokens reach), the live KV,
not the whole cache; they dispatch on the fields (``num_local_experts``),
never on a name.

The load generator's process reads the work functions and may not hold JAX,
so nothing here imports it until a function that needs it is called.
"""

from __future__ import annotations

import functools
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


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json``."""
    heads = int(config["num_attention_heads"])
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": heads,
        "kv_heads": int(config.get("num_key_value_heads", heads)),
        "head_dim": int(config.get("head_dim", int(config["hidden_size"]) // heads)),
        "ffn": int(config["intermediate_size"]),
        "experts": int(config.get("num_local_experts", 0)),
        "top_k": int(config.get("num_experts_per_tok", 2)),
        "rope_theta": float(config.get("rope_theta", 10000.0)),
        "norm_eps": float(config.get("rms_norm_eps", 1e-5)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration."""
    from modal_examples_tpu.models.llama import LlamaConfig

    return LlamaConfig.from_hf_config(config_file)


def layer_weights(key, d: dict) -> dict:
    """One decoder layer. Matmul weights are int8 pairs, ``[in, out]``."""
    D, H, KV, hd, F, E = (
        d["hidden"], d["heads"], d["kv_heads"], d["head_dim"], d["ffn"], d["experts"]
    )
    k = jax.random.split(key, 8)
    out = {
        "attn_norm": jnp.ones((D,), jnp.bfloat16),
        "mlp_norm": jnp.ones((D,), jnp.bfloat16),
        "wq": W._int8_weight(k[0], (D, H * hd)),
        "wk": W._int8_weight(k[1], (D, KV * hd)),
        "wv": W._int8_weight(k[2], (D, KV * hd)),
        "wo": W._int8_weight(k[3], (H * hd, D)),
    }
    if E:
        out["router"] = (
            jax.random.normal(k[4], (D, E), jnp.float32) * D**-0.5
        ).astype(jnp.bfloat16)
        out["moe_gate"] = W._int8_weight(k[5], (E, D, F))
        out["moe_up"] = W._int8_weight(k[6], (E, D, F))
        out["moe_down"] = W._int8_weight(k[7], (E, F, D))
    else:
        out["gate"] = W._int8_weight(k[5], (D, F))
        out["up"] = W._int8_weight(k[6], (D, F))
        out["down"] = W._int8_weight(k[7], (F, D))
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
    """The whole tree, layers stacked on a leading axis, in one jitted call
    (a ``lax.map`` over layers: the peak is the tree plus one layer's
    temporaries, which is what lets 7 Mixtral layers boot where the program's
    own init, whole bf16 leaves at a time, cannot)."""
    _load()

    @jax.jit
    def build(key):
        k_embed, k_head, keys = _split(key, d)
        tree = _outer(k_embed, k_head, d)
        tree["layers"] = jax.lax.map(lambda k: layer_weights(k, d), keys)
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------


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
    q = _rope((x @ R._dequant(lw["wq"], bits)).reshape(S, H, hd), pos, d["rope_theta"])
    k = _rope((x @ R._dequant(lw["wk"], bits)).reshape(S, KV, hd), pos, d["rope_theta"])
    v = (x @ R._dequant(lw["wv"], bits)).reshape(S, KV, hd)
    group = H // KV
    outs = []
    for g in range(KV):  # one key/value head and its query heads at a time
        qg = q[:, g * group:(g + 1) * group]  # [S, group, hd]
        scores = jnp.einsum("sgd,td->gst", qg, k[:, g]) / hd**0.5
        scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
        outs.append(jnp.einsum("gst,td->sgd", jax.nn.softmax(scores, axis=-1), v[:, g]))
    o = jnp.concatenate(outs, axis=1).reshape(S, H * hd)
    return o @ R._dequant(lw["wo"], bits)


def _mlp(h, lw, d, bits):
    """Returns (output, routing margin per token: how far the last expert
    chosen leads the first one left out; inf for a dense layer)."""
    if not d["experts"]:
        a = jax.nn.silu(h @ R._dequant(lw["gate"], bits)) * (h @ R._dequant(lw["up"], bits))
        return a @ R._dequant(lw["down"], bits), jnp.full(h.shape[:1], jnp.inf)
    probs = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    ranked, _ = jax.lax.top_k(probs, d["top_k"] + 1)
    margin = ranked[:, -2] - ranked[:, -1]
    top_p, top_i = jax.lax.top_k(probs, d["top_k"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(d["experts"]):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)  # [S]
        one = lambda name: R._dequant(  # noqa: E731
            {"q": lw[name]["q"][e], "scale": lw[name]["scale"][e]}, bits
        )
        a = jax.nn.silu(h @ one("moe_gate")) * (h @ one("moe_up"))
        out = out + weight[:, None] * (a @ one("moe_down"))
    return out, margin


def _layer(x, margin, lw, d, bits):
    with jax.default_matmul_precision("highest"):
        x = x + _attention(R._rms_norm(x, lw["attn_norm"], d["norm_eps"]), lw, d, bits)
        out, m = _mlp(R._rms_norm(x, lw["mlp_norm"], d["norm_eps"]), lw, d, bits)
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
        layer=jax.jit(_layer, static_argnames=("d", "bits")),
        head=jax.jit(_head, static_argnames=("d", "bits")),
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
    layer at a time, dequantised to float32: the whole model does not fit in
    float32. ``bits=4`` is the control."""
    jit = _jitted()
    d = _Frozen(d)
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    embed = outer["embed"].astype(jnp.float32)
    xs = [(embed[jnp.asarray(ids)], jnp.full((len(ids),), jnp.inf)) for ids in sequences]
    for index in range(d["layers"]):
        t0 = time.monotonic()
        lw = jax.block_until_ready(jit.layer_weights(layer_key(seed, d, index), d=d))
        t1 = time.monotonic()
        xs = jax.block_until_ready([jit.layer(x, m, lw, d, bits) for x, m in xs])
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
    D = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    KV = int(config.get("num_key_value_heads", H))
    hd = int(config.get("head_dim", D // H))
    return {
        "D": D, "H": H, "KV": KV, "hd": hd,
        "F": int(config["intermediate_size"]),
        "L": int(config["num_hidden_layers"]),
        "V": int(config["vocab_size"]),
        "E": int(config.get("num_local_experts", 0)),
        "k": int(config.get("num_experts_per_tok", 2)),
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
    }


def attn_params(s: dict) -> int:
    return s["D"] * s["hd"] * (s["H"] + 2 * s["KV"]) + s["H"] * s["hd"] * s["D"]


def expert_params(s: dict) -> int:
    return 3 * s["D"] * s["F"]


def active_params_per_token(s: dict) -> int:
    """Matmul parameters one token multiplies: attention, its MLP (the top-k
    experts and the router of a routed layer), the output head."""
    mlp = expert_params(s) * (s["k"] if s["E"] else 1) + s["D"] * s["E"]
    return s["L"] * (attn_params(s) + mlp) + s["D"] * s["V"]


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct experts per layer that ``tokens`` tokens reach,
    routing taken as uniform (seeded weights route near uniformly)."""
    if not s["E"]:
        return 1.0
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** max(tokens, 0.0))


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a step over ``tokens`` tokens has to read once."""
    mlp = expert_params(s) * experts_reached(s, tokens) * s["wbytes"]
    router = s["D"] * s["E"] * 2.0
    layer = attn_params(s) * s["wbytes"] + mlp + router
    return s["L"] * layer + s["D"] * s["V"] * s["wbytes"]


def kv_bytes_per_token(s: dict) -> float:
    return 2.0 * s["L"] * s["KV"] * s["hd"] * s["kvbytes"]


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together."""
    s = sizes(config)
    flops = 2.0 * active_params_per_token(s) * batch
    flops += 4.0 * s["L"] * s["H"] * s["hd"] * context_tokens  # q.k and p.v
    nbytes = weight_bytes(s, batch) + kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["D"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    causal attention over each prompt, the weights read once a call."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * (active_params_per_token(s) - s["D"] * s["V"]) * tokens
    flops += 2.0 * s["D"] * s["V"] * len(prompt_lengths)  # the head: last rows only
    flops += 4.0 * s["L"] * s["H"] * s["hd"] * sum(n * (n + 1) / 2.0 for n in prompt_lengths)
    per_call = tokens / max(calls, 1.0)
    nbytes = calls * weight_bytes(s, per_call) + kv_bytes_per_token(s) * tokens
    return {"flops": flops, "bytes": nbytes}


def expert_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """The routed experts' SwiGLU over ``tokens`` tokens in ``calls`` program
    calls of one kind: each token multiplies its top-k experts, each call
    reads once the experts its tokens reach, and every token's activations
    go in and out in bf16. None for a dense model, which has no such layer."""
    s = sizes(config)
    if not s["E"] or tokens <= 0 or calls <= 0:
        return None
    flops = 2.0 * expert_params(s) * s["k"] * tokens * s["L"]
    reached = experts_reached(s, tokens / calls)
    nbytes = calls * s["L"] * expert_params(s) * reached * s["wbytes"]
    nbytes += s["L"] * tokens * 2.0 * s["D"] * 2.0
    return {"flops": flops, "bytes": nbytes}


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call (the prefill calls, the decode steps)
SCOPE_WORK = {"mtpu.expert_scan": expert_scan}
