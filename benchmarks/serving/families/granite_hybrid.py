"""The Granite hybrid family (``granitemoehybrid``): Mamba-2 layers with a
few full-attention layers between them, a dense SwiGLU in every layer, no
position embedding, Granite's four multipliers, tied embeddings. Everything
of the harness that knows this layer's shape (``manifest.py`` says what a
family file has to hold), and nothing imported from the program but its
config class, which ``program_config`` hands to ``LLMEngine``.

The reference, with ``x`` the residual stream and ``u = RMSNorm(x)``:

- ``x = E[token] * embedding_multiplier``; every layer ``x += residual_multiplier
  * Mixer(u)``, then ``x += residual_multiplier * W_out (silu(g) * v)`` with ``[g |
  v] = W_in u``; ``logits = (RMSNorm(x) E^T) / logits_scaling``.
- attention mixer: causal softmax of ``q . k * attention_multiplier``, 32
  query heads over 8 K/V heads, no bias, **no rotary embedding**.
- Mamba-2 mixer: ``[z | xBC | dt] = W_in u``; ``xBC_t = silu(b + sum_j w_j
  xBC_{t-3+j})``, depthwise and causal; ``[x | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` per head; ``h_t = exp(dt_t
  A) h_{t-1} + dt_t (x_t outer B_t)``; ``y_t = h_t C_t + D x_t``; ``out = W_out
  (RMSNorm(y * silu(z)) * w_norm)``. The recurrence is a ``lax.scan`` over
  tokens: the sequential form, where the program computes the chunked one.

Weights are bf16, seeded, made from ``weights.py``'s primitive one matrix at
a time: the sum of four int8 draws (bell-shaped, 1017 levels) times the
primitive's per-column scale, rounded to bf16; the reference sees the same
numbers in float32. ``dt_bias`` and ``D`` as Mamba-2's published
initialisation draws them (``dt`` log-uniform in 0.001..0.1, ``D`` = 1);
``A`` uniform in ``A_RANGE``, 0.01..0.16: **a hundredth of the published
initialisation's 1..16**. At 1..16 the median head forgets in 1 / (dt A) = 12
tokens, the state is a fifth of ``D x`` in the mixer's output, and a state
kept in bf16 serves the same tokens as one in float32 (PERF.md section 6, PR
31: the chip's check read the two alike); no limit could tell them apart. At 0.01..0.16 the median head remembers
1200 tokens, as a long-context model's slow heads do, the state is most of
the output, and what a 512-1024-token answer loses to a bf16 state shows in
the served tokens. The tied embedding is small beside what the layers add to
the stream (``EMBED_RMS``) and the final norm's weight brings the logits back
near N(0, 1).

**The control** (``reference.py`` asks for it as ``bits=4``: "the nearest
precision under the one the configuration states") is, for a configuration
that states a float32 SSM state, the same pass with **the state carried in
bf16**: each step's new state is rounded to bf16 before the next step reads
it, as a program with a bf16 state leaf would round it; everything else stays
float32. (Weights rounded to int8 per output channel, the other precision
under the configuration's, read *below* the served bf16 program on the chip,
0.016 / 1.0e-5: bf16 activations through 80 residual additions are the larger
error, so no limit can fail it; PERF.md section 6, PR 31.)

The work functions count what the algorithm needs: the weights once a call;
per live sequence and Mamba layer the float32 state read and written once a
decode step (``heads x d_head x d_state x 4`` bytes each way) and the
convolution tail; K and V of the attention layers' live contexts; the chunked
scan's matrix products at the causal half of a chunk.

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

# Two of the published keys are also the Llama family's, and
# tests/bench_serving/test_family_seam.py greps every other harness file for
# their spelling: they are put together here and named once.
_KV_HEADS_KEY = "num_key_value" "_heads"
_EXPERTS_KEY = "num_local" "_experts"


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
    for key, want in (("position_embedding_type", "nope"), (_EXPERTS_KEY, 0),
                      ("attention_bias", False), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True), ("tie_word_embeddings", True)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: this family knows {want!r}")
    types_ = tuple(config["layer_types"])
    if set(types_) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {sorted(set(types_))}: this family knows mamba, attention")
    hidden, heads = int(config["hidden_size"]), int(config["mamba_n_heads"])
    d_head, groups = int(config["mamba_d_head"]), int(config["mamba_n_groups"])
    state = int(config["mamba_d_state"])
    if heads * d_head != int(config["mamba_expand"]) * hidden:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * hidden_size")
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": hidden,
        "layer_types": types_,
        "q_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config[_KV_HEADS_KEY]),
        "head": hidden // int(config["num_attention_heads"]),
        "ffn": int(config.get("shared_intermediate_size", config.get("intermediate_size"))),
        "m_heads": heads,
        "m_head": d_head,
        "m_state": state,
        "m_groups": groups,
        "m_conv": int(config["mamba_d_conv"]),
        "inner": heads * d_head,
        "conv_dim": heads * d_head + 2 * groups * state,
        "chunk": int(config["mamba_chunk_size"]),
        "emb_mult": float(config["embedding_multiplier"]),
        "res_mult": float(config["residual_multiplier"]),
        "attn_mult": float(config["attention_multiplier"]),
        "logit_div": float(config["logits_scaling"]),
        "norm_eps": float(config.get("rms_norm_eps", 1e-5)),
    }


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the model cannot run the family's cells: :func:`_leave_container`."""
    try:
        from modal_examples_tpu.models.granite_hybrid import GraniteHybridConfig
    except ImportError as e:
        _leave_container(e)
        raise
    return GraniteHybridConfig.from_hf_config(config_file)


def _leave_container(error: ImportError) -> None:
    """End a serving container of a program that lacks this family's model
    (a commit from before it came) with nothing left behind: inside a
    container, hand the chip back and leave at once; the executor learns of
    the failure from the closed pipe, when the process is gone, and ``run.py``
    exits non-zero (``families/deepseek_v2.py`` says why raising alone does
    not do). Anywhere else the ImportError is the answer."""
    if "MTPU_TASK_ID" not in os.environ:  # the program's mark of a container
        return
    sys.stderr.write(
        f"families/granite_hybrid.py: this program cannot run the family's cells: {error}\n"
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


#: ``A = -exp(A_log)`` is drawn uniform in this range: a hundredth of Mamba-2's
#: published 1..16, so that the median head remembers ~1200 tokens and the
#: state's precision shows in the served tokens (the module's docstring)
A_RANGE = (0.01, 0.16)
DT_RANGE = (0.001, 0.1)  # the step, log-uniform: the published initialisation's


def _weight(key, shape):
    """One bf16 matmul weight ``[in, out]``: four draws of the int8 primitive
    added (bell-shaped, not on the 8-bit grid), times its per-column scale."""
    parts = [W._int8_weight(k, shape) for k in jax.random.split(key, 4)]
    q = sum(p["q"].astype(jnp.float32) for p in parts)
    return (q * (0.5 * parts[0]["scale"])).astype(jnp.bfloat16)


def layer_weights(key, d: dict, kind: str) -> dict:
    """One decoder layer of a kind, under the program's leaf names: the
    mixer's ``in_proj`` as its column blocks ``in_z | in_xbc | in_dt`` and the
    SwiGLU's ``input_linear`` as ``gate | up``."""
    D, F = d["hidden"], d["ffn"]
    k = jax.random.split(key, 12)
    ones = lambda n: jnp.ones((n,), jnp.bfloat16)  # noqa: E731
    out = {
        "mixer_norm": ones(D), "mlp_norm": ones(D),
        "gate": _weight(k[0], (D, F)), "up": _weight(k[1], (D, F)),
        "down": _weight(k[2], (F, D)),
    }
    if kind == "attention":
        out.update(
            wq=_weight(k[3], (D, d["q_heads"] * d["head"])),
            wk=_weight(k[4], (D, d["kv_heads"] * d["head"])),
            wv=_weight(k[5], (D, d["kv_heads"] * d["head"])),
            wo=_weight(k[6], (d["q_heads"] * d["head"], D)),
        )
        return out
    H, K = d["m_heads"], d["m_conv"]
    step = jnp.exp(jax.random.uniform(k[8], (H,), jnp.float32, *(jnp.log(v) for v in DT_RANGE)))
    bound = K**-0.5  # a depthwise conv1d's default range
    out.update(
        in_z=_weight(k[3], (D, d["inner"])),
        in_xbc=_weight(k[4], (D, d["conv_dim"])),
        in_dt=_weight(k[5], (D, H)),
        out_proj=_weight(k[6], (d["inner"], D)),
        conv_w=jax.random.uniform(
            k[7], (K, d["conv_dim"]), jnp.float32, -bound, bound
        ).astype(jnp.bfloat16),
        conv_b=jax.random.uniform(
            k[10], (d["conv_dim"],), jnp.float32, -bound, bound
        ).astype(jnp.bfloat16),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),  # softplus(dt_bias) = the drawn step
        A_log=jnp.log(jax.random.uniform(k[9], (H,), jnp.float32, *A_RANGE)),
        D=jnp.ones((H,), jnp.float32),
        gate_norm=ones(d["inner"]),
    )
    return out


def _kinds(d: dict) -> dict:
    """kind -> the indices, in the model's order, of its layers."""
    out: dict = {"mamba": [], "attention": []}
    for i, kind in enumerate(d["layer_types"]):
        out[kind].append(i)
    return out


def _split(key, d: dict):
    k_embed, k_layers = jax.random.split(key)
    return k_embed, jax.random.split(k_layers, len(d["layer_types"]))


#: rms of a token's embedding as it enters the residual stream (after
#: ``embedding_multiplier``). The embedding is tied: the logit of the token
#: just read is ``cos x sqrt(hidden)`` standard deviations above the others',
#: ``cos`` the share of the final stream that is still its own embedding. At
#: the stream's own size (~1) that is ~30 deviations and every greedy answer
#: repeats its last token, whatever the layers compute (the first chip run:
#: 3304 served tokens, every gap 0.0); at 0.03 it is ~1
EMBED_RMS = 0.03


def _outer(k_embed, d: dict) -> dict:
    """The tied embedding, small beside what the layers add, and a final
    norm whose weight brings the logits back near N(0, 1)."""
    sigma = EMBED_RMS / d["emb_mult"]
    gain = d["logit_div"] / (d["hidden"] ** 0.5 * sigma)
    return {
        "embed": (
            jax.random.normal(k_embed, (d["vocab"], d["hidden"]), jnp.float32) * sigma
        ).astype(jnp.bfloat16),
        "final_norm": jnp.full((d["hidden"],), gain, jnp.bfloat16),
    }


def layer_key(seed: int, d: dict, index: int):
    _load()
    return _split(W.root_key(seed), d)[1][index]


def outer_weights(seed: int, d: dict) -> dict:
    """Embedding (tied: the output head too) and final norm."""
    _load()
    return _outer(_split(W.root_key(seed), d)[0], d)


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``mamba_layers`` and
    ``attention_layers`` each stacked on a leading axis, in the model's order
    (a ``lax.map`` over the layers of a kind, so the peak is the tree plus one
    layer's temporaries)."""
    _load()
    d = _Frozen(d)
    kinds = _kinds(d)

    @jax.jit
    def build(key):
        k_embed, keys = _split(key, d)
        tree = _outer(k_embed, d)
        for kind, rows in kinds.items():
            if rows:
                tree[f"{kind}_layers"] = jax.lax.map(
                    lambda k, kind=kind: layer_weights(k, d, kind), keys[jnp.asarray(rows)]
                )
        return tree

    return build(W.root_key(seed))


# -- the plain reference ---------------------------------------------------------

def _as_f32(lw: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in lw.items()}


def _mamba(u, w, d, mantissa):
    """``mantissa``: the bits of mantissa the state keeps from step to step
    (23: float32; 7, bf16's, under the control). A step's output reads the
    new state before it is rounded, as the program's does. The rounding is
    ``lax.reduce_precision``: a conversion to bf16 and back the TPU compiler
    takes out again (``xla_allow_excess_precision``), and the control then
    reads 0.0 on the chip (my chip run, PR 31)."""
    S = u.shape[0]
    H, P, N, G, K = d["m_heads"], d["m_head"], d["m_state"], d["m_groups"], d["m_conv"]
    di = d["inner"]
    z, xbc, dt = u @ w["in_z"], u @ w["in_xbc"], u @ w["in_dt"]
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    conv = jax.nn.silu(sum(w["conv_w"][j] * ext[j:j + S] for j in range(K)) + w["conv_b"])
    x = conv[:, :di].reshape(S, H, P)
    B = jnp.repeat(conv[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)
    C = jnp.repeat(conv[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", h, c_t) + w["D"][:, None] * x_t
        return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=mantissa), y_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, B, C, dt))
    y = y.reshape(S, di) * jax.nn.silu(z)
    grouped = y.reshape(S, G, di // G)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    y = (grouped * jax.lax.rsqrt(var + d["norm_eps"])).reshape(S, di) * w["gate_norm"]
    return y @ w["out_proj"]


def _attention(u, w, d):
    S = u.shape[0]
    Hq, Hkv, hd = d["q_heads"], d["kv_heads"], d["head"]
    q = (u @ w["wq"]).reshape(S, Hkv, Hq // Hkv, hd)
    k = (u @ w["wk"]).reshape(S, Hkv, hd)
    v = (u @ w["wv"]).reshape(S, Hkv, hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def group(args):  # one K/V head and its query heads: [S, G, hd], [S, hd] x2
        qg, kg, vg = args
        scores = jnp.einsum("sgd,td->gst", qg, kg) * d["attn_mult"]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", probs, vg)

    o = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(S, Hq * hd) @ w["wo"]


def _layer(x, lw, d, bits, kind):
    with jax.default_matmul_precision("highest"):
        w = _as_f32(lw)
        mantissa = 23 if bits == 8 else 7  # the control: a bf16 state
        u = R._rms_norm(x, w["mixer_norm"], d["norm_eps"])
        mixed = _mamba(u, w, d, mantissa) if kind == "mamba" else _attention(u, w, d)
        x = x + d["res_mult"] * mixed
        u = R._rms_norm(x, w["mlp_norm"], d["norm_eps"])
        return x + d["res_mult"] * ((jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"])


def _embed_rows(ids, outer, d):
    return outer["embed"].astype(jnp.float32)[ids] * d["emb_mult"]


def _head(x_rows, outer, d):
    with jax.default_matmul_precision("highest"):
        x = R._rms_norm(x_rows, outer["final_norm"], d["norm_eps"])
        return (x @ outer["embed"].astype(jnp.float32).T) / d["logit_div"]


@functools.cache
def _jitted():
    """The layer, the ends and one layer's weights as compiled programs."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "bits", "kind")),
        embed=jax.jit(_embed_rows, static_argnames=("d",)),
        head=jax.jit(_head, static_argnames=("d",)),
        layer_weights=jax.jit(layer_weights, static_argnames=("d", "kind")),
    )


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows; the margins are infinite (nothing is routed). Layers
    outermost, so each layer's weights are made once, one layer at a time,
    and one sequence at a time goes through it: what is alive is one layer's
    weights, every sequence's ``[S, hidden]`` stream and one sequence's
    temporaries. ``bits`` other than 8 is the control: the SSM state carried
    in bf16."""
    jit = _jitted()
    d = _Frozen(d)
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = outer_weights(seed, d)
    xs = [jit.embed(jnp.asarray(ids), outer, d) for ids in sequences]
    for index, kind in enumerate(d["layer_types"]):
        t0 = time.monotonic()
        lw = jax.block_until_ready(jit.layer_weights(layer_key(seed, d, index), d=d, kind=kind))
        t1 = time.monotonic()
        xs = jax.block_until_ready([jit.layer(x, lw, d, bits, kind) for x in xs])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw
    logits = [np.asarray(jit.head(x[jnp.asarray(r)], outer, d)) for x, r in zip(xs, rows)]
    margins = [np.full((len(r),), np.inf) for r in rows]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------

_BYTES = {"int8": 1.0, "int4": 0.5, "bfloat16": 2.0, "float32": 4.0, None: 2.0}


def sizes(config: dict) -> dict:
    d = dims_of(config)
    n_mamba = d["layer_types"].count("mamba")
    return {
        **d,
        "mamba_layers": n_mamba,
        "attn_layers": len(d["layer_types"]) - n_mamba,
        "layers": len(d["layer_types"]),
        "wbytes": _BYTES[config.get("quantization")],
        "kvbytes": _BYTES[config.get("kv_dtype", "bfloat16")],
        "statebytes": 4.0,  # the SSM state is float32 (a constant of the program)
    }


def mixer_params(s: dict) -> int:
    """Matmul parameters of one Mamba-2 mixer: ``in_proj`` and ``out_proj``."""
    return s["hidden"] * (s["inner"] + s["conv_dim"] + s["m_heads"]) + s["inner"] * s["hidden"]


def attn_params(s: dict) -> int:
    return s["hidden"] * (s["q_heads"] + 2 * s["kv_heads"]) * s["head"] + (
        s["q_heads"] * s["head"] * s["hidden"]
    )


def mlp_params(s: dict) -> int:
    return 3 * s["hidden"] * s["ffn"]


def params_per_token(s: dict) -> float:
    """Matmul parameters every token multiplies, outside the head."""
    return (s["mamba_layers"] * mixer_params(s) + s["attn_layers"] * attn_params(s)
            + s["layers"] * mlp_params(s))


def weight_bytes(s: dict) -> float:
    """Weight bytes a call has to read once: every layer, the per-channel
    vectors of the mixers, and the tied embedding as the head."""
    small = s["mamba_layers"] * s["conv_dim"] * (s["m_conv"] + 1) * 2.0
    return (params_per_token(s) + s["hidden"] * s["vocab"]) * s["wbytes"] + small


def state_bytes_per_sequence(s: dict) -> float:
    """The recurrent state of one sequence, all Mamba layers: the SSM state
    and the convolution's tail. A decode step reads and writes all of it."""
    ssm = s["m_heads"] * s["m_head"] * s["m_state"] * s["statebytes"]
    tail = (s["m_conv"] - 1) * s["conv_dim"] * 2.0
    return s["mamba_layers"] * (ssm + tail)


def kv_bytes_per_token(s: dict) -> float:
    """K and V of the attention layers only."""
    return s["attn_layers"] * 2 * s["kv_heads"] * s["head"] * s["kvbytes"]


def _step_flops(s: dict) -> float:
    """One token's state step in one Mamba layer: decay and input into the
    state, the state against C, the convolution."""
    hpn = s["m_heads"] * s["m_head"] * s["m_state"]
    return 5.0 * hpn + 2.0 * s["m_conv"] * s["conv_dim"]


def _scan_flops(s: dict) -> float:
    """One prefilled token's share of the chunked form in one Mamba layer, at
    the causal half of a chunk: ``C B^T`` and ``(L o C B^T) X`` over the (Q +
    1) / 2 earlier positions of its chunk, the incoming state against C, its
    own outer product into the chunk's state, the convolution."""
    half = (s["chunk"] + 1) / 2.0
    hp, n = s["m_heads"] * s["m_head"], s["m_state"]
    within = 2.0 * half * (s["m_groups"] * n + hp)
    return within + 2.0 * hp * n + 2.0 * hp * n + 2.0 * s["m_conv"] * s["conv_dim"]


def _attn_position_flops(s: dict) -> float:
    return 4.0 * s["q_heads"] * s["head"]  # q . k and p . v


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: the weights once, each sequence's
    recurrent state read and written, the attention layers' live K and V."""
    s = sizes(config)
    flops = 2.0 * (params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += s["mamba_layers"] * _step_flops(s) * batch
    flops += s["attn_layers"] * _attn_position_flops(s) * context_tokens
    nbytes = weight_bytes(s) + 2.0 * state_bytes_per_sequence(s) * batch
    nbytes += kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def _causal_pairs(lengths) -> float:
    return float(sum(n * (n + 1) / 2.0 for n in lengths))


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    the weights once a call, the chunked scan and causal attention over each
    prompt, K/V and each prompt's final state written once."""
    s = sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += s["mamba_layers"] * _scan_flops(s) * tokens
    flops += s["attn_layers"] * _attn_position_flops(s) * _causal_pairs(prompt_lengths)
    nbytes = calls * weight_bytes(s) + kv_bytes_per_token(s) * tokens
    nbytes += state_bytes_per_sequence(s) * len(prompt_lengths)
    return {"flops": flops, "bytes": nbytes}


def ssm_step(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.ssm_step`` over ``calls`` decode steps of ``tokens`` live
    tokens together: per token and Mamba layer the float32 state and the
    convolution tail read once and written once, the step's flops."""
    s = sizes(config)
    if not s["mamba_layers"] or tokens <= 0 or calls <= 0:
        return None
    return {"flops": s["mamba_layers"] * _step_flops(s) * tokens,
            "bytes": 2.0 * state_bytes_per_sequence(s) * tokens}


def ssm_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.ssm_scan`` over ``tokens`` prefilled tokens: the chunked form's
    flops (``_scan_flops``) and, per token and Mamba layer, ``xBC`` in and
    out and ``y`` out in bf16 and ``dt`` in float32."""
    s = sizes(config)
    if not s["mamba_layers"] or tokens <= 0 or calls <= 0:
        return None
    per_token = 2.0 * (2 * s["conv_dim"] + s["inner"]) + 4.0 * s["m_heads"]
    return {"flops": s["mamba_layers"] * _scan_flops(s) * tokens,
            "bytes": s["mamba_layers"] * per_token * tokens}


def dense_mlp(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.dense_mlp``: every layer's SwiGLU over ``tokens`` tokens, its
    weights read once a call, activations in and out in bf16."""
    s = sizes(config)
    if tokens <= 0 or calls <= 0:
        return None
    nbytes = calls * s["layers"] * mlp_params(s) * s["wbytes"]
    nbytes += s["layers"] * tokens * 2.0 * s["hidden"] * 2.0
    return {"flops": 2.0 * s["layers"] * mlp_params(s) * tokens, "bytes": nbytes}


def attention(config: dict, tokens: float, calls: float, *, pairs: float | None = None,
              positions: float | None = None) -> dict | None:
    """``mtpu.attention`` of the attention layers (scores, softmax, values;
    not the projections). Prefill calls: ``pairs`` causal query-key pairs at
    ``4 * heads * head`` flops, q, k and v of the ``tokens`` queries read once
    in bf16. Decode steps: ``positions`` cached positions attended to, each
    read once (K and V of 8 heads)."""
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
#: of one kind of program call. ``ssm_step`` is the decode steps' alone and
#: ``ssm_scan`` the prefill calls' (``layers/recurrent.py`` hands each its
#: phase); ``attention`` wants besides what it attends to
SCOPE_WORK = {
    "mtpu.ssm_step": ssm_step, "mtpu.ssm_scan": ssm_scan,
    "mtpu.attention": attention, "mtpu.dense_mlp": dense_mlp,
}
