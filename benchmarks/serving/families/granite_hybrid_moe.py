"""The routed Granite hybrid family (``granitemoehybrid`` with experts:
Granite-4.0-H-Small): Mamba-2 layers with a few full-attention layers between
them and, behind **every** mixer, a shared SwiGLU beside routed experts (72,
10 a token), of which this chip may hold a share. Everything of the harness
that knows this layer's shape (``manifest.py`` says what a family file has to
hold), and nothing imported from the program but its config class, which
``program_config`` hands to ``LLMEngine``.

The dense sibling (``families/granite_hybrid.py``, Granite-4.0-H-Micro's) is
loaded through ``manifest.load_family`` and not edited: its Mamba-2 pass, its
attention, its seeded mixers, embedding and final norm, and its counts of a
mixer's work are this family's too. What is this file's own is the second
half of the layer, and the share.

The reference, with ``x`` the residual stream and ``u = RMSNorm(x)`` before
each half (from the published ``config.json`` and
``modeling_granitemoehybrid``):

- ``x += residual_multiplier * Mixer(u)``: the sibling's Mamba-2 recurrence,
  a ``lax.scan`` over tokens, or its dense causal attention without
  positions.
- ``x += residual_multiplier * (Shared(u) + Routed(u))``. ``Shared(u) = W_out
  (silu(g) * v)``, ``[g | v] = W_in u``, width ``shared_intermediate_size``.
  ``Routed(u) = sum_{e in top} p_e W_out^e (silu(g_e) * v_e)``, ``[g_e | v_e]
  = W_in^e u``, width ``intermediate_size``; ``l = W_r u`` over the router's
  whole width, ``top`` the ``num_experts_per_tok`` largest ``l`` (a tie to
  the lower id), ``p = softmax(l[top])``: top-k of the logits, then a softmax
  over the chosen. **Every held expert's product is written out for every
  token** and weighed by ``p`` or by zero, one expert at a time: no sort, no
  tiles (3.6 times the chosen pairs' work at 36 held experts; the cell's
  sequences are under 1300 tokens).
- the share: the configuration's experts key counts the experts *held here*
  and ``expert_share`` gives the router's published width and the first held
  expert. The sum runs over the held experts only; what the absent ones would
  add is left out, here as in the program, and that partial result is what
  goes on to the next layer.

The routing margin reported per position is the narrowest, over the layers,
of the gap in softmax probability (over the router's whole width) between
the last expert chosen and the first left out.

Seeded weights: the mixers, the shared expert, the embedding and the norms
as the sibling builds them (bf16 from ``weights.py``'s primitive, four draws
added; ``A`` in 0.01..0.16 so that the state's precision shows). An expert's
three matrices are made the same way from a key of the expert's *global* id
(the share is a slice of the whole model's tree), ``W_out^e`` times
``EXPERT_GAIN``; the router is normal at ``ROUTER_SCALE / sqrt(hidden)`` in
bf16: the configuration's ``assumed`` states both, and the constants below
say why.

**The controls.** ``reference.py`` asks for the control as ``bits=4``, and
the configuration says what that pass is (``check_control``):
``"bf16-state"`` (absent, as in the benchmark's file: the nearest precision
under the stated float32 state, the sibling's control); ``"no-shared"``: the
shared expert left out; ``"drop-<n>"``: the n-th of a token's chosen experts
(1 the largest logit) left out before the softmax over the others, as a
program whose top-k lost that one would; ``"top9"`` is ``drop-<the last>``
under ISSUE 45's name, a route one expert short. The rungs between the first
and the last say how large a routed fault has to be before the check sees it
(the configuration's ``check_why``). The builder reads every control but the
first by giving the probe a copy of the configuration's file with that key
(``probe.py --control --env BENCH_CONFIG_FILE=<copy>``).

The work functions count what the algorithm needs: the weights once a call,
of the held experts those the call's tokens reach (``experts_reached``: not
all 36 at 64 tokens, because the seeded router loads its experts unevenly:
``ROUTE_SKEW``) and the held pairs' flops; per live sequence and
Mamba layer the float32 state read and written once a decode step; K and V
of the attention layers' live contexts.

The load generator's process reads the work functions and may not hold JAX,
so nothing here imports it until a function that needs it is called.
"""

from __future__ import annotations

import functools
import math
import statistics
import time
import types

#: what a configuration's ``check_control`` may say the ``bits=4`` pass is,
#: beside ``drop-<n>`` for each of a token's chosen experts
CONTROLS = ("bf16-state", "top9", "no-shared")
#: the router's logits are this many standard deviations wide (``u`` is
#: normed: unit size). At 2 the ten chosen of 72 lie between ~2.2 and ~4.6
#: and the softmax over them gives the first 37% and the tenth 3%: wide
#: enough that bf16's rounding of the stream seldom changes which ten are
#: chosen, narrow enough that the tenth expert's weight is not noise (a route
#: one expert short, ``top9``, reads 5-8 times the sound runs' median on the
#: chip). At 1 the tenth has 6% and the first 22%
ROUTER_SCALE = 2.0
#: an expert's ``W_out`` over the unit scale. At 1 the held half of the ten
#: experts' weighted sum is a quarter of the shared expert's output, where the
#: published model spends five times the shared expert's parameters on a
#: token's routed experts; at 2, under ``ROUTER_SCALE`` 2, it is two thirds
#: (0.68 on seeded inputs). Read on the chip (PERF.md section 6, PR 45): the
#: sound runs' gaps and ``top9``'s both go as (gain x the tenth's weight)^2,
#: so no setting parts them by more than ~5; at 3 / 1 the sound mean is 0.02
#: and the bf16 state's control, which does not grow with the gain, stands
#: only 2-4 times over it, at 2 / 2 eleven times
EXPERT_GAIN = 2.0

#: how unevenly the seeded router loads its experts: the deviation of the
#: logarithm of an expert's load, taken as log-normal. The seeded stream has a
#: part every token shares (the mixers' and the shared expert's mean output),
#: so a layer's router ranks some experts high for every token and some for
#: next to none. Read on the chip (PERF.md section 6, PR 45): in the
#: reference's routes of 64 sequences the loads' logarithms spread by 0.63 in
#: layer 0 and 1.75 in layer 9, and given its load each expert is reached as
#: by independent tokens (the reached count of n tokens follows from the loads
#: alone, within 0.4 of an expert from n = 2 to 512); the tiles of a decode
#: step's 62.1 live tokens (``mtpu_expert_tile_rows_total``: one 64-row tile a
#: reached expert) cover 33.6, 33.2 and 32.5 of a layer's 36 held experts on
#: three served seeds, where even routing reaches all 36 (``(62/72)^62`` is
#: 1e-4) and the kernel over them read 100.7% of a roofline that counted 36.
#: 1.1 reads 33.0 at 62 tokens; 0 is the even count every other family takes
ROUTE_SKEW = 1.1

jax = jnp = np = R = None


def _sibling():
    """``families/granite_hybrid.py``, loaded as a configuration's family is."""
    import manifest

    return manifest.load_family({"family": "granite_hybrid"})


def _load():
    global jax, jnp, np, R
    G = _sibling()
    G._load()
    if jax is None:
        jax, jnp, np, R = G.jax, G.jnp, G.np, G.R
    return G


# -- sizes and seeded weights --------------------------------------------------


def _dense(config: dict) -> dict:
    """The configuration as the dense sibling reads it: no experts, the
    first ``num_hidden_layers`` layers of a ``layer_types`` kept whole."""
    kinds = list(config["layer_types"])
    n = int(config.get("num_hidden_layers", len(kinds)))
    if len(kinds) < n:
        raise ValueError(f"layer_types names {len(kinds)} layers of {n}")
    return {**config, _sibling()._EXPERTS_KEY: 0, "layer_types": kinds[:n]}


def dims_of(config: dict) -> dict:
    """The sizes the generator and the reference need, from the keys of the
    model's published ``config.json``: the sibling's (of the first
    ``num_hidden_layers`` layers of a ``layer_types`` the file keeps whole;
    its ``ffn`` is the shared expert's width) and the routed half's."""
    G = _sibling()
    held = int(config.get(G._EXPERTS_KEY) or 0)
    if held <= 0:
        raise ValueError(f"{G._EXPERTS_KEY}={held}: this family knows the routed model")
    top_k = int(config["num_experts_per_tok"])
    control = config.get("check_control", CONTROLS[0])
    if control not in CONTROLS and dropped(control, top_k) is None:
        raise ValueError(f"check_control {control!r}: one of {CONTROLS} or drop-<1..{top_k}>")
    share = config.get("expert_share") or {}
    d = {
        **G.dims_of(_dense(config)),
        "control": control,
        "experts": held,
        "router": int(share.get("of", held)),
        "expert_offset": int(share.get("offset", 0)),
        "top_k": top_k,
        "moe_ffn": int(config["intermediate_size"]),
    }
    if not (0 <= d["expert_offset"] <= d["router"] - held and 0 < d["top_k"] <= d["router"]):
        raise ValueError("the held experts or the experts a token lie outside the router's width")
    return d


def program_config(config_file: str):
    """What ``LLMEngine`` is given for this configuration. A program without
    the routed model cannot run the family's cells (a commit from before it
    refuses the file's experts by name, ``NotImplementedError``): the
    sibling's ``_leave_container`` ends its container with nothing left
    behind."""
    try:
        from modal_examples_tpu.models.granite_hybrid import GraniteHybridConfig

        return GraniteHybridConfig.from_hf_config(config_file)
    except (ImportError, NotImplementedError) as e:
        _sibling()._leave_container(e)
        raise


def _keys(key):
    """A layer's key -> (the mixer's and the shared expert's, the router's,
    the experts' three)."""
    k = jax.random.split(key, 5)
    return k[0], k[1], k[2:]


def routed_weights(key, d: dict) -> dict:
    """One layer's router ``[hidden, router]`` and the held experts' three
    stacks, an expert at a time from the key of its global id."""
    G = _load()
    D, F = d["hidden"], d["moe_ffn"]
    _, k_router, k_experts = _keys(key)
    first = d["expert_offset"]

    def stack(k, shape, gain=1.0):
        keys = jax.random.split(k, d["router"])[first:first + d["experts"]]
        return jax.lax.map(lambda e: G._weight(e, shape) * jnp.bfloat16(gain), keys)

    scale = ROUTER_SCALE * D**-0.5
    return {
        "router": (jax.random.normal(k_router, (D, d["router"]), jnp.float32) * scale).astype(
            jnp.bfloat16),
        "moe_gate": stack(k_experts[0], (D, F)), "moe_up": stack(k_experts[1], (D, F)),
        "moe_down": stack(k_experts[2], (F, D), EXPERT_GAIN),
    }


def layer_weights(key, d: dict, kind: str) -> dict:
    """One decoder layer under the program's leaf names: the sibling's layer
    of its kind (the mixer, the norms, ``gate | up | down`` the shared
    expert) and the routed half."""
    G = _load()
    return {**G.layer_weights(_keys(key)[0], d, kind), **routed_weights(key, d)}


def make_tree(seed: int, d: dict) -> dict:
    """The whole tree in one jitted call: ``mamba_layers`` and
    ``attention_layers`` each stacked by kind in the model's order,
    ``moe_layers`` stacked by layer (a ``lax.map`` over the layers, so the
    peak is the tree plus one layer's temporaries)."""
    G = _load()
    d = G._Frozen(d)
    kinds = G._kinds(d)

    @jax.jit
    def build(key):
        k_embed, keys = G._split(key, d)
        tree = G._outer(k_embed, d)
        for kind, rows in kinds.items():
            if rows:
                tree[f"{kind}_layers"] = jax.lax.map(
                    lambda k, kind=kind: G.layer_weights(_keys(k)[0], d, kind),
                    keys[jnp.asarray(rows)],
                )
        tree["moe_layers"] = jax.lax.map(lambda k: routed_weights(k, d), keys)
        return tree

    return build(G.W.root_key(seed))


# -- the plain reference ---------------------------------------------------------


def dropped(control, k: int) -> int | None:
    """Which of a token's ``k`` chosen experts (0 the largest logit) the
    control's pass leaves out, or None where it leaves none out."""
    if control == "top9":
        return k - 1
    n = control[5:] if isinstance(control, str) and control.startswith("drop-") else ""
    return int(n) - 1 if n.isdecimal() and 1 <= int(n) <= k else None


def route(logits, k: int, drop: int | None = None):
    """``logits`` [S, router] -> (the chosen ids, their weights: a softmax
    over the chosen logits; the margin [S]: the gap in softmax probability
    over the router's width between the last of the ``k`` and the first left
    out). ``drop``: the one of the ``k`` (0 the largest) left out before the
    softmax, so that ids and weights are [S, k - 1]."""
    ranked, ids = jax.lax.top_k(logits, k + 1)  # a tie: the lower id
    p = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), ids, axis=-1)
    keep = jnp.asarray([i for i in range(k) if i != drop])
    return ids[:, keep], jax.nn.softmax(ranked[:, keep], axis=-1), p[:, k - 1] - p[:, k]


def _routed(u, w, d, drop: int | None):
    """The held experts' part of the routed sum over u [S, D], every held
    expert's product written out for every token, an expert at a time."""
    ids, p, margin = route(u @ w["router"], d["top_k"], drop)
    combine = (jax.nn.one_hot(ids, d["router"], dtype=jnp.float32) * p[..., None]).sum(axis=1)
    first = d["expert_offset"]
    mine = combine[:, first:first + d["experts"]].T  # [held, S]

    def one(out, expert):
        gate, up, down, weight = expert
        return out + weight[:, None] * ((jax.nn.silu(u @ gate) * (u @ up)) @ down), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (w["moe_gate"], w["moe_up"], w["moe_down"], mine)
    )
    return out, margin


def _layer(x, margin, lw, d, control, kind):
    """One layer over one sequence's stream x [S, D]. ``control``: None, or
    which of ``CONTROLS`` this pass computes."""
    G = _load()
    with jax.default_matmul_precision("highest"):
        w = G._as_f32(lw)
        u = R._rms_norm(x, w["mixer_norm"], d["norm_eps"])
        mantissa = 7 if control == "bf16-state" else 23  # the sibling's: a state carried in bf16
        mixed = G._mamba(u, w, d, mantissa) if kind == "mamba" else G._attention(u, w, d)
        x = x + d["res_mult"] * mixed
        u = R._rms_norm(x, w["mlp_norm"], d["norm_eps"])
        out, m = _routed(u, w, d, dropped(control, d["top_k"]))
        if control != "no-shared":
            out = out + (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]
        return x + d["res_mult"] * out, jnp.minimum(margin, m)


@functools.cache
def _jitted():
    """The layer and one layer's weights as compiled programs (the ends are
    the sibling's)."""
    _load()
    return types.SimpleNamespace(
        layer=jax.jit(_layer, static_argnames=("d", "control", "kind")),
        layer_weights=jax.jit(layer_weights, static_argnames=("d", "kind")),
    )


def logits_at(seed: int, d: dict, sequences: list, rows: list[list[int]],
              bits: int = 8) -> tuple[list, list, dict]:
    """Reference logits of each sequence (token ids, padded by the caller)
    at the given rows, and at those rows the narrowest routing margin over
    the layers. Layers outermost, so each layer's weights are made once, one
    layer at a time, and one sequence at a time goes through it. ``bits``
    other than 8 is the control the configuration names (``check_control``):
    the SSM state carried in bf16, one of the chosen experts left out, or the
    shared expert left out."""
    G = _load()
    jit, ends = _jitted(), G._jitted()
    d = G._Frozen(d)
    control = d["control"] if bits != 8 else None
    clock = {"weights_s": 0.0, "layers_s": 0.0}
    outer = G.outer_weights(seed, d)
    xs = [
        (ends.embed(jnp.asarray(ids), outer, d), jnp.full((len(ids),), jnp.inf))
        for ids in sequences
    ]
    for index, kind in enumerate(d["layer_types"]):
        t0 = time.monotonic()
        lw = jax.block_until_ready(jit.layer_weights(G.layer_key(seed, d, index), d=d, kind=kind))
        t1 = time.monotonic()
        xs = jax.block_until_ready([jit.layer(x, m, lw, d, control, kind) for x, m in xs])
        clock["weights_s"] += t1 - t0
        clock["layers_s"] += time.monotonic() - t1
        del lw
    logits = [np.asarray(ends.head(x[jnp.asarray(r)], outer, d)) for (x, _m), r in zip(xs, rows)]
    margins = [np.asarray(m)[np.asarray(r)] for (_x, m), r in zip(xs, rows)]
    return logits, margins, clock


# -- what the algorithm needs: operations and bytes -----------------------------


def sizes(config: dict) -> dict:
    """The sibling's sizes (layer counts, bytes a weight, a K/V value and a
    state value) with the routed half's."""
    return {**_sibling().sizes(_dense(config)), **dims_of(config)}


def expert_params(s: dict) -> int:
    return 3 * s["hidden"] * s["moe_ffn"]


def held_share(s: dict) -> float:
    """The share of a token's routed pairs that land on held experts,
    routing taken as uniform."""
    return s["experts"] / s["router"]


@functools.cache
def _loads(experts: int) -> tuple[float, ...]:
    """The held experts' loads over the even load: the mid-quantiles of a
    log-normal whose logarithm spreads by ``ROUTE_SKEW``, their mean 1."""
    z = statistics.NormalDist()
    loads = [math.exp(ROUTE_SKEW * z.inv_cdf((i + 0.5) / experts)) for i in range(experts)]
    return tuple(load * experts / sum(loads) for load in loads)


def experts_reached(s: dict, tokens: float) -> float:
    """Expected distinct *held* experts the ``tokens`` tokens of one call
    reach in a layer: each token chooses an expert with ``top_k / router``
    times the expert's load, the tokens independently (one token reaches its
    own ``top_k * held / router``; many reach every held expert)."""
    even = s["top_k"] / s["router"]
    return sum(
        1.0 - (1.0 - min(1.0, even * load)) ** max(tokens, 0.0) for load in _loads(s["experts"])
    )


def _fixed_params(s: dict) -> float:
    """Matmul parameters every token multiplies outside the routed experts
    and the head: the mixers, the shared expert, the router."""
    G = _sibling()
    return (s["mamba_layers"] * G.mixer_params(s) + s["attn_layers"] * G.attn_params(s)
            + s["layers"] * (G.mlp_params(s) + s["hidden"] * s["router"]))


def params_per_token(s: dict) -> float:
    """... and with the held experts a token's pairs reach here."""
    return _fixed_params(s) + s["layers"] * expert_params(s) * s["top_k"] * held_share(s)


def weight_bytes(s: dict, tokens: float) -> float:
    """Weight bytes a call over ``tokens`` tokens has to read once: every
    layer's mixer, shared expert and router, the held experts the tokens
    reach, the per-channel vectors of the Mamba mixers, and the tied
    embedding as the head."""
    small = s["mamba_layers"] * s["conv_dim"] * (s["m_conv"] + 1) * 2.0
    routed = s["layers"] * expert_params(s) * experts_reached(s, tokens)
    return (_fixed_params(s) + routed + s["hidden"] * s["vocab"]) * s["wbytes"] + small


def held_weight_bytes(s: dict) -> float:
    """... and every weight the chip holds, every held expert among them."""
    return weight_bytes(s, float("inf"))


def decode_step(config: dict, batch: float, context_tokens: float) -> dict:
    """One decode step of ``batch`` sequences whose contexts hold
    ``context_tokens`` tokens together: the weights once (the held experts
    the batch reaches), each sequence's recurrent state read and written,
    the attention layers' live K and V."""
    G, s = _sibling(), sizes(config)
    flops = 2.0 * (params_per_token(s) + s["hidden"] * s["vocab"]) * batch
    flops += s["mamba_layers"] * G._step_flops(s) * batch
    flops += s["attn_layers"] * G._attn_position_flops(s) * context_tokens
    nbytes = weight_bytes(s, batch) + 2.0 * G.state_bytes_per_sequence(s) * batch
    nbytes += G.kv_bytes_per_token(s) * (context_tokens + batch)
    nbytes += batch * s["hidden"] * 2.0  # embedding rows
    return {"flops": flops, "bytes": nbytes}


def prefill(config: dict, prompt_lengths: list[int], calls: float) -> dict:
    """Prefill of prompts of the given lengths in ``calls`` program calls:
    the weights once a call (the held experts a call's tokens reach), the
    chunked scan and causal attention over each prompt, K/V and each
    prompt's final state written once."""
    G, s = _sibling(), sizes(config)
    tokens = float(sum(prompt_lengths))
    flops = 2.0 * params_per_token(s) * tokens
    flops += 2.0 * s["hidden"] * s["vocab"] * len(prompt_lengths)  # the head: last rows only
    flops += s["mamba_layers"] * G._scan_flops(s) * tokens
    flops += s["attn_layers"] * G._attn_position_flops(s) * G._causal_pairs(prompt_lengths)
    nbytes = calls * weight_bytes(s, tokens / max(calls, 1.0)) + G.kv_bytes_per_token(s) * tokens
    nbytes += G.state_bytes_per_sequence(s) * len(prompt_lengths)
    return {"flops": flops, "bytes": nbytes}


def expert_scan(config: dict, tokens: float, calls: float) -> dict | None:
    """``mtpu.expert_scan``: the held routed experts' SwiGLU over ``tokens``
    tokens in ``calls`` program calls of one kind: ``top_k * held / router``
    pairs a token at the real pairs' flops, each call reading once the held
    experts its tokens reach (a decode step of 64: ~33 of a layer's 36, once a
    layer a step), every pair's activations in and out in bf16."""
    s = sizes(config)
    if tokens <= 0 or calls <= 0:
        return None
    pairs = s["top_k"] * held_share(s) * tokens
    flops = 2.0 * expert_params(s) * pairs * s["layers"]
    reached = experts_reached(s, tokens / calls)
    nbytes = calls * s["layers"] * expert_params(s) * reached * s["wbytes"]
    nbytes += s["layers"] * pairs * 2.0 * s["hidden"] * 2.0
    return {"flops": flops, "bytes": nbytes}


def _mixers(name: str):
    """The sibling's count of the work under one of the mixers' scopes, or
    of the shared expert's (its ``dense_mlp`` at ``shared_intermediate_size``):
    the same layers, heads, state and widths, read from the dense view."""

    def work(config: dict, tokens: float, calls: float, **attended) -> dict | None:
        return getattr(_sibling(), name)(_dense(config), tokens, calls, **attended)

    return work


#: ``mtpu.*`` scope -> ``fn(config, tokens, calls)``: the needed work under it
#: of one kind of program call. ``ssm_step`` is the decode steps' alone and
#: ``ssm_scan`` the prefill calls' (``layers/recurrent.py`` hands each its
#: phase); ``attention`` wants besides what it attends to
SCOPE_WORK = {
    "mtpu.expert_scan": expert_scan, "mtpu.ssm_step": _mixers("ssm_step"),
    "mtpu.ssm_scan": _mixers("ssm_scan"), "mtpu.dense_mlp": _mixers("dense_mlp"),
    "mtpu.attention": _mixers("attention"),
}
