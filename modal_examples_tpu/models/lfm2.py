"""LFM2 (``lfm2_moe``, LFM2-24B-A2B): gated short-convolution layers with a
few rotary GQA layers between them, leading dense SwiGLU layers, then many
small experts chosen by a sigmoid router with a selection bias; served on the
engine's normal path.

Three kinds of block in one model, and the layer pattern is data
(``cfg.layer_types``, ``cfg.n_dense_layers``): *convolution + dense MLP*,
*attention + experts*, *convolution + experts*. Two kinds of state live side
by side (docs/recurrent_state.md):

- an attention layer keeps K and V per *token* in the two paged leaves of
  ``PagedKVCache``, which cover ``cfg.n_cache_layers`` layers (the attention
  layers only), two K/V heads of 64 to a 128-wide page row as Granite's
  (``granite_hybrid._fold_kv``);
- a convolution layer keeps per *sequence* the last ``conv_L_cache - 1``
  gated inputs of its convolution, the **window**: one per-slot leaf
  ``[n_conv_layers, max_slots, conv_L_cache - 1, dim]`` in the activations'
  dtype (``cfg.state_leaves``), addressed by slot, with no page axis.

The convolution mixer (``mtpu.conv_mix``): ``[B, C, u] = split3(W_in x_t)``;
``g_t = B_t * u_t``; ``c_t = sum_j k_j g_{t - (K - 1) + j}`` per channel
(depthwise, causal, ``g`` before the sequence's start 0, no bias, no
activation); ``out_t = W_out (C_t * c_t)``. **Prefill** computes it as ``K``
shifted multiply-adds over the call's rows, starting from zeros or, where a
chunk continues a prompt, from the window the chunk before left in the slot,
and leaves in the slot the window of the row's *last real token* (taken at
the row's own length, so padding never enters it). **Decode** is the
one-token form over all ``max_slots`` rows at once: the window and the new
``g`` are the three taps' inputs, the window shifts by one; a row whose slot
is not decoding keeps its window, so a slot whose first token is not
harvested yet, or whose prompt is between two chunk calls, keeps what its
prefill wrote. ``g`` is rounded to the window's dtype before either form
multiplies it, so a token computed by decode sees the numbers prefill would
have seen; the taps' sum is float32.

The attention mixer: bias-free projections, an RMSNorm of q and of k over
each head's width, rotary embedding over the whole head (half-split
rotation), causal softmax at ``head_dim ** -0.5``, GQA. Prefill runs the
flash kernel, decode the chunked loop over the pages (a 64-wide head is not
the ragged kernel's: ``paged_impl_plan`` says which form runs).

The routed layer: ``s = sigmoid(h W_g)`` in float32 over all experts, the
``top_k`` of ``s + b`` chosen, weighted by their ``s`` renormalised times
``routed_scaling_factor`` (``moe.route_group_limited`` with ``bias=``, GLM's
route at one group), then only the chosen pairs through
``moe.moe_swiglu_sparse``, the experts' stacks kept ``[L, E, ...]`` and
indexed ``[layer, expert]`` where a tile multiplies. Every expert is held.
The layer also counts, from the route's ids, the real pairs and the tile rows
the loop computes for them (``cfg.counts_expert_tile_rows``:
``mtpu_expert_tile_rows_total``).

The layers of a kind are stacked (``conv_layers``, ``attention_layers``,
``dense_layers``, ``moe_layers``: a layer has a row in one mixer stack and in
one feed-forward stack); runs of convolution layers with one kind of
feed-forward are a ``lax.scan`` that indexes ``[row]`` out of the whole
stacks and, in decode, out of the whole window leaf, updated in place.

Departures from the published ``modeling_lfm2_moe``, none of which changes a
result beyond float32 rounding: the renormalisation adds 1e-20 where the
published code adds 1e-6 (the reference adds 1e-6; the weights then differ by
5e-7 of themselves, under every tolerance a test or the benchmark's check
holds: tests/test_lfm2.py shows it); ``lm_head`` is the embedding (tied).
The plain reference is ``models/lfm2_reference.py``.

What this model does not do yet is refused by name where the engine is built
(``Lfm2Config.unsupported``).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from ..ops import is_quantized, kv_gather, paged_decode_attention_chunked
from ..ops import scopes as _scopes
from ..ops.flash_attention import flash_attention, flash_attention_chunked
from . import granite_hybrid as _hybrid
from . import layers
from . import moe as _moe
from .layers import refuse
from .layers import scatter_rows as _scatter_rows

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    layer_types: tuple = (CONV, CONV, ATTENTION, CONV) * 10
    n_heads: int = 32
    n_kv_heads: int = 8
    conv_L_cache: int = 3  # the convolution's taps; the window holds one fewer
    n_dense_layers: int = 2  # leading layers with a dense SwiGLU
    ffn_dim: int = 11776  # ... of this width
    moe_ffn_dim: int = 1536
    n_experts: int = 64
    top_k_experts: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    dtype: str = "bfloat16"
    tie_embeddings: bool = True

    #: features of the engine this model's programs do not implement yet:
    #: ``LLMEngine`` refuses each by name where it is asked for
    unsupported = (
        "prefix caching", "int8 KV cache", "speculative decoding",
        "disaggregated transfer", "tensor parallelism",
        "LoRA", "vision", "a Pallas paged_impl or scatter_impl",
    )
    #: ``decode_step(return_counts=True)`` hands back [pairs, tile rows]
    counts_expert_tile_rows = True

    def __post_init__(self):
        bad = set(self.layer_types) - {CONV, ATTENTION}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: only {CONV!r} and {ATTENTION!r}")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide the width and their groups")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers lies outside the layers")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache < 2: a convolution of one tap keeps no window")
        if not self.tie_embeddings:
            raise NotImplementedError("Lfm2Config: an output head of its own")

    # -- the seam LLMEngine reads (docs/mla.md) ---------------------------------

    @property
    def model(self):
        """The module that holds this configuration's programs."""
        return sys.modules[__name__]

    @property
    def kv_fold(self) -> int:
        """K/V heads to one page row (``granite_hybrid.kv_fold``: 2 of 64)."""
        return _hybrid.kv_fold(self.n_kv_heads, self.head_dim)

    @property
    def cache_leaf_shapes(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """Per-token shape of the two paged leaves: K and V of an attention
        layer, ``kv_fold`` heads to a row."""
        return ((self.n_kv_heads // self.kv_fold, self.kv_fold * self.head_dim),) * 2

    @property
    def n_cache_layers(self) -> int:
        """Layers the paged leaves cover: the attention layers."""
        return self.layer_types.count(ATTENTION)

    @property
    def state_leaves(self) -> tuple:
        """The per-slot leaf the cache keeps beside its pages, ``(layers,
        per-slot shape, dtype)``: the window of every convolution layer, its
        last ``conv_L_cache - 1`` gated inputs."""
        n = self.layer_types.count(CONV)
        return ((n, (self.conv_L_cache - 1, self.dim), self.dtype),) if n else ()

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import LFM2_TARGETS

        return LFM2_TARGETS

    # -- sizes -------------------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def segments(self) -> tuple:
        """The layers as runs: ``(mixer kind, first row in the mixer's stack,
        dense feed-forward?, first row in the feed-forward's stack, count)``,
        in order. A run holds one kind of mixer and one of feed-forward; an
        attention layer is a run of one."""
        out, seen = [], {CONV: 0, ATTENTION: 0}
        for i, kind in enumerate(self.layer_types):
            dense = i < self.n_dense_layers
            if out and kind == CONV and out[-1][0] == CONV and out[-1][2] == dense:
                out[-1][4] += 1
            else:
                ffn_row = i if dense else i - self.n_dense_layers
                out.append([kind, seen[kind], dense, ffn_row, 1])
            seen[kind] += 1
        return tuple(tuple(s) for s in out)

    @property
    def param_count(self) -> int:
        D, hd = self.dim, self.head_dim
        conv = D * 3 * D + D * D + self.conv_L_cache * D + D
        attn = D * (self.n_heads + 2 * self.n_kv_heads) * hd + self.n_heads * hd * D + 2 * hd + D
        dense = 3 * D * self.ffn_dim + D
        moe = self.n_experts * 3 * D * self.moe_ffn_dim + D * self.n_experts + self.n_experts + D
        n_conv = self.layer_types.count(CONV)
        return (
            self.vocab_size * D + D + n_conv * conv + (self.n_layers - n_conv) * attn
            + self.n_dense_layers * dense + self.n_moe_layers * moe
        )

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "Lfm2Config":
        """Test-tier config: every kind of block (two dense layers, a
        convolution and an attention layer among them, then attention and
        runs of convolution over experts), 8 experts 2 a token."""
        base = dict(
            vocab_size=vocab_size, dim=64,
            layer_types=(CONV, ATTENTION, CONV, CONV, ATTENTION, CONV),
            n_heads=4, n_kv_heads=2, n_dense_layers=2, ffn_dim=128, moe_ffn_dim=32,
            n_experts=8, top_k_experts=2, rope_theta=10000.0, max_seq_len=512,
        )
        base.update(overrides)
        return Lfm2Config(**base)

    @staticmethod
    def from_hf_config(path: str | Path) -> "Lfm2Config":
        """From a published ``config.json`` (``model_type`` ``lfm2_moe``). A
        file that runs the first layers of the published stack keeps
        ``layer_types`` whole and says how many in ``num_hidden_layers``."""
        cfg = json.loads(Path(path).read_text())
        for key, want in (("conv_bias", False), ("use_expert_bias", True)):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"Lfm2Config: {key}={cfg[key]!r} is not modelled (only {want!r})"
                )
        n = int(cfg["num_hidden_layers"])
        kinds = tuple(cfg["layer_types"])
        if len(kinds) < n:
            raise ValueError(f"layer_types names {len(kinds)} layers of {n}")
        rope = cfg.get("rope_parameters") or {}
        return Lfm2Config(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            layer_types=kinds[:n],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            conv_L_cache=cfg["conv_L_cache"],
            n_dense_layers=min(int(cfg.get("num_dense_layers", 0)), n),
            ffn_dim=cfg["intermediate_size"],
            moe_ffn_dim=cfg["moe_intermediate_size"],
            n_experts=cfg["num_experts"],
            top_k_experts=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 1000000.0))),
            norm_eps=cfg.get("norm_eps", 1e-5),
            max_seq_len=cfg.get("max_position_embeddings", 4096),
            tie_embeddings=cfg.get("tie_word_embeddings", cfg.get("tie_embedding", True)),
        )


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: Lfm2Config) -> dict:
    """Random init; the layers of a kind stacked on axis 0. The selection
    bias is drawn, not zero (a program that chose by the unbiased score would
    choose other experts), the taps uniform in a depthwise conv1d's range."""
    dt = cfg.jnp_dtype
    D, hd, K = cfg.dim, cfg.head_dim, cfg.conv_L_cache

    def dense(k, *shape):
        return layers.init_dense(k, shape, dtype=dt)

    keys = jax.random.split(key, 5)
    params = {
        "embed": layers.init_dense(keys[0], (cfg.vocab_size, D), scale=D**-0.5, dtype=dt),
        "final_norm": jnp.ones((D,), dt),
    }
    L = cfg.layer_types.count(CONV)
    if L:
        k = jax.random.split(keys[1], 3)
        params["conv_layers"] = {
            "mixer_norm": jnp.ones((L, D), dt),
            "in_proj": dense(k[0], L, D, 3 * D),
            "conv_w": jax.random.uniform(k[1], (L, K, D), jnp.float32, -(K**-0.5), K**-0.5).astype(dt),
            "out_proj": dense(k[2], L, D, D),
        }
    L = cfg.n_cache_layers
    if L:
        k = jax.random.split(keys[2], 4)
        params["attention_layers"] = {
            "mixer_norm": jnp.ones((L, D), dt),
            "wq": dense(k[0], L, D, cfg.n_heads * hd),
            "wk": dense(k[1], L, D, cfg.n_kv_heads * hd),
            "wv": dense(k[2], L, D, cfg.n_kv_heads * hd),
            "wo": dense(k[3], L, cfg.n_heads * hd, D),
            "q_norm": jnp.ones((L, hd), dt),
            "k_norm": jnp.ones((L, hd), dt),
        }
    L = cfg.n_dense_layers
    if L:
        k = jax.random.split(keys[3], 3)
        params["dense_layers"] = {
            "mlp_norm": jnp.ones((L, D), dt), "gate": dense(k[0], L, D, cfg.ffn_dim),
            "up": dense(k[1], L, D, cfg.ffn_dim), "down": dense(k[2], L, cfg.ffn_dim, D),
        }
    L = cfg.n_moe_layers
    if L:
        k = jax.random.split(keys[4], 5)
        E, F = cfg.n_experts, cfg.moe_ffn_dim
        params["moe_layers"] = {
            "mlp_norm": jnp.ones((L, D), dt),
            "router": dense(k[0], L, D, E),
            "router_bias": 0.1 * jax.random.normal(k[1], (L, E), jnp.float32),
            "moe_gate": dense(k[2], L, E, D, F), "moe_up": dense(k[3], L, E, D, F),
            "moe_down": dense(k[4], L, E, F, D),
        }
    return params


def partition_specs(cfg: Lfm2Config) -> dict:
    refuse(cfg, "tensor parallelism")
    raise NotImplementedError("Lfm2Config has no partition specs")


#: published tensor names under ``model.layers.N.``, as far as the catalog's
#: config and the ``lfm2_moe`` modelling code imply them: ours -> theirs
HF_LAYER_NAMES = {
    "mixer_norm": "operator_norm.weight",
    "mlp_norm": "ffn_norm.weight",
    "in_proj": "conv.in_proj.weight",
    "conv_w": "conv.conv.weight",
    "out_proj": "conv.out_proj.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
    "q_norm": "self_attn.q_layernorm.weight", "k_norm": "self_attn.k_layernorm.weight",
    "gate": "feed_forward.w1.weight", "up": "feed_forward.w3.weight",
    "down": "feed_forward.w2.weight",
    "router": "feed_forward.gate.weight", "router_bias": "feed_forward.expert_bias",
    "moe_gate": "feed_forward.experts.{e}.w1.weight",
    "moe_up": "feed_forward.experts.{e}.w3.weight",
    "moe_down": "feed_forward.experts.{e}.w2.weight",
}
_MIXER_LEAVES = {
    CONV: ("mixer_norm", "in_proj", "conv_w", "out_proj"),
    ATTENTION: ("mixer_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}
_DENSE_LEAVES = ("mlp_norm", "gate", "up", "down")
_MOE_LEAVES = ("mlp_norm", "router", "router_bias") + _moe.EXPERT_LEAVES


def load_hf_weights(model_dir, cfg: Lfm2Config, *, quantization=None, dtype=None) -> dict:
    """The published checkpoint (``*.safetensors`` under ``model_dir``) as
    this module's tree: ``HF_LAYER_NAMES`` under ``model.layers.N.`` for the
    first ``cfg.n_layers`` layers, a torch ``Linear`` ``[out, in]``
    transposed, ``conv.conv.weight`` ``[dim, 1, taps]`` as ``[taps, dim]``,
    the experts stacked, the vocabulary's first ``vocab_size`` rows, the
    final norm ``model.embedding_norm``. ``in_proj`` stays whole: its output
    is ``[B | C | u]`` in that order."""
    import numpy as np
    from safetensors import safe_open

    from .quantize import bits_of, quantize_weight_host

    files = sorted(Path(model_dir).glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")
    where = {}
    for f in files:
        with safe_open(str(f), framework="np") as st:
            where.update({name: f for name in st.keys()})

    def get(name):
        with safe_open(str(where[name]), framework="np") as st:
            return np.asarray(st.get_tensor(name), np.float32)

    dt = jnp.dtype(dtype or cfg.dtype)
    targets = cfg.quant_targets if quantization else ()

    def one(ours, prefix):
        theirs = HF_LAYER_NAMES[ours]
        if "{e}" in theirs:
            return np.stack([get(prefix + theirs.format(e=e)).T for e in range(cfg.n_experts)])
        a = get(prefix + theirs)
        if ours == "conv_w":
            return a[:, 0, :].T
        return a.T if a.ndim == 2 else a

    def stack(layer_ids, names):
        out = {}
        for ours in names:
            full = np.stack([one(ours, f"model.layers.{i}.") for i in layer_ids])
            if ours in targets:
                out[ours] = quantize_weight_host(full, bits_of(quantization))
            else:
                out[ours] = jnp.asarray(full, jnp.float32 if ours == "router_bias" else dt)
        return out

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight")[: cfg.vocab_size], dt),
        "final_norm": jnp.asarray(get("model.embedding_norm.weight"), dt),
    }
    for kind, stack_name in ((CONV, "conv_layers"), (ATTENTION, "attention_layers")):
        ids = [i for i, t in enumerate(cfg.layer_types) if t == kind]
        if ids:
            params[stack_name] = stack(ids, _MIXER_LEAVES[kind])
    if cfg.n_dense_layers:
        params["dense_layers"] = stack(range(cfg.n_dense_layers), _DENSE_LEAVES)
    if cfg.n_moe_layers:
        params["moe_layers"] = stack(range(cfg.n_dense_layers, cfg.n_layers), _MOE_LEAVES)
    return params


def paged_impl_plan(
    cfg: Lfm2Config, page_size: int, impl: str | None = None,
    scatter_impl: str = "xla", *, kv_dtype="bfloat16", mesh=None, warn: bool = True,
    expert_dtype=None,
) -> dict:
    """What runs for this model, chosen from what can be seen here and by no
    option. Attention: the chunked XLA loop over the attention layers' pages
    and the XLA scatter (the ragged kernel wants a head of 128:
    ops.paged_attention.ragged_shapes_ok; this model's is ``head_dim``);
    anything else is refused here. The convolution's window step
    (``state_step``): XLA's, three multiply-adds and a shift of 8 KB a slot.
    The routed experts' tile loop in a decode step (``expert_scan``):
    ``moe.expert_scan_form``'s choice for experts of ``expert_dtype`` (unset:
    the model's own), the grouped-matmul kernel on a TPU, XLA's loop
    elsewhere."""
    from ..ops.kv_quant import resolve_kv_dtype

    if impl not in (None, "xla") or scatter_impl != "xla":  # unset: as "xla"
        refuse(cfg, "a Pallas paged_impl or scatter_impl")
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    kvd = resolve_kv_dtype(kv_dtype)
    if kvd == "int8":
        refuse(cfg, "int8 KV cache")
    return {
        "attention": "xla-gather", "ragged_variant": None, "scatter": "xla",
        "kv_dtype": str(kvd), "tp": 1, "downgraded": [],
        "state_step": "xla" if cfg.state_leaves else None,
        "expert_scan": _moe.expert_scan_form(
            1, cfg.dim, cfg.moe_ffn_dim, expert_dtype or cfg.dtype
        ) if cfg.n_moe_layers else None,
    }


# -- the layer's parts ------------------------------------------------------------

_row = _hybrid._row


def _taps(layer):
    return layer["conv_w"].astype(jnp.float32)  # [K, D]


def _gates(layer, u, dt):
    """u [..., D] (normed) -> ``g = B * u'`` in the window's dtype ``dt`` and
    ``C`` in float32, from ``[B | C | u'] = W_in u``."""
    D = u.shape[-1]
    bcx = layers.mm(u, layer["in_proj"])
    g = (bcx[..., :D] * bcx[..., 2 * D:]).astype(dt)
    return g, bcx[..., D:2 * D]


@jax.named_scope(_scopes.CONV_MIX)
def _conv_prefill(layer, u, lens, window0):
    """The convolution mixer over whole rows. u [b, T, D] (normed), lens [b],
    window0 [b, K - 1, D]: what came before the rows' first positions.
    Returns (out [b, T, D], the window after each row's last real token)."""
    T = u.shape[1]
    g, C = _gates(layer, u, window0.dtype)
    ext = jnp.concatenate([window0, g], axis=1)  # [b, T + K - 1, D]
    w = _taps(layer)
    K = w.shape[0]
    c = sum(w[j] * ext[:, j:j + T].astype(jnp.float32) for j in range(K))
    # the window at the row's own length: ext rows lens .. lens + K - 2
    rows = lens[:, None] + jnp.arange(K - 1)[None, :]
    window = jnp.take_along_axis(ext, rows[..., None], axis=1)
    return layers.mm((C * c).astype(u.dtype), layer["out_proj"]), window


@jax.named_scope(_scopes.CONV_MIX)
def _conv_step(layer, u, live, windows, i):
    """The mixer's one-token form over every slot. u [S, D] (normed), live
    [S] bool; ``windows`` [L, S, K - 1, D] is the whole per-slot leaf, of
    which layer ``i`` is read and written in place. A row that is not live
    keeps its window. Returns (out [S, D], windows)."""
    window = windows[i]
    g, C = _gates(layer, u, window.dtype)
    full = jnp.concatenate([window, g[:, None, :]], axis=1)  # [S, K, D]
    c = jnp.einsum("skd,kd->sd", full.astype(jnp.float32), _taps(layer))
    windows = windows.at[i].set(jnp.where(live[:, None, None], full[:, 1:], window))
    return layers.mm((C * c).astype(u.dtype), layer["out_proj"]), windows


def _rope(x, cos, sin):
    """x [..., heads, hd] rotated by the tables [..., hd / 2] of its leading
    positions: the half-split rotation (``rotate_half``), in float32."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _qkv(layer, h, cos, sin, cfg):
    """h [..., D] (normed) -> q [..., Hq, hd], k and v [..., Hkv, hd]; q and
    k normed over each head's width, then rotated."""
    dt, hd = h.dtype, cfg.head_dim
    q = layers.mm(h, layer["wq"]).astype(dt).reshape(*h.shape[:-1], cfg.n_heads, hd)
    k = layers.mm(h, layer["wk"]).astype(dt).reshape(*h.shape[:-1], cfg.n_kv_heads, hd)
    v = layers.mm(h, layer["wv"]).astype(dt).reshape(*h.shape[:-1], cfg.n_kv_heads, hd)
    q = _rope(layers.rms_norm(q, layer["q_norm"], cfg.norm_eps), cos, sin)
    k = _rope(layers.rms_norm(k, layer["k_norm"], cfg.norm_eps), cos, sin)
    return q, k, v


def route(layer, x, cfg):
    """x [T, D] -> (weights [T, k] f32, expert ids [T, k]): each expert
    scored on its own in float32, chosen with the layer's selection bias,
    weighted by the unbiased scores renormalised."""
    with jax.named_scope(_scopes.ROUTER):
        logits = jnp.einsum(
            "td,de->te", x.astype(jnp.float32), layer["router"].astype(jnp.float32)
        )
        return _moe.route_group_limited(
            jax.nn.sigmoid(logits), cfg.top_k_experts, scale=cfg.routed_scaling_factor,
            renormalize=cfg.norm_topk_prob, bias=layer["router_bias"],
        )


def tile_rows(ids, token_mask, n_experts: int, tile: int):
    """[pairs, rows] int32: the (token, expert) pairs of the counted tokens,
    and the rows of the tiles ``moe_swiglu_sparse`` computes for them (each
    reached expert's pairs padded to whole tiles of ``tile`` rows)."""
    eid = ids if token_mask is None else jnp.where(token_mask[:, None], ids, n_experts)
    per_expert = jnp.bincount(eid.reshape(-1), length=n_experts + 1)[:n_experts]
    rows = jnp.sum((per_expert + tile - 1) // tile * tile)
    return jnp.stack([jnp.sum(per_expert), rows]).astype(jnp.int32)


def _ffn_row(params, dense: bool, i):
    """Row ``i`` of the feed-forward's stack; a routed layer gets the
    experts' whole stacks and its own index into them (``moe.scan_layers``
    says why)."""
    if dense:
        return _row(params["dense_layers"], i)
    stack = params["moe_layers"]
    small = _row({k: v for k, v in stack.items() if k not in _moe.EXPERT_LEAVES}, i)
    return dict(small, **{k: stack[k] for k in _moe.EXPERT_LEAVES}, expert_layer=i)


def _ffn(layer, x, cfg, dense: bool, token_mask):
    """``x + FFN(RMSNorm(x))`` and the routed layer's [pairs, tile rows]."""
    h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    if dense:
        out = layers.swiglu_mlp({k: layer[k] for k in ("gate", "up", "down")}, h)
        return x + out.astype(x.dtype), jnp.zeros((2,), jnp.int32)
    flat = h.reshape(-1, cfg.dim)
    mask = None if token_mask is None else token_mask.reshape(-1)
    weights, ids = route(layer, flat, cfg)
    out, _ = _moe.moe_swiglu_sparse(
        *(layer[n] for n in _moe.EXPERT_LEAVES), flat, ids, weights,
        token_mask=mask, layer=layer["expert_layer"],
    )
    counts = tile_rows(
        ids, mask, cfg.n_experts,
        _moe.expert_tile(flat.shape[0], cfg.top_k_experts, cfg.n_experts),
    )
    return x + out.astype(x.dtype).reshape(x.shape), counts


def _logits(params, x, cfg):
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return layers.mm(x, params["embed"].T)


def _check_serving(cfg, k_pages, mesh, input_embeds=None):
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    if input_embeds is not None:
        refuse(cfg, "vision")
    if is_quantized(k_pages):
        refuse(cfg, "int8 KV cache")


# -- forward, for tests and tools -----------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: Lfm2Config, *,
            attn_impl: str = "flash", lora=None):
    """Full-sequence forward of the program's own layers, no cache: [B, S] ->
    logits [B, S, vocab]."""
    if lora is not None:
        refuse(cfg, "LoRA")
    B, S = tokens.shape
    logits, _, _, _ = _prefill_impl(
        params, tokens, None, None, None, jnp.full((B,), S, jnp.int32), cfg,
        q_offset=0, attn_impl=attn_impl, state=None, slot_ids=None, all_logits=True,
    )
    return logits


# -- serving: prefill + paged decode ----------------------------------------


def _prefill_impl(params, tokens, k_pages, v_pages, page_tables, lens, cfg, *,
                  q_offset: int, attn_impl: str, state, slot_ids, all_logits: bool = False):
    """``lens`` valid tokens of [B, C] at global positions from ``q_offset``
    on. With pages and state: writes K/V of the attention layers, starts the
    convolutions from zeros (``q_offset`` 0) or from the rows' slots, and
    leaves each row's window after its last real token in its slot."""
    B, C = tokens.shape
    cached = k_pages is not None
    valid = jnp.arange(C)[None, :] < lens[:, None]
    n_conv = cfg.layer_types.count(CONV)
    if cached and q_offset:
        (window_in,) = _hybrid._gather_state(state, slot_ids)
    else:
        window_in = jnp.zeros((n_conv, B, cfg.conv_L_cache - 1, cfg.dim), cfg.jnp_dtype)
    positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
    cos, sin = layers.rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
    if cached:
        page_size = k_pages.shape[2]
        page_idx = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
        page_idx = jnp.where(valid, page_idx, 0)
        slot = jnp.where(valid, positions % page_size, 0)
        n_prefix_pages = q_offset // page_size
        prefix_tables = page_tables[:, :n_prefix_pages]

    def attention_mixer(x, j):
        layer = _row(params["attention_layers"], j)
        u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, u, cos, sin, cfg)
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [B, heads, C, hd]
        k_all, v_all = k, v
        if cached and n_prefix_pages:
            def prefix(pages):
                got = kv_gather(pages, prefix_tables, layer=j, dtype=k.dtype)
                got = got.reshape(*got.shape[:3], cfg.n_kv_heads, cfg.head_dim)  # unfolded
                return got.transpose(0, 3, 1, 2, 4).reshape(B, cfg.n_kv_heads, q_offset, -1)

            k_all = jnp.concatenate([prefix(k_pages), k], axis=2)
            v_all = jnp.concatenate([prefix(v_pages), v], axis=2)
        with jax.named_scope(_scopes.ATTENTION):
            if attn_impl == "flash" and not q_offset:
                o = flash_attention(q, k_all, v_all, True, cfg.softmax_scale)
            elif attn_impl == "flash":
                o = flash_attention_chunked(
                    q, k_all, v_all, q_offset=q_offset, sm_scale=cfg.softmax_scale
                )
            else:
                from ..ops import reference as _ops_ref

                o = _ops_ref.attention_chunked(
                    q, k_all, v_all, q_offset=q_offset, sm_scale=cfg.softmax_scale
                )
        o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.head_dim)
        return x + layers.mm(o, layer["wo"]).astype(x.dtype), (k, v)

    x = params["embed"][tokens]
    windows, ks, vs = [], [], []
    for kind, first, dense, ffn_first, count in cfg.segments:
        if kind == ATTENTION:
            x, (k, v) = attention_mixer(x, first)
            x, _ = _ffn(_ffn_row(params, dense, ffn_first), x, cfg, dense, valid)
            ks.append(k)
            vs.append(v)
            continue

        def conv_layer(x, scanned, first=first, dense=dense, ffn_first=ffn_first):
            j, window0 = scanned
            layer = _row(params["conv_layers"], first + j)
            u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
            mixed, window = _conv_prefill(layer, u, lens, window0)
            x, _ = _ffn(
                _ffn_row(params, dense, ffn_first + j), x + mixed.astype(x.dtype), cfg,
                dense, valid,
            )
            return x, window

        x, window = jax.lax.scan(
            conv_layer, x, (jnp.arange(count), window_in[first:first + count])
        )
        windows.append(window)
    if cached:
        if ks:
            # [La, B, Hkv, C, hd] -> the block [La, B, C, Hkv, hd] at (page, slot)
            rows = (
                _hybrid._fold_kv(jnp.stack(a).transpose(0, 1, 3, 2, 4), cfg) for a in (ks, vs)
            )
            k_pages, v_pages = (
                _scatter_rows(pages, r, page_idx, slot) for pages, r in zip((k_pages, v_pages), rows)
            )
        if windows:
            state = (_hybrid._scatter_state(state[0], jnp.concatenate(windows), slot_ids),)
    if all_logits:
        return _logits(params, x, cfg), k_pages, v_pages, state
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None].repeat(x.shape[-1], -1), 1)[:, 0]
    return _logits(params, x_last, cfg), k_pages, v_pages, state


def prefill(
    params: dict,
    tokens: jax.Array,  # [B, S] padded
    k_pages: jax.Array,  # [n_cache_layers, n_pages, page_size, Hkv / fold, fold * hd]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    seq_lens: jax.Array,  # [B] true lengths
    cfg: Lfm2Config,
    attn_impl: str = "flash",
    input_embeds=None,
    mesh=None,
    *,
    state: tuple,  # the cache's per-slot leaf, (windows,)
    slot_ids: jax.Array,  # [B] the rows' slots; out of range: a row with none
):
    """Process prompts from their first token: fills the attention layers'
    pages and leaves each row's window, from zeros, in its slot. Returns
    (logits_last, k_pages, v_pages, state)."""
    _check_serving(cfg, k_pages, mesh, input_embeds)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, page_tables, seq_lens, cfg,
        q_offset=0, attn_impl=attn_impl, state=state, slot_ids=slot_ids,
    )


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # [B, C] — one chunk of the prompt
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,
    chunk_lens: jax.Array,  # [B] valid tokens in THIS chunk
    cfg: Lfm2Config,
    *,
    q_offset: int,  # global position of the chunk's first token (static)
    attn_impl: str = "flash",
    mesh=None,
    state: tuple,
    slot_ids: jax.Array,
):
    """One chunk of a long prompt: the attention layers attend to the cached
    prefix and the chunk, the convolutions go on from the window the previous
    chunk call left in the rows' slots (from zeros at offset 0)."""
    _check_serving(cfg, k_pages, mesh)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, page_tables, chunk_lens, cfg,
        q_offset=q_offset, attn_impl=attn_impl, state=state, slot_ids=slot_ids,
    )


def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 — current token per slot
    positions: jax.Array,  # [B] int32 — its position
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool — live slots
    cfg: Lfm2Config,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,
    mesh=None,
    return_counts: bool = False,
    *,
    state: tuple,  # (windows [n_conv, B, K - 1, D],): row b is slot b
):
    """One token of batched decode: the attention layers against their pages
    (read-only inside the step, one scatter after it), the convolution
    layers one window step over every slot, the leaf indexed ``[layer]`` and
    updated in place. A slot that is not ``active`` keeps its window and
    routes no pair. Returns (logits [B, vocab], k_pages, v_pages, state)
    and, with ``return_counts``, [pairs, tile rows] of the routed layers."""
    _check_serving(cfg, k_pages, mesh)
    paged_impl_plan(cfg, k_pages.shape[2], impl, scatter_impl, kv_dtype=k_pages.dtype)
    page_size = k_pages.shape[2]
    B = tokens.shape[0]
    page_idx = jnp.take_along_axis(page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(active, page_idx, 0)
    slot = jnp.where(active, positions % page_size, 0)
    prefix_lens = jnp.where(active, positions, 0).astype(jnp.int32)
    cos, sin = layers.rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)  # [B, hd / 2]

    x = params["embed"][tokens]
    (windows,) = state
    counts = jnp.zeros((2,), jnp.int32)
    ks, vs = [], []
    for kind, first, dense, ffn_first, count in cfg.segments:
        if kind == ATTENTION:
            layer = _row(params["attention_layers"], first)
            u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
            q, k, v = _qkv(layer, u, cos, sin, cfg)  # [B, heads, hd]
            k, v = _hybrid._fold_kv(k, cfg), _hybrid._fold_kv(v, cfg)
            o = paged_decode_attention_chunked(
                _hybrid._fold_q(q, cfg), k_pages, v_pages, jnp.int32(first), page_tables,
                prefix_lens, k, v, sm_scale=cfg.softmax_scale,
            )
            o = _hybrid._unfold_o(o, cfg).reshape(B, -1)
            x = x + layers.mm(o, layer["wo"]).astype(x.dtype)
            x, c = _ffn(_ffn_row(params, dense, ffn_first), x, cfg, dense, active)
            counts = counts + c
            ks.append(k)
            vs.append(v)
            continue

        def conv_layer(carry, j, first=first, dense=dense, ffn_first=ffn_first):
            x, windows, counts = carry
            layer = _row(params["conv_layers"], first + j)
            u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
            mixed, windows = _conv_step(layer, u, active, windows, first + j)
            x, c = _ffn(
                _ffn_row(params, dense, ffn_first + j), x + mixed.astype(x.dtype), cfg,
                dense, active,
            )
            return (x, windows, counts + c), None

        (x, windows, counts), _ = jax.lax.scan(
            conv_layer, (x, windows, counts), jnp.arange(count)
        )
    if ks:
        # [La, B, Hkv / fold, fold * hd]: one scatter for every attention layer's token
        k_pages = _scatter_rows(k_pages, jnp.stack(ks), page_idx, slot)
        v_pages = _scatter_rows(v_pages, jnp.stack(vs), page_idx, slot)
    out = (_logits(params, x, cfg), k_pages, v_pages, (windows,))
    return (*out, counts) if return_counts else out
