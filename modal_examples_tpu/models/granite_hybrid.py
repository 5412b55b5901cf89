"""Granite-4.0-H (``granitemoehybrid``): Mamba-2 layers with a few
full-attention layers between them, a dense SwiGLU in every layer (H-Micro)
or, behind every mixer, a shared SwiGLU beside routed experts (H-Small: 72
experts, 10 a token), no position embedding, Granite's four multipliers,
served on the engine's normal path.

Two kinds of state live side by side (docs/recurrent_state.md):

- the attention layers keep K and V per *token* in the two paged leaves of
  ``PagedKVCache``, which for this model cover ``cfg.n_cache_layers`` layers
  (the attention layers only), not all of them;
- a Mamba-2 layer keeps per *sequence* an SSM state ``[heads, d_head,
  d_state]`` in float32 and the last ``d_conv - 1`` inputs of its
  convolution: the cache's **per-slot leaves** ``[n_state_layers, max_slots,
  ...]`` (``cfg.state_leaves``), addressed by slot, with no page axis.

The mixer has two forms. **Prefill** (``mtpu.ssm_scan``) computes the
recurrence in chunks of ``mamba_chunk_size`` in the matrix form of the Mamba-2
paper: within a chunk ``(L o C B^T) X`` with ``L`` the lower-triangular
products of the decays, between chunks the chunk's state carried by a short
scan; it starts from a given state (zeros, or what the previous chunk call of
a long prompt left in the slot) and leaves the state after the row's *last
real token*: a padded position gets ``dt = 0`` (decay 1, input 0), and the
convolution tail is taken at the row's own length. **Decode**
(``mtpu.ssm_step``) is the one-token form over all ``max_slots`` rows of the
state at once; a row whose slot is not decoding stands still (``dt = 0``, its
tail kept), so a slot whose first token is not harvested yet, or whose prompt
is between two chunk calls, keeps what its prefill wrote. The state's update
and the output are one pass over the state where ``paged_impl_plan`` finds
the kernel's conditions (ops/ssm_step.py: a TPU, a float32 state in whole
vregs), XLA's update and a reduction that reads the state again elsewhere.

The layers of a kind are stacked (``mamba_layers``, ``attention_layers``);
the layer pattern is ``cfg.layer_types``. The Mamba layers run as scans over
the runs between attention layers, each step indexing ``[layer]`` out of the
whole stack and, in decode, out of the whole state leaf, which is updated in
place. In the prefill programs the state leaf is touched once: a gather of
the rows' slots before the layers (chunk calls at an offset) and one scatter
after them.

**The routed half** (``cfg.n_experts > 0``; docs/recurrent_state.md "A routed
layer beside per-slot state"). With ``u = RMSNorm(x)`` after the mixer, ``x +=
residual_multiplier * (Shared(u) + Routed(u))``: ``Shared`` the SwiGLU of
``ffn_dim`` every layer had (``mtpu.dense_mlp``), ``Routed(u) = sum_{e in
top_k} p_e Expert_e(u)`` with ``l = W_r u`` in float32 over the router's whole
width, the ``top_k`` largest chosen, ``p`` a softmax over the chosen logits
(computed as the softmax over all, top-k, renormalised: the same ids and
weights), and only the chosen pairs through ``moe.moe_swiglu_sparse``. The
experts live in a stack of their own, ``moe_layers``, **indexed by layer**
where the mixers' stacks are indexed by kind: ``router [L, D, n_experts]``
and ``moe_gate`` / ``moe_up`` / ``moe_down`` ``[L, held, D, F]``, never sliced
by a scan (``moe.scan_layers`` says why): a scan body holds two indices, its
row in the Mamba stack and state leaf and its layer in the expert stacks. A
chip may hold a share of the experts (``n_held_experts`` from
``expert_offset`` on): the router keeps its width, pairs routed elsewhere add
nothing, and no code stands in for the other chips or their exchange. A
decode step then holds two Mosaic calls a Mamba layer on a TPU
(``paged_impl_plan``: ``state_step`` and ``expert_scan``) and counts its
routed pairs and its tiles' rows on the device (``counts_routed_pairs``,
``counts_expert_tile_rows``).

Departures from ``modeling_granitemoehybrid``, none of which changes a
result: ``shared_mlp.input_linear`` is kept as its two halves (``gate``,
``up``) and ``mamba.in_proj`` as its three column blocks (``in_z``,
``in_xbc``, ``in_dt``); ``time_step_limit`` ``(0, inf)`` is a no-op and left
out; ``block_sparse_moe.input_linear`` is kept as its halves too
(``moe_gate``, ``moe_up``); the route's renormalisation adds 1e-20 to the sum
of ten probabilities. The plain
reference is ``models/granite_hybrid_reference.py``.

What this model does not do yet is refused by name where the engine is built
(``GraniteHybridConfig.unsupported``).
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
from ..ops.ssm_step import ssm_step, ssm_step_shapes_ok, ssm_step_xla
from . import layers
from . import moe as _moe
from .layers import refuse
from .layers import scatter_rows as _scatter_rows

MAMBA, ATTENTION = "mamba", "attention"
#: the SSM state's precision. A constant, not an option: no check tells a
#: bf16 state's answers from float32's until a cell holds one to a limit
#: (PERF.md section 6, PR 31); the convolution tail is ``cfg.dtype``
STATE_DTYPE = "float32"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    layer_types: tuple = (
        (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    ) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 8192  # shared_intermediate_size: the SwiGLU of every layer
    n_experts: int = 0  # num_local_experts: the router's width; 0: no routed half
    n_held_experts: int | None = None  # how many of them this chip holds (unset: all) ...
    expert_offset: int = 0  # ... from this one on
    top_k: int = 0  # num_experts_per_tok
    expert_dim: int = 0  # intermediate_size: one routed expert's width
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: str = "bfloat16"
    tie_embeddings: bool = True

    #: features of the engine this model's programs do not implement yet:
    #: ``LLMEngine`` refuses each by name where it is asked for
    unsupported = (
        "prefix caching", "int8 KV cache", "speculative decoding",
        "disaggregated transfer", "tensor parallelism",
        "LoRA", "vision", "a Pallas paged_impl or scatter_impl",
    )

    def __post_init__(self):
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: only {MAMBA!r} and {ATTENTION!r}")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.dim:
            raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * dim")
        if self.mamba_n_heads % self.mamba_n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide into their groups")
        if self.n_experts and not (0 < self.top_k <= self.n_experts and self.expert_dim > 0):
            raise ValueError("a routed model needs top_k in 1..n_experts and an expert_dim")
        if not 0 <= self.expert_offset <= self.n_experts - self.held_experts:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.held_experts} lie outside "
                f"the router's {self.n_experts}"
            )

    # -- the seam LLMEngine reads (docs/mla.md) ---------------------------------

    @property
    def model(self):
        """The module that holds this configuration's programs."""
        return sys.modules[__name__]

    @property
    def kv_fold(self) -> int:
        """How many K/V heads share one row of a page: the most that divide
        the K/V heads and keep the row within the TPU's 128 lanes (2 at the
        published 8 heads of 64). A paged leaf whose rows are 64 wide is laid
        out pages-minor by the compiler and relaid out on the way into and
        out of every program that gathers or scatters it: four copies of
        0.375 GiB a decode block at the benchmark's size, found compile-only
        (PERF.md section 6, PR 31); rows of 128 are not."""
        return kv_fold(self.n_kv_heads, self.head_dim)

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
        """The per-slot leaves the cache keeps beside its pages, each
        ``(layers, per-slot shape, dtype)``: the SSM state and the
        convolution's last ``d_conv - 1`` inputs, of every Mamba layer."""
        n = self.layer_types.count(MAMBA)
        if not n:
            return ()
        return (
            (n, (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state), STATE_DTYPE),
            (n, (self.mamba_d_conv - 1, self.conv_dim), self.dtype),
        )

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import GRANITE_HYBRID_TARGETS

        return GRANITE_HYBRID_TARGETS

    @property
    def counts_routed_pairs(self) -> bool:
        """``decode_step(return_counts=True)`` hands back, after the state,
        [held, all] routed pairs of the live slots ..."""
        return self.n_experts > 0

    @property
    def counts_expert_tile_rows(self) -> bool:
        """... and then [pairs, rows] of the tiles computed for them."""
        return self.n_experts > 0

    # -- sizes -------------------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held_experts(self) -> int:
        """Experts whose matrices this chip holds: all, or its share."""
        return self.n_experts if self.n_held_experts is None else self.n_held_experts

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Width of ``xBC``: the convolution runs over x, B and C together."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_n_heads

    @property
    def segments(self) -> tuple:
        """The layer pattern as runs: ``(kind, first row in the kind's stack,
        count)``, in order; an attention layer is a run of one."""
        out, seen = [], {MAMBA: 0, ATTENTION: 0}
        for kind in self.layer_types:
            if out and out[-1][0] == kind == MAMBA:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(s) for s in out)

    @property
    def param_count(self) -> int:
        D, F = self.dim, self.ffn_dim
        mamba = (
            D * self.in_proj_dim + self.d_inner * D + self.conv_dim * (self.mamba_d_conv + 1)
            + 3 * self.mamba_n_heads + self.d_inner
        )
        attn = D * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim + self.n_heads * self.head_dim * D
        per_layer = 3 * D * F + 2 * D + D * self.n_experts + self.held_experts * 3 * D * self.expert_dim
        emb = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        n_mamba = self.layer_types.count(MAMBA)
        return (
            emb + D + self.n_layers * per_layer + n_mamba * mamba
            + (self.n_layers - n_mamba) * attn
        )

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "GraniteHybridConfig":
        """Test-tier config: both kinds of layer, runs of different lengths,
        a chunk shorter than the test prompts."""
        base = dict(
            vocab_size=vocab_size, dim=64,
            layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, ATTENTION, MAMBA),
            n_heads=4, n_kv_heads=2, ffn_dim=128, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
            mamba_chunk_size=8, attention_multiplier=0.25, max_seq_len=512,
        )
        base.update(overrides)
        return GraniteHybridConfig(**base)

    @staticmethod
    def tiny_moe(vocab_size: int = 512, **overrides) -> "GraniteHybridConfig":
        """``tiny`` with the routed half behind every mixer: 8 experts of 32,
        3 a token, beside a shared SwiGLU of 64."""
        base = dict(ffn_dim=64, n_experts=8, top_k=3, expert_dim=32)
        base.update(overrides)
        return GraniteHybridConfig.tiny(vocab_size, **base)

    @staticmethod
    def from_hf_config(path: str | Path) -> "GraniteHybridConfig":
        """From a published ``config.json`` (``model_type`` ``granitemoehybrid``).
        A file that runs the first layers of the published stack keeps
        ``layer_types`` whole and says how many in ``num_hidden_layers``; one
        that states the chip's share of the experts says so beside the
        published keys (as ``DeepseekV2Config.from_hf_config`` reads it):
        ``num_local_experts`` then counts the experts held here and
        ``expert_share`` is ``{"of": the router's width, "offset": the first
        held expert}``. ``intermediate_size`` is read as one routed expert's
        width (the published file has no key of its own for it)."""
        cfg = json.loads(Path(path).read_text())
        for key, want in (
            ("position_embedding_type", "nope"),
            ("attention_bias", False), ("mamba_proj_bias", False),
            ("mamba_conv_bias", True), ("hidden_act", "silu"),
            ("normalization_function", "rmsnorm"),
        ):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"GraniteHybridConfig: {key}={cfg[key]!r} is not modelled (only {want!r})"
                )
        shared = cfg.get("shared_intermediate_size", cfg.get("intermediate_size"))
        kinds = tuple(cfg["layer_types"])
        n = int(cfg.get("num_hidden_layers", len(kinds)))
        if len(kinds) < n:
            raise ValueError(f"layer_types names {len(kinds)} layers of {n}")
        share = cfg.get("expert_share") or {}
        held = int(cfg.get("num_local_experts") or 0)
        return GraniteHybridConfig(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            layer_types=kinds[:n],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_dim=shared,
            n_experts=int(share.get("of", held)),
            n_held_experts=held if share else None,
            expert_offset=int(share.get("offset", 0)),
            top_k=int(cfg.get("num_experts_per_tok") or 0) if held else 0,
            expert_dim=int(cfg["intermediate_size"]) if held else 0,
            mamba_n_heads=cfg["mamba_n_heads"],
            mamba_d_head=cfg["mamba_d_head"],
            mamba_d_state=cfg["mamba_d_state"],
            mamba_n_groups=cfg["mamba_n_groups"],
            mamba_d_conv=cfg["mamba_d_conv"],
            mamba_expand=cfg["mamba_expand"],
            mamba_chunk_size=cfg["mamba_chunk_size"],
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_seq_len=cfg.get("max_position_embeddings", 4096),
            tie_embeddings=cfg.get("tie_word_embeddings", True),
        )


def kv_fold(n_kv_heads: int, head_dim: int) -> int:
    """The most K/V heads that divide ``n_kv_heads`` and keep a page row
    within the TPU's 128 lanes (``GraniteHybridConfig.kv_fold`` says why)."""
    return max(
        f for f in range(1, n_kv_heads + 1)
        if n_kv_heads % f == 0 and (f == 1 or f * head_dim <= 128)
    )


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> dict:
    """Random init; the layers of a kind stacked on axis 0. ``A_log``,
    ``dt_bias`` and ``D`` as Mamba-2's published initialisation draws them
    (``A`` uniform in 1..16, ``dt`` log-uniform in 0.001..0.1, ``D`` = 1), so
    that a state remembers hundreds of tokens."""
    dt = cfg.jnp_dtype
    D, F, H = cfg.dim, cfg.ffn_dim, cfg.mamba_n_heads

    def dense(k, *shape):
        return layers.init_dense(k, shape, dtype=dt)

    def mlp(k, L):
        k = jax.random.split(k, 3)
        return {
            "mlp_norm": jnp.ones((L, D), dt), "gate": dense(k[0], L, D, F),
            "up": dense(k[1], L, D, F), "down": dense(k[2], L, F, D),
        }

    keys = jax.random.split(key, 8)
    params = {
        "embed": layers.init_dense(keys[0], (cfg.vocab_size, D), scale=0.02, dtype=dt),
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], D, cfg.vocab_size)
    L = cfg.layer_types.count(MAMBA)
    if L:
        k = jax.random.split(keys[2], 6)
        step = jnp.exp(jax.random.uniform(
            k[3], (L, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)
        ))
        params["mamba_layers"] = {
            "mixer_norm": jnp.ones((L, D), dt),
            **_split_in_proj(dense(k[0], L, D, cfg.in_proj_dim), cfg),
            "conv_w": layers.init_dense(
                k[1], (L, cfg.mamba_d_conv, cfg.conv_dim), scale=cfg.mamba_d_conv**-0.5, dtype=dt
            ),
            "conv_b": jnp.zeros((L, cfg.conv_dim), dt),
            # softplus(dt_bias) = the drawn step
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(jnp.float32),
            "A_log": jnp.log(jax.random.uniform(k[2], (L, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((L, H), jnp.float32),
            "gate_norm": jnp.ones((L, cfg.d_inner), dt),
            "out_proj": dense(k[4], L, cfg.d_inner, D),
            **mlp(k[5], L),
        }
    L = cfg.n_cache_layers
    if L:
        k = jax.random.split(keys[3], 5)
        hd = cfg.head_dim
        params["attention_layers"] = {
            "mixer_norm": jnp.ones((L, D), dt),
            "wq": dense(k[0], L, D, cfg.n_heads * hd),
            "wk": dense(k[1], L, D, cfg.n_kv_heads * hd),
            "wv": dense(k[2], L, D, cfg.n_kv_heads * hd),
            "wo": dense(k[3], L, cfg.n_heads * hd, D),
            **mlp(k[4], L),
        }
    if cfg.n_experts:
        k = jax.random.split(keys[4], 4)
        L, E, Fe = cfg.n_layers, cfg.held_experts, cfg.expert_dim
        params["moe_layers"] = {  # by layer index, whatever the layer's mixer
            "router": dense(k[0], L, D, cfg.n_experts),
            "moe_gate": dense(k[1], L, E, D, Fe), "moe_up": dense(k[2], L, E, D, Fe),
            "moe_down": dense(k[3], L, E, Fe, D),
        }
    return params


def partition_specs(cfg: GraniteHybridConfig) -> dict:
    refuse(cfg, "tensor parallelism")
    raise NotImplementedError("GraniteHybridConfig has no partition specs")


def load_hf_weights(model_dir, cfg: GraniteHybridConfig, *, quantization=None, dtype=None) -> dict:
    """The published checkpoint (``*.safetensors`` under ``model_dir``) as
    this module's tree. Published names, per layer ``N`` of
    ``model.layers.N``: ``input_layernorm``, ``post_attention_layernorm``,
    ``mamba.{in_proj,conv1d,A_log,D,dt_bias,norm,out_proj}`` or
    ``self_attn.{q,k,v,o}_proj``, ``shared_mlp.{input_linear,output_linear}``
    and, of a routed model, ``block_sparse_moe.{input_linear,output_linear}``
    (one tensor for all experts, ``[experts, 2 F, D]`` and ``[experts, D,
    F]``) and ``block_sparse_moe.router.layer``;
    a torch ``Linear`` is ``[out, in]`` and is transposed, ``conv1d.weight``
    ``[conv_dim, 1, d_conv]`` becomes ``[d_conv, conv_dim]``,
    ``input_linear`` is split into its ``gate`` and ``up`` halves and
    ``in_proj`` into its ``z``, ``xBC`` and ``dt`` blocks. The first
    ``cfg.n_layers`` layers and ``cfg.vocab_size`` rows are read, and of the
    experts those held here (``cfg.expert_offset`` on); the router keeps its
    width."""
    import numpy as np
    from safetensors import safe_open

    from .quantize import bits_of, quantize_weight_host

    dt = jnp.dtype(dtype or cfg.dtype)
    tensors = {}
    for path in sorted(Path(model_dir).glob("*.safetensors")):
        with safe_open(str(path), framework="numpy") as f:
            tensors.update({name: f.get_tensor(name) for name in f.keys()})
    if not tensors:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")

    def get(name):
        return np.asarray(tensors[name], dtype=np.float32)

    def linear(name):
        return get(name + ".weight").T

    per_kind: dict = {MAMBA: [], ATTENTION: []}
    routed: list = []
    held = slice(cfg.expert_offset, cfg.expert_offset + cfg.held_experts)
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        w_in = linear(p + "shared_mlp.input_linear")  # [D, 2F]: gate | up
        layer = {
            "mixer_norm": get(p + "input_layernorm.weight"),
            "mlp_norm": get(p + "post_attention_layernorm.weight"),
            "gate": w_in[:, : cfg.ffn_dim], "up": w_in[:, cfg.ffn_dim:],
            "down": linear(p + "shared_mlp.output_linear"),
        }
        if kind == MAMBA:
            layer.update(
                **_split_in_proj(linear(p + "mamba.in_proj"), cfg),
                conv_w=get(p + "mamba.conv1d.weight")[:, 0, :].T,
                conv_b=get(p + "mamba.conv1d.bias"),
                dt_bias=get(p + "mamba.dt_bias"), A_log=get(p + "mamba.A_log"),
                D=get(p + "mamba.D"), gate_norm=get(p + "mamba.norm.weight"),
                out_proj=linear(p + "mamba.out_proj"),
            )
        else:
            layer.update({
                "w" + n: linear(p + f"self_attn.{n}_proj") for n in ("q", "k", "v", "o")
            })
        per_kind[kind].append(layer)
        if cfg.n_experts:
            e_in = get(p + "block_sparse_moe.input_linear.weight")[held]  # [E, 2F, D]: gate | up
            routed.append({
                "router": linear(p + "block_sparse_moe.router.layer"),
                "moe_gate": e_in[:, : cfg.expert_dim].transpose(0, 2, 1),
                "moe_up": e_in[:, cfg.expert_dim:].transpose(0, 2, 1),
                "moe_down": get(p + "block_sparse_moe.output_linear.weight")[held].transpose(0, 2, 1),
            })

    keep_f32 = ("dt_bias", "A_log", "D")

    def stack(rows, name):
        full = np.stack([r[name] for r in rows])
        if quantization is not None and name in cfg.quant_targets:
            return quantize_weight_host(full, bits_of(quantization))
        return jnp.asarray(full, jnp.float32 if name in keep_f32 else dt)

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight")[: cfg.vocab_size], dt),
        "final_norm": jnp.asarray(get("model.norm.weight"), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jnp.asarray(linear("lm_head")[:, : cfg.vocab_size], dt)
    for kind, rows in per_kind.items():
        if rows:
            params[f"{kind}_layers"] = {name: stack(rows, name) for name in rows[0]}
    if routed:
        params["moe_layers"] = {name: stack(routed, name) for name in routed[0]}
    return params


def paged_impl_plan(
    cfg: GraniteHybridConfig, page_size: int, impl: str | None = None,
    scatter_impl: str = "xla", *, kv_dtype="bfloat16", mesh=None, warn: bool = True,
    state_dtype=None, expert_dtype=None,
) -> dict:
    """What runs for this model. Attention: the chunked XLA loop over the
    attention layers' pages and the XLA scatter (the ragged kernel wants a
    head of 128: ops.paged_attention.ragged_shapes_ok); anything else is
    refused here. The Mamba layers' state step (``state_step``): the one-pass
    kernel (``"pallas"``, ops.ssm_step) where the backend is a TPU and the
    state leaf (``state_dtype``; unset: what ``cfg.state_leaves`` declares)
    is float32 in whole vregs (``ssm_step_shapes_ok``); XLA's update and
    reduction (``"xla"``) everywhere else: the CPU, where the kernel would
    run in the interpreter, and the tests' tiny shapes. One computation, and
    two counts of passes over the state. A routed model's tile loop in a
    decode step (``expert_scan``, a key only such a model's plan has):
    ``moe.expert_scan_form``'s choice for experts of ``expert_dtype`` (unset:
    the model's own), the grouped-matmul kernel on a TPU, XLA's loop
    elsewhere. All chosen from what can be seen here, by no option."""
    from ..ops.kv_quant import resolve_kv_dtype

    if impl not in (None, "xla") or scatter_impl != "xla":  # unset: as "xla"
        refuse(cfg, "a Pallas paged_impl or scatter_impl")
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    kvd = resolve_kv_dtype(kv_dtype)
    if kvd == "int8":
        refuse(cfg, "int8 KV cache")
    kernel = (
        jax.default_backend() == "tpu" and bool(cfg.state_leaves)
        and ssm_step_shapes_ok(
            cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.state_leaves[0][2] if state_dtype is None else state_dtype,
        )
    )
    routed = {"expert_scan": _moe.expert_scan_form(
        1, cfg.dim, cfg.expert_dim, expert_dtype or cfg.dtype
    )} if cfg.n_experts else {}
    return {
        "attention": "xla-gather", "ragged_variant": None, "scatter": "xla",
        "kv_dtype": str(kvd), "tp": 1, "downgraded": [],
        "state_step": "pallas" if kernel else "xla", **routed,
    }


# -- the Mamba-2 mixer ----------------------------------------------------------


def _split_in_proj(w, cfg) -> dict:
    """The published ``in_proj`` [..., D, d_inner + conv_dim + heads] as its
    three column blocks ``[z | xBC | dt]``, a leaf each: ``in_z``, ``in_xbc``,
    ``in_dt``. One leaf 8512 wide is no multiple of the TPU's 128 lanes, and
    the compiler then keeps a transposed copy of the whole stack (1.2 GB at
    the published sizes: found compile-only, PERF.md section 6, PR 31)."""
    di, cd = cfg.d_inner, cfg.conv_dim
    return {"in_z": w[..., :di], "in_xbc": w[..., di:di + cd], "in_dt": w[..., di + cd:]}


def _in_proj(layer, u):
    """u [..., D] -> z, xBC (u's dtype) and dt (f32)."""
    z = layers.mm(u, layer["in_z"]).astype(u.dtype)
    xbc = layers.mm(u, layer["in_xbc"]).astype(u.dtype)
    return z, xbc, layers.mm(u, layer["in_dt"])


def _row(stack: dict, i):
    """Layer ``i`` of a stack of layers, indexed out of the whole leaves."""
    return jax.tree.map(lambda w: w[i], stack)


def _gated_norm(y, z, weight, eps: float, n_groups: int):
    """``RMSNorm(y * silu(z)) * weight`` in f32, over each group's share."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(*g.shape[:-1], n_groups, -1)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + eps)).reshape(g.shape)
    return (normed * weight.astype(jnp.float32)).astype(y.dtype)


def _split_xbc(conv, cfg):
    """Convolved ``xBC`` [..., conv_dim] -> x [..., H, P], B and C [..., G, N]."""
    di, GN = cfg.d_inner, cfg.mamba_n_groups * cfg.mamba_d_state
    lead = conv.shape[:-1]
    return (
        conv[..., :di].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head),
        conv[..., di:di + GN].reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state),
        conv[..., di + GN:].reshape(*lead, cfg.mamba_n_groups, cfg.mamba_d_state),
    )


def ssd_chunked(x, dt, A, B, C, h0, chunk: int):
    """The Mamba-2 recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t (x_t outer
    B_t)``, ``y_t = h_t C_t`` over a whole sequence, chunk by chunk in matrix
    form (the "SSD" algorithm of the Mamba-2 paper).

    x [b, T, H, P], dt [b, T, H] (f32, after softplus; 0 where the state is to
    stand still), A [H] (negative), B and C [b, T, G, N], h0 [b, H, P, N]
    f32. Returns (y [b, T, H, P] f32, the state after the last position). ``T``
    not a multiple of ``chunk`` is padded with ``dt = 0``.

    Within a chunk ``y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j +
    exp(a_i) C_i . h_in`` with ``a`` the running sum of ``dt A`` inside the
    chunk, and the chunk hands on ``exp(a_last) h_in + sum_j exp(a_last - a_j)
    dt_j (x_j outer B_j)``: a ``lax.scan`` over the chunks carries the state
    (at most ``T / chunk`` steps, each a few matrix products). The running
    sums and the decays are float32, the products' operands ``x``'s dtype,
    their sums float32."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C)
        )
    n = (T + pad) // chunk
    op = x.dtype

    def chunks(a):  # [b, n * Q, ...] -> [n, b, Q, ...]
        return jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(h, inputs):
        xc, dtc, Bc, Cc = inputs  # [b, Q, H, P], [b, Q, H], [b, Q, G, N] x2
        a = jnp.cumsum(dtc * A, axis=1)  # [b, Q, H] f32, decreasing
        xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(op)  # dt_j x_j
        # within the chunk: (L o C B^T) X, a head's group broadcast over it
        cb = jnp.einsum("bign,bjgn->bgij", Cc, Bc, preferred_element_type=jnp.float32)
        ah = jnp.moveaxis(a, 2, 1)  # [b, H, Q]
        decay = jnp.exp(
            jnp.where(causal, ah[..., :, None] - ah[..., None, :], -jnp.inf)
        )  # [b, H, i, j]: exp(a_i - a_j) for j <= i, else 0
        mix = cb.reshape(b, G, 1, chunk, chunk) * decay.reshape(b, G, H // G, chunk, chunk)
        y = jnp.einsum(
            "bhij,bjhp->bihp", mix.reshape(b, H, chunk, chunk).astype(op), xdt,
            preferred_element_type=jnp.float32,
        )
        # what the state at the chunk's start adds: exp(a_i) C_i . h_in
        Ch = jnp.einsum(
            "bign,bgrpn->bigrp", Cc, h.reshape(b, G, H // G, P, N).astype(op),
            preferred_element_type=jnp.float32,
        ).reshape(b, chunk, H, P)
        y = y + jnp.exp(a)[..., None] * Ch
        # the state the chunk hands on
        to_end = jnp.exp(a[:, -1:, :] - a)  # [b, Q, H]
        xw = (xdt.astype(jnp.float32) * to_end[..., None]).astype(op)
        dh = jnp.einsum(
            "bjgrp,bjgn->bgrpn", xw.reshape(b, chunk, G, H // G, P), Bc,
            preferred_element_type=jnp.float32,
        ).reshape(b, H, P, N)
        h = jnp.exp(a[:, -1, :])[:, :, None, None] * h + dh
        return h, y

    h, y = jax.lax.scan(one, h0.astype(jnp.float32), (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * chunk, H, P)
    return y[:, :T], h


def _mamba_prefill(layer, u, valid, lens, h0, tail0, cfg):
    """The mixer over whole rows. u [b, T, D] (normed), valid [b, T], lens
    [b], h0 [b, H, P, N] f32, tail0 [b, K - 1, conv_dim]. Returns (out [b, T,
    D], the state and the convolution tail after each row's last real
    token)."""
    b, T, _ = u.shape
    dt_ = u.dtype
    di, K = cfg.d_inner, cfg.mamba_d_conv
    with jax.named_scope(_scopes.SSM_PROJ):
        z, xbc, dt = _in_proj(layer, u)
    with jax.named_scope(_scopes.SSM_SCAN):
        ext = jnp.concatenate([tail0.astype(dt_), xbc], axis=1)  # [b, T + K - 1, .]
        w = layer["conv_w"].astype(jnp.float32)
        conv = layer["conv_b"].astype(jnp.float32) + sum(
            w[j] * ext[:, j:j + T].astype(jnp.float32) for j in range(K)
        )
        x, B, C = _split_xbc(jax.nn.silu(conv).astype(dt_), cfg)
        # a padded position: dt = 0, so its decay is 1 and its input 0
        dt = jnp.where(valid[..., None], jax.nn.softplus(dt + layer["dt_bias"]), 0.0)
        A = -jnp.exp(layer["A_log"].astype(jnp.float32))
        y, h = ssd_chunked(x, dt, A, B, C, h0, cfg.mamba_chunk_size)
        y = y + layer["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        # the tail at the row's own length: ext rows lens .. lens + K - 2
        rows = lens[:, None] + jnp.arange(K - 1)[None, :]
        tail = jnp.take_along_axis(ext, rows[..., None], axis=1)
    with jax.named_scope(_scopes.SSM_PROJ):
        y = _gated_norm(
            y.reshape(b, T, di).astype(dt_), z, layer["gate_norm"], cfg.norm_eps,
            cfg.mamba_n_groups,
        )
        return layers.mm(y, layer["out_proj"]), h, tail


def _mamba_step(layer, u, live, ssm, tails, i, cfg, state_step: str):
    """The mixer's one-token form over every slot. u [S, D] (normed), live
    [S] bool; ``ssm`` [L, S, H, P, N] and ``tails`` [L, S, K - 1, conv_dim]
    are the whole per-slot leaves, of which layer ``i`` is read and written
    in place (inside the scope, so that the trace charges the state's
    traffic to ``mtpu.ssm_step``), by the form ``state_step`` names
    (``paged_impl_plan``): the kernel's one pass or XLA's update and
    reduction. A row that is not live keeps its state and its tail. Returns
    (out [S, D], ssm, tails)."""
    S = u.shape[0]
    dt_ = u.dtype
    di, G = cfg.d_inner, cfg.mamba_n_groups
    with jax.named_scope(_scopes.SSM_PROJ):
        z, xbc, dt = _in_proj(layer, u)
    with jax.named_scope(_scopes.SSM_STEP):
        tail = tails[i]
        window = jnp.concatenate([tail, xbc[:, None, :].astype(tail.dtype)], axis=1)
        conv = layer["conv_b"].astype(jnp.float32) + jnp.einsum(
            "skc,kc->sc", window.astype(jnp.float32), layer["conv_w"].astype(jnp.float32)
        )
        x, B, C = _split_xbc(jax.nn.silu(conv).astype(dt_), cfg)
        x = x.astype(jnp.float32)
        dt = jnp.where(live[:, None], jax.nn.softplus(dt + layer["dt_bias"]), 0.0)
        A = -jnp.exp(layer["A_log"].astype(jnp.float32))
        step = ssm_step if state_step == "pallas" else ssm_step_xla
        # h' = exp(dt A) h + dt x (x) B, y = h' . C; layer i of ``ssm`` set to h'
        ssm, y = step(
            ssm, i, jnp.exp(dt * A), dt[..., None] * x,
            B.astype(jnp.float32), C.astype(jnp.float32),
        )
        y = y + layer["D"].astype(jnp.float32)[:, None] * x
        tails = tails.at[i].set(jnp.where(live[:, None, None], window[:, 1:], tail))
    with jax.named_scope(_scopes.SSM_PROJ):
        y = _gated_norm(
            y.reshape(S, di).astype(dt_), z, layer["gate_norm"], cfg.norm_eps, G
        )
        return layers.mm(y, layer["out_proj"]), ssm, tails


# -- shared parts ---------------------------------------------------------------


def _residual(x, mixed, cfg):
    return x + (cfg.residual_multiplier * mixed).astype(x.dtype)


def route(router, x, cfg):
    """x [T, D], router [D, n_experts] -> (weights [T, k] f32, expert ids
    [T, k]): the logits in float32 over the router's whole width, the
    ``top_k`` largest (a tie to the lower id), a softmax over the chosen
    ones, computed as Mixtral's route is (``moe.moe_swiglu_routed``): the
    softmax over all, its top-k, renormalised."""
    with jax.named_scope(_scopes.ROUTER):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
        return _moe.route_group_limited(
            jax.nn.softmax(logits, axis=-1), cfg.top_k, renormalize=True
        )


def held_tile_rows(ids, token_mask, cfg, tile: int):
    """[pairs, rows] int32: the counted tokens' pairs that land on the
    experts held here, and the rows of the tiles ``moe_swiglu_sparse``
    computes for them (each reached expert's pairs padded to whole tiles)."""
    E = cfg.held_experts
    local = ids - cfg.expert_offset
    here = (local >= 0) & (local < E)
    if token_mask is not None:
        here = here & token_mask[:, None]
    per_expert = jnp.bincount(jnp.where(here, local, E).reshape(-1), length=E + 1)[:E]
    rows = jnp.sum((per_expert + tile - 1) // tile * tile)
    return jnp.stack([jnp.sum(per_expert), rows]).astype(jnp.int32)


def _mlp(layer, x, cfg, moe=None, index=None, token_mask=None):
    """``x + residual_multiplier * MLP(RMSNorm(x))`` and what the layer
    counted. A dense model's MLP is the layer's SwiGLU and it counts nothing
    (``()``). A routed model's is that SwiGLU, the shared expert, plus the
    routed experts of layer ``index`` of ``moe``, the experts' whole stacks
    (``params["moe_layers"]``), and it counts ([held, all] routed pairs,
    [pairs, rows] of its tiles) over the tokens of ``token_mask``."""
    h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    shared = layers.swiglu_mlp({k: layer[k] for k in ("gate", "up", "down")}, h)
    if moe is None:
        return _residual(x, shared, cfg), ()
    flat = h.reshape(-1, cfg.dim)
    mask = None if token_mask is None else token_mask.reshape(-1)
    weights, ids = route(moe["router"][index], flat, cfg)
    out, pairs = _moe.moe_swiglu_sparse(
        *(moe[n] for n in _moe.EXPERT_LEAVES), flat, ids, weights,
        expert_offset=cfg.expert_offset, token_mask=mask, layer=index,
    )
    tile = _moe.expert_tile(flat.shape[0], cfg.top_k, cfg.held_experts)
    mixed = shared.astype(jnp.float32) + out.reshape(x.shape)
    return _residual(x, mixed, cfg), (pairs, held_tile_rows(ids, mask, cfg, tile))


def _layer_ids(moe, first: int, count: int) -> tuple:
    """What a scan over a run of Mamba layers scans beside their rows in the
    Mamba stack: a routed model's layer indices (into ``moe``, the experts'
    stacks), nothing for a dense model."""
    return () if moe is None else (jnp.arange(first, first + count),)


def _embed(params, tokens, cfg):
    x = params["embed"][tokens]
    return (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)


def _logits(params, x, cfg):
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return layers.mm(x, head) / cfg.logits_scaling


def _qkv(layer, h, cfg):
    """h [..., D] (normed) -> q [..., Hq, hd], k and v [..., Hkv, hd]."""
    dt, hd = h.dtype, cfg.head_dim
    q = layers.mm(h, layer["wq"]).astype(dt).reshape(*h.shape[:-1], cfg.n_heads, hd)
    k = layers.mm(h, layer["wk"]).astype(dt).reshape(*h.shape[:-1], cfg.n_kv_heads, hd)
    v = layers.mm(h, layer["wv"]).astype(dt).reshape(*h.shape[:-1], cfg.n_kv_heads, hd)
    return q, k, v


def _fold_kv(a, cfg):
    """K or V [..., Hkv, hd] as the pages keep it: ``cfg.kv_fold`` heads to a
    row, [..., Hkv / fold, fold * hd] (the same bytes in the same order)."""
    return a.reshape(*a.shape[:-2], *cfg.cache_leaf_shapes[0])


def _fold_mask(cfg):
    """[Hq, fold]: 1 where a query head's K/V head sits in a folded row."""
    kv_head = jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)
    return jax.nn.one_hot(kv_head % cfg.kv_fold, cfg.kv_fold, dtype=jnp.float32)


def _fold_q(q, cfg):
    """Queries [..., Hq, hd] against folded rows: each at its K/V head's
    place in the row and zero beside it, [..., Hq, fold * hd], so that its
    product with a folded K row is its product with its own head."""
    wide = q[..., None, :] * _fold_mask(cfg)[:, :, None].astype(q.dtype)
    return wide.reshape(*q.shape[:-1], cfg.kv_fold * cfg.head_dim)


def _unfold_o(o, cfg):
    """Attention over folded V rows [..., Hq, fold * hd] -> each query
    head's own head's share [..., Hq, hd]."""
    parts = o.reshape(*o.shape[:-1], cfg.kv_fold, cfg.head_dim)
    return (parts * _fold_mask(cfg)[:, :, None].astype(o.dtype)).sum(axis=-2)


def _check_serving(cfg, k_pages, mesh, input_embeds=None):
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    if input_embeds is not None:
        refuse(cfg, "vision")
    if is_quantized(k_pages):
        refuse(cfg, "int8 KV cache")


def _gather_state(state, slot_ids):
    """The rows' slots out of the per-slot leaves: [L, b, ...] each (a row
    with no slot, ``slot_ids`` out of range, reads slot 0: it is never used)."""
    n_slots = state[0].shape[1]
    ids = jnp.where(slot_ids < n_slots, slot_ids, 0)
    return tuple(leaf[:, ids] for leaf in state)


def _scatter_state(leaf, rows, slot_ids):
    """Write ``rows`` [L, b, ...] into ``leaf`` [L, slots, ...] at
    ``slot_ids`` [b]; a row whose id is out of range is dropped. The layer
    index is spelt out, as for the pages (layers.scatter_rows)."""
    layer = jnp.arange(leaf.shape[0])[:, None]
    return leaf.at[layer, slot_ids[None, :]].set(rows.astype(leaf.dtype), mode="drop")


# -- forward, for tests and tools -----------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: GraniteHybridConfig, *,
            attn_impl: str = "flash", lora=None):
    """Full-sequence forward of the program's own layers (chunked scan, flash
    attention), no cache: [B, S] -> logits [B, S, vocab]."""
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
    Mamba layers from zeros (``q_offset`` 0) or from the rows' slots, and
    leaves each row's state after its last real token in its slot."""
    B, C = tokens.shape
    cached = k_pages is not None
    valid = jnp.arange(C)[None, :] < lens[:, None]
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    n_mamba = cfg.layer_types.count(MAMBA)
    if cached and q_offset:
        h_in, tail_in = _gather_state(state, slot_ids)
    else:
        h_in = jnp.zeros((n_mamba, B, H, P, N), jnp.float32)
        tail_in = jnp.zeros((n_mamba, B, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.jnp_dtype)
    if cached:
        page_size = k_pages.shape[2]
        positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
        page_idx = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
        page_idx = jnp.where(valid, page_idx, 0)
        slot = jnp.where(valid, positions % page_size, 0)
        n_prefix_pages = q_offset // page_size
        prefix_tables = page_tables[:, :n_prefix_pages]

    moe = params.get("moe_layers")  # the experts' stacks, by layer index; None: a dense model

    def mamba_layer(x, scanned):
        # i: the row in the Mamba stack; index: the layer, a routed model's second index
        i, h0, tail0, *index = scanned
        layer = _row(params["mamba_layers"], i)
        u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        mixed, h, tail = _mamba_prefill(layer, u, valid, lens, h0, tail0, cfg)
        x, _ = _mlp(layer, _residual(x, mixed, cfg), cfg, moe, *index, token_mask=valid)
        return x, (h, tail)

    def attention_layer(x, j, index):
        layer = _row(params["attention_layers"], j)
        u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, u, cfg)
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
                o = flash_attention(q, k_all, v_all, True, cfg.attention_multiplier)
            elif attn_impl == "flash":
                o = flash_attention_chunked(
                    q, k_all, v_all, q_offset=q_offset, sm_scale=cfg.attention_multiplier
                )
            else:
                from ..ops import reference as _ops_ref

                o = _ops_ref.attention_chunked(
                    q, k_all, v_all, q_offset=q_offset, sm_scale=cfg.attention_multiplier
                )
        o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.head_dim)
        x = _residual(x, layers.mm(o, layer["wo"]), cfg)
        x, _ = _mlp(layer, x, cfg, moe, index, token_mask=valid)
        return x, (k, v)

    x = _embed(params, tokens, cfg)
    hs, tails, ks, vs = [], [], [], []
    at = 0  # the segment's first layer
    for kind, first, count in cfg.segments:
        if kind == MAMBA:
            rows = slice(first, first + count)
            x, (h, tail) = jax.lax.scan(
                mamba_layer, x,
                (jnp.arange(first, first + count), h_in[rows], tail_in[rows],
                 *_layer_ids(moe, at, count)),
            )
            hs.append(h)
            tails.append(tail)
        else:
            x, (k, v) = attention_layer(x, first, at)
            ks.append(k)
            vs.append(v)
        at += count
    if cached:
        if ks:
            # [La, B, Hkv, C, hd] -> the block [La, B, C, Hkv, hd] at (page, slot)
            rows = (_fold_kv(jnp.stack(a).transpose(0, 1, 3, 2, 4), cfg) for a in (ks, vs))
            k_pages, v_pages = (
                _scatter_rows(pages, r, page_idx, slot) for pages, r in zip((k_pages, v_pages), rows)
            )
        if hs:
            state = (
                _scatter_state(state[0], jnp.concatenate(hs), slot_ids),
                _scatter_state(state[1], jnp.concatenate(tails), slot_ids),
            )
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
    cfg: GraniteHybridConfig,
    attn_impl: str = "flash",
    input_embeds=None,
    mesh=None,
    *,
    state: tuple,  # the cache's per-slot leaves
    slot_ids: jax.Array,  # [B] the rows' slots; out of range: a row with none
):
    """Process prompts from their first token: fills the attention layers'
    pages and leaves each row's recurrent state, from zeros, in its slot.
    Returns (logits_last, k_pages, v_pages, state)."""
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
    cfg: GraniteHybridConfig,
    *,
    q_offset: int,  # global position of the chunk's first token (static)
    attn_impl: str = "flash",
    mesh=None,
    state: tuple,
    slot_ids: jax.Array,
):
    """One chunk of a long prompt: the attention layers attend to the cached
    prefix and the chunk, the Mamba layers go on from the state the previous
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
    cfg: GraniteHybridConfig,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,
    mesh=None,
    return_counts: bool = False,
    *,
    state: tuple,  # per-slot leaves [n_mamba, B, ...]: row b is slot b
):
    """One token of batched decode: the attention layers against their
    pages (read-only inside the step, one scatter after it, as
    ``llama.decode_step``), the Mamba layers one state step over every slot,
    the state leaves indexed ``[layer]`` and updated in place. A slot that is
    not ``active`` keeps its state and routes no pair. Returns (logits [B,
    vocab], k_pages, v_pages, state) and, with ``return_counts`` (a routed
    model's), two [2] int32 after them: [held, all] routed pairs of the live
    slots, and [pairs, rows] of the tiles computed for them."""
    _check_serving(cfg, k_pages, mesh)
    plan = paged_impl_plan(
        cfg, k_pages.shape[2], impl, scatter_impl, kv_dtype=k_pages.dtype,
        state_dtype=state[0].dtype,
    )  # (the experts' tile loop picks its own form where it runs: moe_swiglu_sparse)
    page_size = k_pages.shape[2]
    B = tokens.shape[0]
    page_idx = jnp.take_along_axis(page_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(active, page_idx, 0)
    slot = jnp.where(active, positions % page_size, 0)
    prefix_lens = jnp.where(active, positions, 0).astype(jnp.int32)

    moe = params.get("moe_layers")  # the experts' stacks, by layer index; None: a dense model
    # what the routed layers count, summed over them: () for a dense model
    counts = () if moe is None else (jnp.zeros((2,), jnp.int32),) * 2

    def mamba_layer(carry, scanned):
        x, ssm, tails, *counts = carry
        # i: the row in the Mamba stack and the state leaf; index: the layer,
        # a routed model's second index
        i, *index = scanned
        layer = _row(params["mamba_layers"], i)
        u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        mixed, ssm, tails = _mamba_step(
            layer, u, active, ssm, tails, i, cfg, plan["state_step"]
        )
        x, counted = _mlp(layer, _residual(x, mixed, cfg), cfg, moe, *index, token_mask=active)
        return (x, ssm, tails, *(a + b for a, b in zip(counts, counted))), None

    x = _embed(params, tokens, cfg)
    ssm, tails = state
    ks, vs = [], []
    at = 0  # the segment's first layer
    for kind, first, count in cfg.segments:
        if kind == MAMBA:
            (x, ssm, tails, *counts), _ = jax.lax.scan(
                mamba_layer, (x, ssm, tails, *counts),
                (jnp.arange(first, first + count), *_layer_ids(moe, at, count)),
            )
            at += count
            continue
        layer = _row(params["attention_layers"], first)
        u = layers.rms_norm(x, layer["mixer_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, u, cfg)  # [B, heads, hd]
        k, v = _fold_kv(k, cfg), _fold_kv(v, cfg)
        o = paged_decode_attention_chunked(
            _fold_q(q, cfg), k_pages, v_pages, jnp.int32(first), page_tables, prefix_lens, k, v,
            sm_scale=cfg.attention_multiplier,
        )
        x = _residual(x, layers.mm(_unfold_o(o, cfg).reshape(B, -1), layer["wo"]), cfg)
        x, counted = _mlp(layer, x, cfg, moe, at, token_mask=active)
        counts = [a + b for a, b in zip(counts, counted)]
        at += count
        ks.append(k)
        vs.append(v)
    if ks:
        # [La, B, Hkv / fold, fold * hd]: one scatter for every attention layer's token
        k_pages = _scatter_rows(k_pages, jnp.stack(ks), page_idx, slot)
        v_pages = _scatter_rows(v_pages, jnp.stack(vs), page_idx, slot)
    out = (_logits(params, x, cfg), k_pages, v_pages, (ssm, tails))
    return (*out, *counts) if return_counts else out
