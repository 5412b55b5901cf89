"""GLM-5.2 (``glm_moe_dsa``): multi-head latent attention restricted to the
positions a learned indexer selects (a lightning indexer, an exact top-k,
IndexShare), leading dense layers, then a shared expert beside routed ones
chosen by a sigmoid router with a selection bias; served on the engine's
normal path (docs/sparse_attention.md, docs/mla.md).

The latent attention is DeepSeek-V2's at other widths and is imported from
``models/deepseek_v2.py``: the projections, the expansion for prefill, the
absorbed form for decode, the routed layer. What this file adds:

- **the indexer**, in the layers ``indexer_types`` calls ``full``: index
  queries from the query's latent, one index key a token from the layer's
  input (LayerNorm, a rotated slice), a weight a head; ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` the ``index_topk`` positions of
  largest score (``ops/sparse_attention.py``). The index keys are a **third
  paged leaf** of the cache (``cache_leaf_shapes[2]``, over the ``full``
  layers only: ``cache_leaf_layers``), which the programs take and hand back
  as ``state=(index pages,)``;
- **IndexShare**: a ``shared`` layer has no indexer and attends to what the
  nearest ``full`` layer before it selected. The selection rides the layer
  scans' carry (prefill: a mask ``[B, C, keys]``; decode: ``index_topk``
  positions a slot) and a ``lax.cond`` on the layer's kind computes it anew
  or passes it on; the indexers' weights are a stack of their own
  (``indexer_layers``), indexed by the layer's number among the ``full`` ones;
- **attention over ``S_t`` only**: prefill expands the latents as DeepSeek-V2
  does, a block of positions at a time, and runs the flash kernel under the
  selection mask; decode gathers the selected latents and runs the absorbed
  form over them. A call whose keys number at most ``index_topk`` selects
  everything and skips the scoring (the index keys are written all the same);
- **a chunk program that takes its offset as an argument**
  (``chunk_offset_runtime``): contexts here run to 16k tokens, and programs
  an offset would be nine times the widths where three prefix buckets do.

The router: ``p = sigmoid(h W_r)``, chosen by ``p + bias``, weights the chosen
``p`` renormalised times ``routed_scaling_factor`` (``moe.route_group_limited``
with ``bias=``). "Experts held here" as DeepSeek-V2's.

Left out, by name: the multi-token-prediction block
(``num_nextn_predict_layers``; the next-token logits do not depend on it).
The plain reference is ``models/glm_dsa_reference.py``.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import is_quantized, kv_gather
from ..ops import scopes as _scopes
from ..ops import sparse_attention as _sparse
from . import deepseek_v2 as _mla
from . import layers
from . import moe as _moe
from .layers import refuse
from .layers import scatter_rows as _scatter_rows


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    vocab_size: int = 154880
    dim: int = 6144
    n_layers: int = 78
    n_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: "full" | "shared" a layer; None: full in the first ``index_skip_topk_offset``
    #: layers, then every ``index_topk_freq``-th (the published rule)
    indexer_types: tuple | None = None
    #: "dense" | "sparse" a layer; None: ``first_k_dense`` dense ones lead
    mlp_layer_types: tuple | None = None
    first_k_dense: int = 3
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    ffn_dim: int = 12288
    moe_ffn_dim: int = 2048
    n_routed_experts: int = 256  # the router's width, as published
    n_held_experts: int = 256  # how many of them this chip holds ...
    expert_offset: int = 0  # ... from this one on
    n_shared_experts: int = 1
    top_k_experts: int = 8
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 8000000.0
    rope_scaling: tuple | None = None  # plain rope; the seam _mla's tables read
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    unsupported = (
        "int8 KV cache", "speculative decoding",
        "disaggregated transfer", "tensor parallelism", "LoRA", "vision",
        "a Pallas paged_impl or scatter_impl",
    )
    counts_routed_pairs = True
    #: the chunk program takes the chunk's offset as an argument, and the
    #: length of the cached prefix it gathers as a static bucket
    chunk_offset_runtime = True

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed_experts - self.n_held_experts:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.n_held_experts} lie outside "
                f"the router's {self.n_routed_experts}"
            )
        if self.n_group != 1:
            raise NotImplementedError("GlmDsaConfig: a group-limited router (n_group > 1)")
        kinds = self.layer_kinds
        if len(kinds) != self.n_layers or len(self.mlp_kinds) != self.n_layers:
            raise ValueError("indexer_types / mlp_layer_types must name every layer")
        if kinds and kinds[0] != "full":
            raise ValueError("the first layer must have an indexer ('full')")
        if set(kinds) - {"full", "shared"} or set(self.mlp_kinds) - {"dense", "sparse"}:
            raise ValueError(f"layer kinds {set(kinds) | set(self.mlp_kinds)}")
        if "dense" in self.mlp_kinds[self.n_dense_layers:]:
            raise NotImplementedError("GlmDsaConfig: a dense layer after a routed one")

    # -- the layer pattern ------------------------------------------------------

    @property
    def layer_kinds(self) -> tuple:
        """"full" (has an indexer) or "shared" (uses the selection carried
        from the nearest full layer before it), a layer."""
        if self.indexer_types is not None:
            return tuple(self.indexer_types)
        off, freq = self.index_skip_topk_offset, self.index_topk_freq
        return tuple(
            "full" if i < off or (i - off) % freq == freq - 1 else "shared"
            for i in range(self.n_layers)
        )

    @property
    def mlp_kinds(self) -> tuple:
        if self.mlp_layer_types is not None:
            return tuple(self.mlp_layer_types)
        k = min(self.first_k_dense, self.n_layers)
        return ("dense",) * k + ("sparse",) * (self.n_layers - k)

    @property
    def full_layers(self) -> tuple:
        """The layers that have an indexer, in order."""
        return tuple(i for i, kind in enumerate(self.layer_kinds) if kind == "full")

    # -- the seam LLMEngine reads (docs/mla.md) ---------------------------------

    @property
    def model(self):
        return sys.modules[__name__]

    @property
    def cache_leaf_shapes(self) -> tuple:
        """Per-token shape of the paged leaves: the latent, the rotated key,
        the indexer's key."""
        return (
            (1, self.kv_lora_rank), (1, self.qk_rope_head_dim), (1, self.index_head_dim),
        )

    @property
    def cache_leaf_layers(self) -> tuple:
        """... and how many layers each covers: only a full layer has index keys."""
        return (self.n_layers, self.n_layers, len(self.full_layers))

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import DEEPSEEK_V2_TARGETS

        return DEEPSEEK_V2_TARGETS + ("wq_idx", "wk_idx")

    def sparse_positions(self, queries, phase: str) -> dict:
        """What ``mtpu_sparse_positions_total`` counts for a dispatch
        (``phase``: "prefill" | "decode") whose queries sit at the positions
        ``queries`` (host numbers): (query, position, layer) triples the
        indexers scored, that lie in a query's selection, and that the
        attention program computed for: every causal one in prefill (the
        flash kernel under a mask) and the selected ones in decode (gathered)."""
        dense = phase == "prefill"
        seen = np.asarray(queries, np.int64) + 1  # positions s <= t
        selected = int(np.minimum(seen, self.index_topk).sum()) * self.n_layers
        return {
            "scored": int(seen.sum()) * len(self.full_layers),
            "selected": selected,
            "attended": int(seen.sum()) * self.n_layers if dense else selected,
        }

    # -- sizes -------------------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_dense_layers(self) -> int:
        kinds = self.mlp_kinds
        return next((i for i, k in enumerate(kinds) if k != "dense"), len(kinds))

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def index_scale(self) -> float:
        return self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5

    @property
    def param_count(self) -> int:
        """Parameters held here (the held experts, not the router's width)."""
        D, H = self.dim, self.n_heads
        attn = (
            D * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
            + D * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim)
            + H * self.v_head_dim * D + self.q_lora_rank + self.kv_lora_rank + 2 * D
        )
        indexer = (
            self.q_lora_rank * self.index_n_heads * self.index_head_dim
            + D * self.index_head_dim + D * self.index_n_heads + 2 * self.index_head_dim
        )
        moe = (
            3 * D * self.moe_ffn_dim * (self.n_held_experts + self.n_shared_experts)
            + D * self.n_routed_experts + self.n_routed_experts
        )
        emb = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        return (
            emb + D + self.n_layers * attn + len(self.full_layers) * indexer
            + self.n_dense_layers * 3 * D * self.ffn_dim + self.n_moe_layers * moe
        )

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "GlmDsaConfig":
        """Test-tier config: every mechanism at a small size. Five layers
        ``full, shared, shared, full, shared`` (the first dense), a top-k of
        8 positions, 3 of 16 experts a token with a selection bias.
        ``n_held_experts=4, expert_offset=4`` gives a share."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=5, n_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=4, index_head_dim=16, index_topk=8,
            indexer_types=("full", "shared", "shared", "full", "shared"),
            mlp_layer_types=("dense",) + ("sparse",) * 4, first_k_dense=1,
            ffn_dim=128, moe_ffn_dim=32, n_routed_experts=16, n_held_experts=16,
            n_shared_experts=1, top_k_experts=3, routed_scaling_factor=2.5,
            rope_theta=10000.0, max_seq_len=512,
        )
        base.update(overrides)
        return GlmDsaConfig(**base)

    @staticmethod
    def from_hf_config(path: str | Path) -> "GlmDsaConfig":
        """From a published ``config.json``. The layer pattern is read from
        ``indexer_types`` and ``mlp_layer_types`` where the file has them,
        else from ``index_topk_freq`` / ``index_skip_topk_offset`` and
        ``first_k_dense_replace``. A file that runs a stretch of the
        published stack keeps those keys as published and names the stretch:
        ``layer_range`` ``[first, past the last]``, ``num_hidden_layers`` of
        them. A file that states the chip's share says so as DeepSeek-V2's
        does (``expert_share``: ``n_routed_experts`` then counts the held
        ones)."""
        cfg = json.loads(Path(path).read_text())
        for key, want in (
            ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
            ("attention_bias", False), ("hidden_act", "silu"), ("n_group", 1),
            ("rope_interleave", True), ("indexer_rope_interleave", True),
        ):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"GlmDsaConfig: {key}={cfg[key]!r} is not modelled (only {want!r})"
                )
        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(f"GlmDsaConfig: rope_parameters {rope!r} (only default)")
        if cfg.get("num_nextn_predict_layers", 0):
            raise NotImplementedError(
                "GlmDsaConfig: the multi-token-prediction block is not modelled "
                "(run with num_nextn_predict_layers 0: the next-token logits do "
                "not depend on it)"
            )
        share = cfg.get("expert_share") or {}
        held = int(cfg["n_routed_experts"])
        n = int(cfg["num_hidden_layers"])

        first, last = cfg.get("layer_range") or (0, n)
        if last - first != n or first < 0:
            raise ValueError(f"layer_range {[first, last]} is not {n} layers")
        off, freq = cfg.get("index_skip_topk_offset", 0), cfg.get("index_topk_freq", 1)
        dense = cfg.get("first_k_dense_replace", 0)

        def kinds(key, rule):
            # the published stack's kinds, then the stretch this file runs
            got = cfg.get(key)
            if got is None:
                got = [rule(i) for i in range(last)]
            elif len(got) < last or ("layer_range" not in cfg and len(got) != n):
                raise ValueError(
                    f"{key} names {len(got)} layers; the file runs layers {first}..{last - 1}"
                )
            return tuple(got[first:last])

        return GlmDsaConfig(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            n_layers=n,
            n_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            index_n_heads=cfg["index_n_heads"],
            index_head_dim=cfg["index_head_dim"],
            index_topk=cfg["index_topk"],
            indexer_types=kinds(
                "indexer_types",
                lambda i: "full" if i < off or (i - off) % freq == freq - 1 else "shared",
            ),
            mlp_layer_types=kinds(
                "mlp_layer_types", lambda i: "dense" if i < dense else "sparse"
            ),
            first_k_dense=max(0, dense - first),
            index_topk_freq=freq,
            index_skip_topk_offset=max(0, off - first),
            ffn_dim=cfg["intermediate_size"],
            moe_ffn_dim=cfg["moe_intermediate_size"],
            n_routed_experts=int(share.get("of", held)),
            n_held_experts=held,
            expert_offset=int(share.get("offset", 0)),
            n_shared_experts=cfg.get("n_shared_experts") or 0,
            top_k_experts=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 10000.0))),
            norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_seq_len=cfg.get("max_position_embeddings", 4096),
            tie_embeddings=cfg.get("tie_word_embeddings", False),
        )


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: GlmDsaConfig) -> dict:
    """Random init: DeepSeek-V2's tree (``dense_layers``, ``moe_layers``), a
    selection bias beside each router (drawn, not zero: a program that chose
    by the unbiased score would choose other experts), and
    ``indexer_layers``, the indexers of the full layers stacked in order."""
    k_mla, k_bias, k_idx = jax.random.split(key, 3)
    params = _mla.init_params(k_mla, cfg)
    dt = cfg.jnp_dtype
    if cfg.n_moe_layers:
        # a tenth of a sigmoid's range: moves the chosen set for a visible
        # share of the tokens without deciding it
        params["moe_layers"]["router_bias"] = 0.1 * jax.random.normal(
            k_bias, (cfg.n_moe_layers, cfg.n_routed_experts), jnp.float32
        )
    L, D, Hi, Di = len(cfg.full_layers), cfg.dim, cfg.index_n_heads, cfg.index_head_dim
    k = jax.random.split(k_idx, 3)
    params["indexer_layers"] = {
        "wq_idx": layers.init_dense(k[0], (L, cfg.q_lora_rank, Hi * Di), dtype=dt),
        "wk_idx": layers.init_dense(k[1], (L, D, Di), dtype=dt),
        "k_norm": jnp.ones((L, Di), dt),
        "k_norm_bias": jnp.zeros((L, Di), dt),
        "w_idx": layers.init_dense(k[2], (L, D, Hi), dtype=dt),
    }
    return params


def partition_specs(cfg: GlmDsaConfig) -> dict:
    refuse(cfg, "tensor parallelism")
    raise NotImplementedError("GlmDsaConfig has no partition specs")


#: published tensor names, as far as the catalog's config implies them
#: (``glm_moe_dsa`` follows ``deepseek_v3``'s naming with an ``indexer``
#: module in the attention of a full layer): ours -> theirs
HF_LAYER_NAMES = {
    "attn_norm": "input_layernorm.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "wq_a": "self_attn.q_a_proj.weight",
    "q_norm": "self_attn.q_a_layernorm.weight",
    "wq_b": "self_attn.q_b_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_norm": "self_attn.kv_a_layernorm.weight",
    "wkv_b": "self_attn.kv_b_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight", "down": "mlp.down_proj.weight",
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
    "moe_gate": "mlp.experts.{e}.gate_proj.weight",
    "moe_up": "mlp.experts.{e}.up_proj.weight",
    "moe_down": "mlp.experts.{e}.down_proj.weight",
    "wq_idx": "self_attn.indexer.wq_b.weight",
    "wk_idx": "self_attn.indexer.wk.weight",
    "k_norm": "self_attn.indexer.k_norm.weight",
    "k_norm_bias": "self_attn.indexer.k_norm.bias",
    "w_idx": "self_attn.indexer.weights_proj.weight",
}


def load_hf_weights(model_dir, cfg: GlmDsaConfig, *, quantization=None, layer_offset: int = 0):
    """A published checkpoint's tensors (``*.safetensors`` under
    ``model_dir``) as this model's tree: ``HF_LAYER_NAMES`` under
    ``model.layers.<layer_offset + i>.``, matrices transposed to ``[in, out]``,
    the held experts ``expert_offset .. +n_held_experts``, the vocabulary's
    first ``vocab_size`` rows. The rotated slices stay interleaved as
    published (the programs rotate the published pairs)."""
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
            return st.get_tensor(name)

    targets = cfg.quant_targets if quantization else ()
    dt = cfg.jnp_dtype

    def leaf(ours, names):
        mats = [get(n) for n in names]
        if mats[0].ndim == 2:
            mats = [m.T for m in mats]
        a = np.stack(mats) if len(mats) > 1 else mats[0]
        if ours in targets:
            return quantize_weight_host(a, bits_of(quantization))
        return jnp.asarray(a, jnp.float32 if ours == "router_bias" else dt)

    def stack(layer_ids, names, experts=False):
        out = {}
        for ours in names:
            theirs = HF_LAYER_NAMES[ours]
            per_layer = []
            for i in layer_ids:
                prefix = f"model.layers.{layer_offset + i}."
                if experts and "{e}" in theirs:
                    held = range(cfg.expert_offset, cfg.expert_offset + cfg.n_held_experts)
                    per_layer.append(leaf(ours, [prefix + theirs.format(e=e) for e in held]))
                else:
                    per_layer.append(leaf(ours, [prefix + theirs]))
            out[ours] = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
        return out

    attn = ("attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
    n_dense = cfg.n_dense_layers
    V = cfg.vocab_size
    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight")[:V], dt),
        "final_norm": jnp.asarray(get("model.norm.weight"), dt),
        "indexer_layers": stack(
            cfg.full_layers, ("wq_idx", "wk_idx", "k_norm", "k_norm_bias", "w_idx")
        ),
    }
    if not cfg.tie_embeddings:
        head = get("lm_head.weight")[:V].T
        params["lm_head"] = (
            quantize_weight_host(head, bits_of(quantization)) if quantization
            else jnp.asarray(head, dt)
        )
    if n_dense:
        params["dense_layers"] = stack(range(n_dense), attn + ("gate", "up", "down"))
    if cfg.n_moe_layers:
        shared = ("shared_gate", "shared_up", "shared_down") if cfg.n_shared_experts else ()
        params["moe_layers"] = stack(
            range(n_dense, cfg.n_layers),
            attn + ("router", "router_bias", "moe_gate", "moe_up", "moe_down") + shared,
            experts=True,
        )
    return params


def paged_impl_plan(cfg: GlmDsaConfig, page_size: int, impl: str | None = None,
                    scatter_impl: str = "xla", **kwargs) -> dict:
    """DeepSeek-V2's plan: XLA gathers over the latent pages, the XLA scatter."""
    return _mla.paged_impl_plan(cfg, page_size, impl, scatter_impl, **kwargs)


# -- the layer's parts ----------------------------------------------------------


@jax.named_scope(_scopes.INDEXER)
def _index_project(ip, h, c_q, cos, sin, cfg):
    """One indexer (``ip``) on the normed input h [..., D] and the query's
    latent c_q [..., q_rank]: index queries [..., Hi, Di] and the token's
    index key [..., Di], both with their first ``qk_rope_head_dim`` values
    rotated (published pairs, kept de-interleaved as the attention's), and
    the heads' weights [..., Hi] f32 with both scales folded in."""
    dt = h.dtype
    rope, Hi, Di = cfg.qk_rope_head_dim, cfg.index_n_heads, cfg.index_head_dim
    q = layers.mm(c_q, ip["wq_idx"]).astype(dt).reshape(*h.shape[:-1], Hi, Di)
    q = jnp.concatenate([_mla._rope(q[..., :rope], cos, sin), q[..., rope:]], axis=-1)
    k = layers.layer_norm(
        layers.mm(h, ip["wk_idx"]).astype(dt), ip["k_norm"].astype(jnp.float32),
        ip["k_norm_bias"].astype(jnp.float32), cfg.norm_eps,
    )
    k = jnp.concatenate(
        [_mla._rope(k[..., None, :rope], cos, sin)[..., 0, :], k[..., rope:]], axis=-1
    )
    w = layers.mm(h, ip["w_idx"]) * cfg.index_scale
    return q, w, k


def _indexer(params, i):
    """The ``i``-th full layer's indexer out of the stack (``i`` traced)."""
    return jax.tree.map(lambda a: a[i], params["indexer_layers"])


def _scan_layers(params, cfg, layer_fn, carry):
    """``layer_fn(carry, layer, cache layer, dense, is_full, full index) ->
    (carry, ys)`` over the dense layers, then the routed ones
    (``moe.scan_layers``); ys concatenated on axis 0. The carry holds the
    selection beside the activations."""
    full = np.asarray([k == "full" for k in cfg.layer_kinds])
    full_index = np.maximum(np.cumsum(full) - 1, 0).astype(np.int32)
    ys, first = [], 0
    for name, dense, n in (
        ("dense_layers", True, cfg.n_dense_layers), ("moe_layers", False, cfg.n_moe_layers),
    ):
        if n:
            def body(carry, layer, i, is_full, fi, dense=dense, first=first):
                return layer_fn(carry, layer, first + i, dense, is_full, fi)

            carry, y = _moe.scan_layers(
                params[name], body, carry,
                jnp.asarray(full[first:first + n]), jnp.asarray(full_index[first:first + n]),
            )
            ys.append(y)
            first += n
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys)


def _expand_blocks(layer, c_kv, k_pe, cfg, block: int):
    """``deepseek_v2._expand`` a block of positions at a time: latents [B, S,
    rank] and rotated keys [B, S, rope] -> keys [B, S / block, H, block, qk]
    and values [B, S / block, H, block, v], as ``selected_attention`` takes
    them. What is alive at once of the float32 product is one block's (over a
    16k prefix whole it is 2 GiB)."""
    B, S, _ = c_kv.shape

    def blocks(a):  # [B, S, w] -> [S / block, B, block, w]
        return a.reshape(B, S // block, block, a.shape[-1]).transpose(1, 0, 2, 3)

    k, v = jax.lax.map(lambda cr: _mla._expand(layer, *cr, cfg), (blocks(c_kv), blocks(k_pe)))
    return k.transpose(1, 0, 2, 3, 4), v.transpose(1, 0, 2, 3, 4)


def _full_rows(rows, cfg):
    """The full layers' rows of a per-layer output [L, ...]."""
    return rows[np.asarray(cfg.full_layers)]


# -- forward, for tests and tools -----------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: GlmDsaConfig, *,
            attn_impl: str = "flash", lora=None, return_selected: bool = False):
    """Full-sequence forward of the program's own layer, no cache: [B, S] ->
    logits [B, S, vocab] (and, asked for, each layer's selection mask
    [L, B, S, S])."""
    if lora is not None:
        refuse(cfg, "LoRA")
    logits, _, _, _, masks = _prefill_impl(
        params, tokens, None, None, None, None,
        jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32), cfg,
        q_offset=0, prefix_len=0, attn_impl=attn_impl, all_rows=True,
        return_selected=return_selected,
    )
    return (logits, masks) if return_selected else logits


# -- serving: prefill + paged decode ----------------------------------------


def _prefill_impl(params, tokens, c_pages, r_pages, i_pages, page_tables, lens, cfg, *,
                  q_offset, prefix_len: int, attn_impl: str, all_rows: bool = False,
                  return_selected: bool = False):
    """``lens`` valid tokens of [B, C] at global positions from ``q_offset``
    on (a traced scalar, or a static one) over a cached prefix gathered at
    the static length ``prefix_len >= q_offset``: writes their latents and
    index keys, attends over the selected positions of the prefix and
    themselves. Without pages (``forward``): no prefix, nothing written."""
    B, C = tokens.shape
    P = prefix_len
    if not P:
        q_offset = 0
    topk = cfg.index_topk
    selecting = P + C > topk  # else S_t is every position s <= t
    # a call over a prefix attends under a mask (the prefix's rows past the
    # run-time offset are no one's), selecting or not; its keys are the
    # prefix, its own, and a pad to whole key blocks of the attention kernel
    masked = selecting or bool(P)
    S = P + C
    if masked and S > 1024:
        S += -S % 1024
    block = _sparse.key_block(S)
    positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
    valid = jnp.arange(C)[None, :] < lens[:, None]
    cos, sin = _mla._rope_tables(positions, cfg)
    cached = c_pages is not None
    if cached:
        page_size = c_pages.shape[2]
        page_idx = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
        page_idx = jnp.where(valid, page_idx, 0)
        slot = jnp.where(valid, positions % page_size, 0)
        prefix_tables = page_tables[:, : P // page_size]
    # what a query may attend to: the prefix's positions below the offset
    # (a bucket's rows past it hold another sequence's tokens, or none) and
    # the call's own up to itself
    allowed = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(P)[None, None, :] < q_offset, (B, C, P)),
        jnp.broadcast_to(jnp.arange(C)[:, None] >= jnp.arange(S - P)[None, :], (B, C, S - P)),
    ], axis=-1)
    dt = cfg.jnp_dtype

    def layer_fn(carry, layer, li, dense, is_full, fi):
        x, sel = carry
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q_nope, q_pe, c_kv, k_pe, c_q = _mla._project(layer, h, cos, sin, cfg, with_c_q=True)
        pad = ((0, 0), (0, S - P - C), (0, 0))
        c_all, r_all = jnp.pad(c_kv, pad), jnp.pad(k_pe, pad)
        if P:
            with jax.named_scope(_scopes.PAGE_GATHER):
                pc = kv_gather(c_pages, prefix_tables, layer=li).reshape(B, P, -1)
                pr = kv_gather(r_pages, prefix_tables, layer=li).reshape(B, P, -1)
            c_all = jnp.concatenate([pc.astype(dt), c_all], axis=1)
            r_all = jnp.concatenate([pr.astype(dt), r_all], axis=1)

        def select():
            q_idx, w, k_idx = _index_project(_indexer(params, fi), h, c_q, cos, sin, cfg)
            if not selecting:
                return (allowed if masked else sel), k_idx
            k_all = jnp.pad(k_idx, pad)
            if P:
                with jax.named_scope(_scopes.INDEXER):  # the leaf's gather is the indexer's
                    pk = i_pages[fi, prefix_tables].reshape(B, P, -1)
                k_all = jnp.concatenate([pk.astype(dt), k_all], axis=1)
            scores = _sparse.index_scores(q_idx, w, k_all)
            return _sparse.select_mask(scores, allowed, topk), k_idx

        def carried():
            return sel, jnp.zeros((B, C, cfg.index_head_dim), dt)

        sel, k_idx = jax.lax.cond(is_full, select, carried)
        if masked:
            k, v = _expand_blocks(layer, c_all, r_all, cfg, block)
            q = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 1, 3)
            o = _sparse.selected_attention(
                q, k, v, sel, sm_scale=cfg.softmax_scale, impl=attn_impl
            )
            o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.v_head_dim)
            attn = layers.mm(o, layer["wo"])
        else:
            attn = _mla._expanded_attention(
                layer, q_nope, q_pe, c_all, r_all, cfg, q_offset=0, attn_impl=attn_impl
            )
        x = x + attn.astype(x.dtype)
        h, _ = _mla._mlp(layer, layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), cfg, dense, valid)
        ys = (c_kv[:, :, None, :], k_pe[:, :, None, :], k_idx[:, :, None, :])
        return (x + h, sel), ((*ys, sel) if return_selected else ys)

    sel0 = jnp.zeros((B, C, S) if masked else (), bool)
    (x, _), (c_new, r_new, i_new, *masks) = _scan_layers(
        params, cfg, layer_fn, (params["embed"][tokens], sel0)
    )
    if cached:
        # [L, B, C, 1, w] -> pages at (page_idx[b, s], slot[b, s])
        c_pages = _scatter_rows(c_pages, c_new, page_idx, slot)
        r_pages = _scatter_rows(r_pages, r_new, page_idx, slot)
        i_pages = _scatter_rows(i_pages, _full_rows(i_new, cfg), page_idx, slot)
    if not all_rows:
        last = jnp.maximum(lens - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None].repeat(x.shape[-1], -1), 1)[:, 0]
    return _mla._logits(params, x, cfg), c_pages, r_pages, i_pages, (masks[0][..., : P + C] if masks else None)


def _check_serving(cfg, k_pages, state, mesh, input_embeds=None):
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    if input_embeds is not None:
        refuse(cfg, "vision")
    if is_quantized(k_pages):
        refuse(cfg, "int8 KV cache")
    if len(state) != 1:
        raise ValueError("GlmDsaConfig's programs take state=(the indexer's pages,)")


def prefill(
    params: dict,
    tokens: jax.Array,  # [B, S] padded
    k_pages: jax.Array,  # [L, n_pages, page_size, 1, kv_lora_rank] — latents
    v_pages: jax.Array,  # [L, n_pages, page_size, 1, qk_rope_head_dim] — rotated keys
    page_tables: jax.Array,  # [B, pages_per_seq]
    seq_lens: jax.Array,  # [B] true lengths
    cfg: GlmDsaConfig,
    attn_impl: str = "flash",
    input_embeds=None,
    mesh=None,
    state: tuple = (),  # ([Lf, n_pages, page_size, 1, index_head_dim],) — index keys
    slot_ids=None,  # the seam's; nothing here is kept per slot
):
    """Process prompts, filling the three paged leaves; returns (logits_last,
    k_pages, v_pages, state). Padded positions write to trash page 0."""
    _check_serving(cfg, k_pages, state, mesh, input_embeds)
    logits, k_pages, v_pages, i_pages, _ = _prefill_impl(
        params, tokens, k_pages, v_pages, state[0], page_tables, seq_lens, cfg,
        q_offset=0, prefix_len=0, attn_impl=attn_impl,
    )
    return logits, k_pages, v_pages, (i_pages,)


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # [B, C] — one chunk of the prompt
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,
    chunk_lens: jax.Array,  # [B] valid tokens in THIS chunk
    cfg: GlmDsaConfig,
    *,
    q_offset,  # global position of the chunk's first token: int32 scalar, traced or not
    prefix_len: int | None = None,  # static: cached positions gathered (>= q_offset)
    attn_impl: str = "flash",
    mesh=None,
    state: tuple = (),
    slot_ids=None,
):
    """One chunk of a long prompt at a run-time offset: gathers
    ``prefix_len`` cached positions (``q_offset`` itself where that is
    static and no bucket is given), scores and selects over those below the
    offset and the chunk's own, attends to the selected; writes its own
    latents and index keys."""
    _check_serving(cfg, k_pages, state, mesh)
    logits, k_pages, v_pages, i_pages, _ = _prefill_impl(
        params, tokens, k_pages, v_pages, state[0], page_tables, chunk_lens, cfg,
        q_offset=q_offset, prefix_len=q_offset if prefix_len is None else prefix_len,
        attn_impl=attn_impl,
    )
    return logits, k_pages, v_pages, (i_pages,)


def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 — current token per slot
    positions: jax.Array,  # [B] int32 — its position
    k_pages: jax.Array,  # latents
    v_pages: jax.Array,  # rotated keys
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool — live slots (dead slots write trash page 0)
    cfg: GlmDsaConfig,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,
    mesh=None,
    return_counts: bool = False,
    state: tuple = (),  # (the indexer's pages,)
):
    """One token of batched decode: in a full layer the index scores over the
    slot's live pages of the third leaf and their exact top-k, in every layer
    the absorbed attention over the selected latents alone (gathered); the
    pages are read-only inside the layer scans and every layer's new rows are
    scattered after them. Returns (logits [B, vocab], k_pages, v_pages,
    state) and, with ``return_counts``, the routed pairs [held, all]."""
    _check_serving(cfg, k_pages, state, mesh)
    paged_impl_plan(cfg, k_pages.shape[2], impl, scatter_impl, kv_dtype=k_pages.dtype)
    (i_pages,) = state
    page_size = k_pages.shape[2]
    B = tokens.shape[0]
    cos, sin = _mla._rope_tables(positions, cfg)  # [B, rope/2]
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    page_idx = jnp.where(active, page_idx, 0)
    slot = jnp.where(active, positions % page_size, 0)
    prefix_lens = jnp.where(active, positions, 0).astype(jnp.int32)
    H, vd, topk = cfg.n_heads, cfg.v_head_dim, cfg.index_topk

    def layer_fn(carry, layer, li, dense, is_full, fi):
        x, sel, counts_sel = carry
        dt = x.dtype
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q_nope, q_pe, c_kv, k_pe, c_q = _mla._project(layer, h, cos, sin, cfg, with_c_q=True)

        def select():
            q_idx, w, k_idx = _index_project(_indexer(params, fi), h, c_q, cos, sin, cfg)
            scores = _sparse.paged_index_scores(
                q_idx, w, i_pages, fi, page_tables, prefix_lens, k_idx
            )
            return (*_sparse.select_positions(scores, topk), k_idx)

        def carried():
            return sel, counts_sel, jnp.zeros((B, cfg.index_head_dim), dt)

        sel, counts_sel, k_idx = jax.lax.cond(is_full, select, carried)
        wk, wv, sk, sv = _mla._kvb_halves(layer["wkv_b"], cfg, dt)
        q_lat = jnp.einsum(
            "bhd,chd->bhc", (q_nope * sk).astype(dt), wk, preferred_element_type=jnp.float32,
        )
        o_lat = _sparse.paged_latent_decode_attention_selected(
            q_lat, q_pe, k_pages, v_pages, li, page_tables, sel, counts_sel,
            prefix_lens, c_kv, k_pe, sm_scale=cfg.softmax_scale,
        )  # [B, H, rank] f32
        o = jnp.einsum(
            "bhc,chd->bhd", o_lat.astype(dt), wv, preferred_element_type=jnp.float32
        ) * sv
        x = x + layers.mm(o.astype(dt).reshape(-1, H * vd), layer["wo"]).astype(dt)
        h, counts = _mla._mlp(
            layer, layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), cfg, dense, active
        )
        return (x + h, sel, counts_sel), (c_kv[:, None, :], k_pe[:, None, :],
                                          k_idx[:, None, :], counts)

    carry0 = (params["embed"][tokens], jnp.zeros((B, topk), jnp.int32),
              jnp.zeros((B, topk), bool))
    (x, _, _), (c_new, r_new, i_new, counts) = _scan_layers(params, cfg, layer_fn, carry0)
    # [L, B, 1, w]: one scatter for every layer's token
    k_pages = _scatter_rows(k_pages, c_new, page_idx, slot)
    v_pages = _scatter_rows(v_pages, r_new, page_idx, slot)
    i_pages = _scatter_rows(i_pages, _full_rows(i_new, cfg), page_idx, slot)
    logits = _mla._logits(params, x, cfg)
    if return_counts:
        return logits, k_pages, v_pages, (i_pages,), counts.sum(axis=0)
    return logits, k_pages, v_pages, (i_pages,)
