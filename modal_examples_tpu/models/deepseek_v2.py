"""DeepSeek-V2: multi-head latent attention (MLA) over a latent paged cache,
a leading dense layer, then shared experts beside routed ones chosen by a
group-limited router, served on the engine's normal path.

What the cache holds per token and layer is one normalised latent
(``kv_lora_rank`` values) and one rotated key (``qk_rope_head_dim`` values)
shared by every head: the two paged leaves ``PagedKVCache`` keeps, with the
shapes ``DeepseekV2Config.cache_leaf_shapes`` declares (docs/mla.md). Two
attention paths read it:

- **prefill, expanded**: latents are expanded by ``W_kvb`` to per-head keys
  and values (under ``mtpu.latent_expand``), the call's own and, in a chunk at
  an offset, the cached prefix's again, and the flash kernel attends with a
  q/k width of ``nope + rope`` and a value width of ``v_head_dim``;
- **decode, absorbed**: ``W_kvb``'s key half is absorbed into the query
  (``q_lat = q_nope W_kvb^K``), attention runs over ``[c_kv, k_pe]`` itself
  (ops.paged_latent_decode_attention_chunked: PR 25's chunk loop, a page read
  once for all heads), and the value half is applied to the result.

The routed layers compute "experts held here" (``model-configs`` section 4):
the router keeps its published width, this chip holds ``n_held_experts`` of
them from ``expert_offset`` on and computes their part of the sum through
``moe.moe_swiglu_sparse`` (only the pairs that land here; no capacity, no
drop). On one chip the layer runs without its exchange: what the absent
experts would add is left out, here and in the reference alike.

Departures from ``modeling_deepseek``, none of which changes a result: the
rotated slices are kept de-interleaved (the pairs ``(2i, 2i+1)`` of the
published layout are rotated, then stored evens first, odds after, queries
and cached keys alike, so every dot product is the published one); the
cache keeps ``c_kv`` *after* its RMSNorm. The plain reference is
``models/deepseek_v2_reference.py``.

What this model does not do yet is refused by name where the engine is
built (``DeepseekV2Config.unsupported``): an int8 KV cache, speculation,
disaggregated transfer, tensor parallelism, LoRA, vision.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from ..ops import (
    is_quantized,
    kv_gather,
    paged_latent_decode_attention_chunked,
)
from ..ops import scopes as _scopes
from ..ops.flash_attention import flash_attention_chunked
from . import deepseek_v2_reference as _ref
from . import layers
from .layers import refuse
from .layers import scatter_rows as _scatter_rows
from . import moe as _moe


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    dim: int = 5120
    n_layers: int = 60
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 12288  # the leading dense layers' SwiGLU
    moe_ffn_dim: int = 1536  # one routed expert; a shared expert is as wide
    n_routed_experts: int = 160  # the router's width, as published
    n_held_experts: int = 160  # how many of them this chip holds ...
    expert_offset: int = 0  # ... from this one on
    n_shared_experts: int = 2
    top_k_experts: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense: int = 1
    rope_theta: float = 10000.0
    rope_scaling: tuple | None = None  # tuple(sorted(yarn dict.items()))
    norm_eps: float = 1e-6
    max_seq_len: int = 163840
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    #: features of the engine this model's programs do not implement yet:
    #: ``LLMEngine`` refuses each by name where it is asked for
    unsupported = (
        "int8 KV cache", "speculative decoding",
        "disaggregated transfer", "tensor parallelism", "LoRA", "vision",
        "a Pallas paged_impl or scatter_impl",
    )
    #: the decode block hands back the routed pairs it counted
    counts_routed_pairs = True

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed_experts - self.n_held_experts:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.n_held_experts} lie outside "
                f"the router's {self.n_routed_experts}"
            )
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group groups")

    # -- the seam LLMEngine reads (docs/mla.md) ---------------------------------

    @property
    def model(self):
        """The module that holds this configuration's programs."""
        return sys.modules[__name__]

    @property
    def cache_leaf_shapes(self) -> tuple[tuple[int, int], tuple[int, int]]:
        """Per-token shape of the two paged leaves: the latent, the rotated key."""
        return ((1, self.kv_lora_rank), (1, self.qk_rope_head_dim))

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import DEEPSEEK_V2_TARGETS

        return DEEPSEEK_V2_TARGETS

    # -- sizes -------------------------------------------------------------------

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_dense_layers(self) -> int:
        return min(self.first_k_dense, self.n_layers)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return _ref.softmax_scale(self)

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
        moe = (
            3 * D * self.moe_ffn_dim * (self.n_held_experts + self.n_shared_experts)
            + D * self.n_routed_experts
        )
        emb = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        return (
            emb + D + self.n_layers * attn
            + self.n_dense_layers * 3 * D * self.ffn_dim + self.n_moe_layers * moe
        )

    @staticmethod
    def tiny(vocab_size: int = 512, **overrides) -> "DeepseekV2Config":
        """Test-tier config: every mechanism at a small size (two groups of
        the four kept, three of sixteen experts a token, the rotated slice
        yarn-scaled). ``n_held_experts=4, expert_offset=4`` gives a share."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=3, n_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            ffn_dim=128, moe_ffn_dim=32, n_routed_experts=16, n_held_experts=16,
            n_shared_experts=2, top_k_experts=3, n_group=4, topk_group=2,
            routed_scaling_factor=4.0, first_k_dense=1, max_seq_len=512,
            rope_scaling=tuple(sorted({
                "type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
                "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
            }.items())),
        )
        base.update(overrides)
        return DeepseekV2Config(**base)

    @staticmethod
    def from_hf_config(path: str | Path) -> "DeepseekV2Config":
        """From a published ``config.json``. A file that states the chip's
        share says so beside the published keys: ``n_routed_experts`` then
        counts the experts held here and ``expert_share`` is ``{"of": the
        router's width, "offset": the first held expert}``."""
        cfg = json.loads(Path(path).read_text())
        for key, want in (
            ("scoring_func", "softmax"), ("topk_method", "group_limited_greedy"),
            ("moe_layer_freq", 1), ("attention_bias", False), ("hidden_act", "silu"),
        ):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"DeepseekV2Config: {key}={cfg[key]!r} is not modelled (only {want!r})"
                )
        if cfg.get("q_lora_rank") is None:
            raise NotImplementedError(
                "DeepseekV2Config: a model without q_lora_rank (a full-rank query "
                "projection) is not modelled"
            )
        scaling = cfg.get("rope_scaling")
        if scaling is not None and scaling.get("type", scaling.get("rope_type")) != "yarn":
            raise NotImplementedError(f"DeepseekV2Config: rope_scaling {scaling!r} (only yarn)")
        share = cfg.get("expert_share") or {}
        held = int(cfg["n_routed_experts"])
        return DeepseekV2Config(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            ffn_dim=cfg["intermediate_size"],
            moe_ffn_dim=cfg["moe_intermediate_size"],
            n_routed_experts=int(share.get("of", held)),
            n_held_experts=held,
            expert_offset=int(share.get("offset", 0)),
            n_shared_experts=cfg.get("n_shared_experts") or 0,
            top_k_experts=cfg["num_experts_per_tok"],
            n_group=cfg.get("n_group", 1),
            topk_group=cfg.get("topk_group", 1),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            first_k_dense=cfg.get("first_k_dense_replace", 0),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rope_scaling=tuple(sorted(scaling.items())) if scaling else None,
            norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_seq_len=cfg.get("max_position_embeddings", 4096),
            tie_embeddings=cfg.get("tie_word_embeddings", False),
        )


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: DeepseekV2Config) -> dict:
    """Random init; the layers of a kind stacked on axis 0 for the scans:
    ``dense_layers`` (the leading SwiGLU layers) and ``moe_layers``."""
    dt = cfg.jnp_dtype
    D, H = cfg.dim, cfg.n_heads

    def dense(k, *shape):
        return layers.init_dense(k, shape, dtype=dt)

    def attention(k, L):
        k = jax.random.split(k, 5)
        return {
            "attn_norm": jnp.ones((L, D), dt),
            "wq_a": dense(k[0], L, D, cfg.q_lora_rank),
            "q_norm": jnp.ones((L, cfg.q_lora_rank), dt),
            "wq_b": dense(k[1], L, cfg.q_lora_rank, H * cfg.qk_head_dim),
            "wkv_a": dense(k[2], L, D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_norm": jnp.ones((L, cfg.kv_lora_rank), dt),
            "wkv_b": dense(
                k[3], L, cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            ),
            "wo": dense(k[4], L, H * cfg.v_head_dim, D),
            "mlp_norm": jnp.ones((L, D), dt),
        }

    keys = jax.random.split(key, 12)
    params = {
        "embed": layers.init_dense(keys[0], (cfg.vocab_size, D), scale=0.02, dtype=dt),
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[1], D, cfg.vocab_size)
    if cfg.n_dense_layers:
        L, F = cfg.n_dense_layers, cfg.ffn_dim
        params["dense_layers"] = {
            **attention(keys[2], L),
            "gate": dense(keys[3], L, D, F),
            "up": dense(keys[4], L, D, F),
            "down": dense(keys[5], L, F, D),
        }
    if cfg.n_moe_layers:
        L, E, F = cfg.n_moe_layers, cfg.n_held_experts, cfg.moe_ffn_dim
        S = cfg.n_shared_experts * F
        params["moe_layers"] = {
            **attention(keys[6], L),
            "router": dense(keys[7], L, D, cfg.n_routed_experts),
            "moe_gate": dense(keys[8], L, E, D, F),
            "moe_up": dense(keys[9], L, E, D, F),
            "moe_down": dense(keys[10], L, E, F, D),
        }
        if S:
            ks = jax.random.split(keys[11], 3)
            params["moe_layers"].update(
                shared_gate=dense(ks[0], L, D, S), shared_up=dense(ks[1], L, D, S),
                shared_down=dense(ks[2], L, S, D),
            )
    return params


def partition_specs(cfg: DeepseekV2Config) -> dict:
    refuse(cfg, "tensor parallelism")
    raise NotImplementedError("DeepseekV2Config has no partition specs")


def load_hf_weights(model_dir, cfg, **kwargs):
    raise NotImplementedError(
        "DeepseekV2Config: loading a published checkpoint is not implemented "
        "(seeded weights only; models/deepseek_v2.py)"
    )


def paged_impl_plan(
    cfg: DeepseekV2Config, page_size: int, impl: str | None = None,
    scatter_impl: str = "xla", *, kv_dtype="bfloat16", mesh=None, warn: bool = True,
    expert_dtype=None,
) -> dict:
    """What runs for this model: the chunked XLA loop over the latent pages
    and the XLA scatter. The ragged kernel refuses a 576-wide head
    (ops.paged_attention.ragged_shapes_ok), so anything else is refused here.
    ``expert_scan``: the form of the routed experts' tile loop in a decode
    step, ``moe.expert_scan_form``'s choice for experts of ``expert_dtype``
    (unset: the model's own)."""
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
        "expert_scan": _moe.expert_scan_form(
            1, cfg.dim, cfg.moe_ffn_dim, expert_dtype or cfg.dtype
        ) if cfg.n_moe_layers else None,
    }


# -- the layer's parts ----------------------------------------------------------


def _rope_tables(positions, cfg: DeepseekV2Config):
    """cos/sin [..., S, rope/2] in f32 at yarn's frequencies."""
    scaling = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    inv_freq = jnp.asarray(
        _ref.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, scaling), jnp.float32
    )
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    m = _ref.rope_mscale(scaling)
    return jnp.cos(angle) * m, jnp.sin(angle) * m


def _rope(x, cos, sin):
    """Rotate the published pairs ``(2i, 2i+1)`` of x [..., S, heads, rope]
    and keep them de-interleaved (evens, then odds). cos/sin: [..., S, rope/2]."""
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    cos, sin = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _project(layer, h, cos, sin, cfg, *, with_c_q: bool = False):
    """h [..., S, D] (normed) -> q_nope [..., S, H, nope], q_pe [..., S, H,
    rope] rotated, c_kv [..., S, rank] normalised, k_pe [..., S, rope] rotated
    (and, asked for, the query's normalised latent c_q [..., S, q_rank] fifth:
    what a model with an indexer projects its index queries from)."""
    dt = h.dtype
    H, nope, rank = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = layers.rms_norm(layers.mm(h, layer["wq_a"]).astype(dt), layer["q_norm"], cfg.norm_eps)
    q = layers.mm(c_q, layer["wq_b"]).astype(dt).reshape(*h.shape[:-1], H, cfg.qk_head_dim)
    kv_a = layers.mm(h, layer["wkv_a"]).astype(dt)
    c_kv = layers.rms_norm(kv_a[..., :rank], layer["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv_a[..., None, rank:], cos, sin)[..., 0, :]
    out = (q[..., :nope], _rope(q[..., nope:], cos, sin), c_kv, k_pe)
    return (*out, c_q) if with_c_q else out


def _kvb_halves(w, cfg, dt):
    """``W_kvb`` [rank, H * (nope + v)] as its key half [rank, H, nope] and
    value half [rank, H, v] at ``dt``, with the per-column scales of an int8
    weight ([H, nope], [H, v]; 1.0 for a plain one) to apply outside."""
    H, nope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    quantized = hasattr(w, "scale")
    full = (w.q if quantized else w).astype(dt).reshape(cfg.kv_lora_rank, H, nope + vd)
    if quantized:
        scale = w.scale.reshape(H, nope + vd)
        return full[..., :nope], full[..., nope:], scale[:, :nope], scale[:, nope:]
    return full[..., :nope], full[..., nope:], 1.0, 1.0


@jax.named_scope(_scopes.LATENT_EXPAND)
def _expand(layer, c_kv, k_pe, cfg):
    """Latents [B, S, rank] and rotated keys [B, S, rope] -> per-head keys
    [B, H, S, nope + rope] and values [B, H, S, v]."""
    B, S, _ = c_kv.shape
    H, nope = cfg.n_heads, cfg.qk_nope_head_dim
    kv = layers.mm(c_kv, layer["wkv_b"]).astype(c_kv.dtype).reshape(
        B, S, H, nope + cfg.v_head_dim
    )
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, k_pe.shape[-1]))],
        axis=-1,
    )
    return k.transpose(0, 2, 1, 3), kv[..., nope:].transpose(0, 2, 1, 3)


def _expanded_attention(layer, q_nope, q_pe, c_kv, k_pe, cfg, *, q_offset, attn_impl):
    """Causal attention of the queries [B, C, H, .] at ``q_offset`` over the
    latents [B, S_kv, .] (a cached prefix first, then the call's own)."""
    B, C = q_nope.shape[:2]
    k, v = _expand(layer, c_kv, k_pe, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 1, 3)  # [B, H, C, qk]
    with jax.named_scope(_scopes.ATTENTION):
        if attn_impl == "flash":
            o = flash_attention_chunked(
                q, k, v, q_offset=q_offset, sm_scale=cfg.softmax_scale
            )
        else:
            from ..ops import reference as _ops_ref

            o = _ops_ref.attention_chunked(
                q, k, v, q_offset=q_offset, sm_scale=cfg.softmax_scale
            )
    o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * cfg.v_head_dim)
    return layers.mm(o, layer["wo"])


def _mlp(layer, h, cfg, dense: bool, token_mask):
    """h [..., D] (normed) -> (out [..., D], routed-pair counts [2] int32)."""
    if dense:
        out = layers.swiglu_mlp({k: layer[k] for k in ("gate", "up", "down")}, h)
        return out, jnp.zeros((2,), jnp.int32)
    flat = h.reshape(-1, cfg.dim)
    # GLM-5.2's router (models/glm_dsa.py runs this layer too): each expert
    # scored on its own, chosen with the layer's selection bias
    biased = {"score": getattr(cfg, "scoring_func", "softmax")}
    if "router_bias" in layer:
        biased["bias"] = layer["router_bias"]
    out, counts = _moe.moe_swiglu_routed(
        layer["router"], *(layer[n] for n in _moe.EXPERT_LEAVES), flat, cfg.top_k_experts,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        scale=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
        expert_offset=cfg.expert_offset, **biased,
        token_mask=None if token_mask is None else token_mask.reshape(-1),
        layer=layer.get("expert_layer"),
    )
    if cfg.n_shared_experts:
        out = out + layers.swiglu_mlp(
            {k: layer[f"shared_{k}"] for k in ("gate", "up", "down")}, flat
        ).astype(jnp.float32)
    return out.astype(h.dtype).reshape(h.shape), counts


def _scan_layers(params, cfg, layer_fn, x, per_layer=None):
    """Run ``layer_fn(x, layer, cache layer index, dense) -> (x, ys)`` over
    the dense layers, then the routed ones (``moe.scan_layers``: a routed
    layer's dict holds the experts' whole stacks and its own index into
    them, not its slice of them); ys concatenated on axis 0. ``per_layer``
    [L, ...], if given, reaches the layer sliced, as ``layer["per_layer"]``."""
    ys = []
    first = 0
    for name, dense, n in (
        ("dense_layers", True, cfg.n_dense_layers), ("moe_layers", False, cfg.n_moe_layers),
    ):
        if n:
            stack = dict(params[name])
            if per_layer is not None:
                stack["per_layer"] = per_layer[first:first + n]

            def body(x, layer, i, dense=dense, first=first):
                return layer_fn(x, layer, first + i, dense)

            x, y = _moe.scan_layers(stack, body, x)
            ys.append(y)
            first += n
    return x, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys)


def _logits(params, x, cfg):
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return layers.mm(x, head)


# -- forward, for tests and tools -----------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: DeepseekV2Config, *,
            attn_impl: str = "flash", lora=None):
    """Full-sequence forward of the program's own layer (expanded attention,
    sparse dispatch), no cache: [B, S] -> logits [B, S, vocab]."""
    if lora is not None:
        refuse(cfg, "LoRA")
    B, S = tokens.shape
    cos, sin = _rope_tables(jnp.broadcast_to(jnp.arange(S), (B, S)), cfg)

    def layer_fn(x, layer, _li, dense):
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q_nope, q_pe, c_kv, k_pe = _project(layer, h, cos, sin, cfg)
        x = x + _expanded_attention(
            layer, q_nope, q_pe, c_kv, k_pe, cfg, q_offset=0, attn_impl=attn_impl
        ).astype(x.dtype)
        h, _ = _mlp(layer, layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), cfg, dense, None)
        return x + h, ()

    x, _ = _scan_layers(params, cfg, layer_fn, params["embed"][tokens])
    return _logits(params, x, cfg)


# -- serving: prefill + paged decode ----------------------------------------


def _prefill_impl(params, tokens, c_pages, r_pages, page_tables, lens, cfg, *,
                  q_offset: int, attn_impl: str):
    """``lens`` valid tokens of [B, C] at global positions from ``q_offset``
    on: writes their latents, attends over the cached prefix and themselves."""
    B, C = tokens.shape
    page_size = c_pages.shape[2]
    positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
    valid = jnp.arange(C)[None, :] < lens[:, None]
    cos, sin = _rope_tables(positions, cfg)
    page_idx = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
    page_idx = jnp.where(valid, page_idx, 0)
    slot = jnp.where(valid, positions % page_size, 0)
    # the cached prefix, page-aligned (q_offset is a multiple of a bucket)
    n_prefix_pages = q_offset // page_size
    prefix_tables = page_tables[:, :n_prefix_pages]

    def layer_fn(x, layer, li, dense):
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q_nope, q_pe, c_kv, k_pe = _project(layer, h, cos, sin, cfg)
        c_all, r_all = c_kv, k_pe
        if n_prefix_pages:
            with jax.named_scope(_scopes.PAGE_GATHER):
                pc = kv_gather(c_pages, prefix_tables, layer=li).reshape(B, q_offset, -1)
                pr = kv_gather(r_pages, prefix_tables, layer=li).reshape(B, q_offset, -1)
            c_all = jnp.concatenate([pc.astype(c_kv.dtype), c_kv], axis=1)
            r_all = jnp.concatenate([pr.astype(k_pe.dtype), k_pe], axis=1)
        x = x + _expanded_attention(
            layer, q_nope, q_pe, c_all, r_all, cfg, q_offset=q_offset, attn_impl=attn_impl
        ).astype(x.dtype)
        h, _ = _mlp(layer, layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), cfg, dense, valid)
        return x + h, (c_kv[:, :, None, :], k_pe[:, :, None, :])

    x, (c_new, r_new) = _scan_layers(params, cfg, layer_fn, params["embed"][tokens])
    # [L, B, C, 1, w] -> pages at (page_idx[b, s], slot[b, s])
    c_pages = _scatter_rows(c_pages, c_new, page_idx, slot)
    r_pages = _scatter_rows(r_pages, r_new, page_idx, slot)
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None].repeat(x.shape[-1], -1), 1)[:, 0]
    return _logits(params, x_last, cfg), c_pages, r_pages


def _check_serving(cfg, k_pages, mesh, input_embeds=None):
    if mesh is not None:
        refuse(cfg, "tensor parallelism")
    if input_embeds is not None:
        refuse(cfg, "vision")
    if is_quantized(k_pages):
        refuse(cfg, "int8 KV cache")


def prefill(
    params: dict,
    tokens: jax.Array,  # [B, S] padded
    k_pages: jax.Array,  # [L, n_pages, page_size, 1, kv_lora_rank] — latents
    v_pages: jax.Array,  # [L, n_pages, page_size, 1, qk_rope_head_dim] — rotated keys
    page_tables: jax.Array,  # [B, pages_per_seq]
    seq_lens: jax.Array,  # [B] true lengths
    cfg: DeepseekV2Config,
    attn_impl: str = "flash",
    input_embeds=None,
    mesh=None,
):
    """Process prompts, filling the latent cache; returns (logits_last,
    k_pages, v_pages), as ``llama.prefill`` does. Padded positions write to
    reserved trash page 0."""
    _check_serving(cfg, k_pages, mesh, input_embeds)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, page_tables, seq_lens, cfg,
        q_offset=0, attn_impl=attn_impl,
    )


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # [B, C] — one chunk of the prompt
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,
    chunk_lens: jax.Array,  # [B] valid tokens in THIS chunk
    cfg: DeepseekV2Config,
    *,
    q_offset: int,  # global position of the chunk's first token (static)
    attn_impl: str = "flash",
    mesh=None,
):
    """One chunk of a long prompt: attends to the cached prefix's latents,
    expanded again by ``W_kvb``, and to itself; writes its own latents."""
    _check_serving(cfg, k_pages, mesh)
    return _prefill_impl(
        params, tokens, k_pages, v_pages, page_tables, chunk_lens, cfg,
        q_offset=q_offset, attn_impl=attn_impl,
    )


def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 — current token per slot
    positions: jax.Array,  # [B] int32 — its position
    k_pages: jax.Array,  # latents
    v_pages: jax.Array,  # rotated keys
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool — live slots (dead slots write trash page 0)
    cfg: DeepseekV2Config,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,
    mesh=None,
    return_counts: bool = False,
):
    """One token of batched decode against the latent cache, absorbed: the
    pages are read-only inside the layer scans, every layer's new latent is
    scattered in one update after them (``llama.decode_step``'s structure).
    Returns (logits [B, vocab], k_pages, v_pages) and, with
    ``return_counts``, [2] int32: the live slots' routed pairs that landed
    on held experts, and all of them, over the layers."""
    _check_serving(cfg, k_pages, mesh)
    paged_impl_plan(cfg, k_pages.shape[2], impl, scatter_impl, kv_dtype=k_pages.dtype)
    page_size = k_pages.shape[2]
    x = params["embed"][tokens]  # [B, D]
    cos, sin = _rope_tables(positions, cfg)  # [B, rope/2]
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    page_idx = jnp.where(active, page_idx, 0)
    slot = jnp.where(active, positions % page_size, 0)
    prefix_lens = jnp.where(active, positions, 0).astype(jnp.int32)
    H, vd = cfg.n_heads, cfg.v_head_dim

    def layer_fn(x, layer, li, dense):
        dt = x.dtype
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q_nope, q_pe, c_kv, k_pe = _project(layer, h, cos, sin, cfg)
        wk, wv, sk, sv = _kvb_halves(layer["wkv_b"], cfg, dt)
        q_lat = jnp.einsum(
            "bhd,chd->bhc", (q_nope * sk).astype(dt), wk,
            preferred_element_type=jnp.float32,
        )
        o_lat = paged_latent_decode_attention_chunked(
            q_lat, q_pe, k_pages, layer["per_layer"], li, page_tables, prefix_lens,
            c_kv, k_pe,
            sm_scale=cfg.softmax_scale,
        )  # [B, H, rank] f32
        o = jnp.einsum(
            "bhc,chd->bhd", o_lat.astype(dt), wv, preferred_element_type=jnp.float32
        ) * sv
        x = x + layers.mm(o.astype(dt).reshape(-1, H * vd), layer["wo"]).astype(dt)
        h, counts = _mlp(
            layer, layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), cfg, dense, active
        )
        return x + h, (c_kv[:, None, :], k_pe[:, None, :], counts)

    # the rotated keys reach a layer as its slice, the latents whole (see
    # ops.paged_latent_decode_attention_chunked on why)
    x, (c_new, r_new, counts) = _scan_layers(params, cfg, layer_fn, x, per_layer=v_pages)
    # [L, B, 1, w]: one scatter for every layer's token
    k_pages = _scatter_rows(k_pages, c_new, page_idx, slot)
    v_pages = _scatter_rows(v_pages, r_new, page_idx, slot)
    logits = _logits(params, x, cfg)
    if return_counts:
        return logits, k_pages, v_pages, counts.sum(axis=0)
    return logits, k_pages, v_pages
