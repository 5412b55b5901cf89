"""Llama-family decoder LM — the framework's flagship model.

Serves the north-star config (BASELINE.md: Llama-2-7B at >= A100-class
tok/s/chip on v5e) and the LLM workloads the reference delegates to
vLLM/SGLang/TRT-LLM (06_gpu_and_ml/llm-serving/vllm_inference.py,
unsloth_finetune.py). Architecture covers Llama 2/3 and friends: RMSNorm,
RoPE, GQA, SwiGLU.

TPU-first design:
- parameters are a pytree of bf16 arrays; ``partition_specs()`` gives the
  tensor-parallel NamedSharding layout (column-parallel wq/wk/wv/gate/up,
  row-parallel wo/down — XLA inserts the psum over the ``tensor`` ICI axis);
- training/prefill attention is the Pallas flash kernel; serving decode is
  the Pallas ragged paged kernel against an HBM page cache;
- per-layer weights are stacked along a leading axis and the layer loop is a
  ``lax.scan`` — one compiled layer body instead of n_layers copies (compile
  time and code size stay O(1) in depth);
- init is sharded: each weight is created directly on its target devices via
  jit so a 7B model never materializes on one host.

HF interop: ``load_hf_weights()`` maps safetensors checkpoints (the HF cache
volume pattern, vllm_inference.py:77) into this tree without a 2x RAM spike.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import (
    is_quantized,
    kv_gather,
    kv_scatter,
    mesh_tp_degree,
    paged_decode_attention_chunked,
    sharded_flash_attention,
    sharded_flash_attention_chunked,
    sharded_ragged_decode,
    sharded_scatter_kv_pages,
)
from ..ops import scopes as _scopes
from . import layers
from . import moe as _moe


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    # MoE (Mixtral-style): n_experts > 0 replaces the dense SwiGLU MLP with
    # a routed expert MLP (models.moe): every expert on every token in
    # ``forward``, only the pairs the router chose in the serving programs
    n_experts: int = 0
    top_k_experts: int = 2
    expert_capacity_factor: float = 1.5
    # llama3.1-style rope scaling (HF config 'rope_scaling'); hashable for
    # static jit args
    rope_scaling: tuple | None = None  # tuple(sorted(dict.items())) or None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    # -- the seam LLMEngine reads (docs/mla.md): a configuration names the
    # module that holds its programs (init_params, prefill, prefill_chunk,
    # decode_step, paged_impl_plan, partition_specs, load_hf_weights), the
    # per-token shape of the two paged cache leaves, and its int8 targets

    @property
    def model(self):
        import sys

        return sys.modules[__name__]

    @property
    def cache_leaf_shapes(self) -> tuple[tuple[int, int], tuple[int, int]]:
        kv = (self.n_kv_heads, self.head_dim)
        return (kv, kv)

    @property
    def quant_targets(self) -> tuple[str, ...]:
        from .quantize import LLAMA_TARGETS

        return LLAMA_TARGETS

    @property
    def counts_routed_pairs(self) -> bool:
        """A routed model's decode block hands back the pairs it counted."""
        return self.n_experts > 0

    @property
    def param_count(self) -> int:
        emb = self.vocab_size * self.dim * (1 if self.tie_embeddings else 2)
        if self.n_experts > 0:
            mlp = self.n_experts * 3 * self.dim * self.ffn_dim + self.dim * self.n_experts
        else:
            mlp = 3 * self.dim * self.ffn_dim  # gate/up/down
        per_layer = (
            self.dim * self.head_dim * (self.n_heads + 2 * self.n_kv_heads)  # qkv
            + self.n_heads * self.head_dim * self.dim  # o
            + mlp
            + 2 * self.dim  # norms
        )
        return emb + self.n_layers * per_layer + self.dim

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, rope_theta=500000.0, max_seq_len=8192,
        )

    @staticmethod
    def llama31_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, rope_theta=500000.0, max_seq_len=131072,
            rope_scaling=(
                ("factor", 8.0), ("high_freq_factor", 4.0),
                ("low_freq_factor", 1.0),
                ("original_max_position_embeddings", 8192),
            ),
        )

    @staticmethod
    def llama32_1b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
            ffn_dim=8192, rope_theta=500000.0, max_seq_len=131072,
            tie_embeddings=True,
            rope_scaling=(
                ("factor", 32.0), ("high_freq_factor", 4.0),
                ("low_freq_factor", 1.0),
                ("original_max_position_embeddings", 8192),
            ),
        )

    @staticmethod
    def mistral_7b() -> "LlamaConfig":
        # sliding-window attention not yet modeled; full attention within
        # max_seq_len is exact for contexts <= the window (4096)
        return LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, rope_theta=10000.0, max_seq_len=4096,
        )

    @staticmethod
    def mixtral_8x7b() -> "LlamaConfig":
        # the Mixtral-shape MoE (reference serves MoE models engine-side:
        # vllm_inference.py:54-58, sglang_low_latency.py:67)
        return LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, rope_theta=1e6, max_seq_len=32768,
            n_experts=8, top_k_experts=2,
        )

    @staticmethod
    def tiny_moe(vocab_size: int = 512) -> "LlamaConfig":
        """Test-tier Mixtral-shape config (cheap-mode switch, SURVEY.md §4)."""
        return LlamaConfig(
            vocab_size=vocab_size, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=256, max_seq_len=256, n_experts=4, top_k_experts=2,
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """Test-tier config (the reference's cheap-mode switch, SURVEY.md §4)."""
        return LlamaConfig(
            vocab_size=vocab_size, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=256, max_seq_len=256,
        )

    @staticmethod
    def from_hf_config(path: str | Path) -> "LlamaConfig":
        cfg = json.loads(Path(path).read_text())
        return LlamaConfig(
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            ffn_dim=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_seq_len=cfg.get("max_position_embeddings", 4096),
            tie_embeddings=cfg.get("tie_word_embeddings", False),
            n_experts=cfg.get("num_local_experts", 0),
            top_k_experts=cfg.get("num_experts_per_tok", 2),
            rope_scaling=(
                tuple(sorted(cfg["rope_scaling"].items()))
                if isinstance(cfg.get("rope_scaling"), dict)
                and cfg["rope_scaling"].get("rope_type", cfg["rope_scaling"].get("type")) == "llama3"
                else None
            ),
        )


# -- parameters -------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Random init; per-layer weights stacked on axis 0 for the scan."""
    dt = cfg.jnp_dtype
    D, H, KVH, hd, F, L = (
        cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
        cfg.n_layers,
    )
    keys = jax.random.split(key, 10)

    def dense(k, *shape):
        return layers.init_dense(k, shape, dtype=dt)

    if cfg.n_experts > 0:
        E = cfg.n_experts
        k9 = jax.random.split(keys[9])[0]
        mlp = {
            "router": dense(keys[5], L, D, E),
            "moe_gate": dense(keys[6], L, E, D, F),
            "moe_up": dense(keys[7], L, E, D, F),
            "moe_down": dense(k9, L, E, F, D),
        }
    else:
        mlp = {
            "gate": dense(keys[5], L, D, F),
            "up": dense(keys[6], L, D, F),
            "down": dense(keys[7], L, F, D),
        }
    params = {
        "embed": layers.init_dense(keys[0], (cfg.vocab_size, D), scale=0.02, dtype=dt),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": dense(keys[1], L, D, H * hd),
            "wk": dense(keys[2], L, D, KVH * hd),
            "wv": dense(keys[3], L, D, KVH * hd),
            "wo": dense(keys[4], L, H * hd, D),
            "mlp_norm": jnp.ones((L, D), dt),
            **mlp,
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[8], D, cfg.vocab_size)
    return params


def partition_specs(cfg: LlamaConfig) -> dict:
    """Tensor-parallel PartitionSpecs over the ``tensor`` mesh axis.

    Column-parallel in-projections, row-parallel out-projections — the
    Megatron layout expressed as sharding annotations; XLA inserts the
    all-reduce over ICI (replaces the reference's engine-internal NCCL TP,
    vllm_inference.py:179-180).
    """
    if cfg.n_experts > 0:
        # MoE: shard the ffn dim over tensor (expert-axis sharding goes
        # through moe.moe_mlp_ep / shard_map, not these specs)
        mlp_specs = {
            "router": P(None, None, None),
            "moe_gate": P(None, None, None, "tensor"),
            "moe_up": P(None, None, None, "tensor"),
            "moe_down": P(None, None, "tensor", None),
        }
    else:
        mlp_specs = {
            "gate": P(None, None, "tensor"),
            "up": P(None, None, "tensor"),
            "down": P(None, "tensor", None),
        }
    specs = {
        "embed": P("tensor", None),  # vocab-sharded
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tensor"),
            "wk": P(None, None, "tensor"),
            "wv": P(None, None, "tensor"),
            "wo": P(None, "tensor", None),
            "mlp_norm": P(None, None),
            **mlp_specs,
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tensor")
    return specs


def _layer_stack(params: dict):
    """[(leaf_name -> [L, ...])] -> per-layer pytrees for lax.scan."""
    return params["layers"]


def _mlp_block(
    layer: dict, h: jax.Array, cfg: LlamaConfig, *, lora=None, lora_scale=1.0,
    moe_impl: str = "nodrop",
) -> tuple[jax.Array, jax.Array]:
    """Post-norm MLP for one layer of ``forward`` (training, and the tests'
    ground truth): dense SwiGLU, or — when cfg.n_experts > 0 — top-k routed
    SwiGLU experts. ``moe_impl="nodrop"`` runs every expert on every token
    in float32, exact per token; ``"capacity"`` is the GShard-dispatched
    formulation at ~top_k/E the FLOPs for compute-bound training forward.
    The serving programs run ``_serving_mlp``. Returns (out,
    aux_load_balance_loss)."""
    if cfg.n_experts > 0:
        if lora is not None and any(
            f"{n}_a" in lora for n in ("gate", "up", "down")
        ):
            # silently skipping MLP adapters on the expert branch would make
            # "LoRA fine-tune a MoE model" train only the attention adapters
            # with no signal anything was dropped (ADVICE r2)
            raise ValueError(
                "LoRA MLP adapters (gate/up/down) are not supported for MoE "
                "expert MLPs; restrict LoRAConfig.targets to attention "
                "projections (wq/wk/wv/wo) for n_experts > 0"
            )
        shape = h.shape
        if moe_impl == "capacity":
            flat, aux = _moe.moe_swiglu_capacity(
                layer["router"], layer["moe_gate"], layer["moe_up"],
                layer["moe_down"], h.reshape(-1, cfg.dim), cfg.top_k_experts,
                cfg.expert_capacity_factor,
            )
        else:
            flat, aux = _moe.moe_swiglu_nodrop(
                layer["router"], layer["moe_gate"], layer["moe_up"],
                layer["moe_down"], h.reshape(-1, cfg.dim), cfg.top_k_experts,
            )
        return flat.reshape(shape).astype(h.dtype), aux
    out = layers.swiglu_mlp(
        {k: layer[k] for k in ("gate", "up", "down")}, h,
        lora=lora, lora_scale=lora_scale,
    )
    return out, jnp.zeros((), jnp.float32)


def _serving_mlp(
    layer: dict, h: jax.Array, cfg: LlamaConfig, token_mask=None, mesh=None
) -> tuple[jax.Array, jax.Array]:
    """Post-norm MLP for one layer of a serving program (``layer`` as
    ``moe.scan_layers`` hands it over): dense SwiGLU, or the routed layer as
    Mixtral defines it: softmax over all experts, the top k renormalised,
    and only those (token, expert) pairs multiplied, out of the experts'
    whole stacks at ``layer["expert_layer"]``. Returns (out, [2] int32: the
    routed pairs of the tokens ``token_mask`` counts, held here and all;
    zeros for a dense layer). Under tensor parallelism (``mesh``) the experts'
    matrices are sharded over their width and the compiler partitions XLA's
    tile loop; the grouped-matmul kernel is one device's program, so the
    loop stays (``paged_impl_plan``'s ``expert_scan``)."""
    if cfg.n_experts == 0:
        out = layers.swiglu_mlp({k: layer[k] for k in ("gate", "up", "down")}, h)
        return out, jnp.zeros((2,), jnp.int32)
    flat, counts = _moe.moe_swiglu_routed(
        layer["router"], *(layer[n] for n in _moe.EXPERT_LEAVES),
        h.reshape(-1, cfg.dim), cfg.top_k_experts, renormalize=True,
        layer=layer["expert_layer"],
        token_mask=None if token_mask is None else token_mask.reshape(-1),
        scan="xla" if mesh_tp_degree(mesh) > 1 else None,
    )
    return flat.astype(h.dtype).reshape(h.shape), counts


# -- forward (training / prefill) ------------------------------------------


def forward(
    params: dict,
    tokens: jax.Array,  # [B, S] int32
    cfg: LlamaConfig,
    *,
    positions: jax.Array | None = None,  # [B, S] (defaults to arange)
    attn_impl: str = "flash",
    lora: dict | None = None,  # adapter pytree (models.lora), applied on the fly
    lora_scale: float = 1.0,
    return_aux: bool = False,  # MoE: also return the mean load-balance loss
    moe_impl: str = "nodrop",  # "capacity": GShard dispatch (training scale)
    input_embeds: jax.Array | None = None,  # [B, P, D]: multimodal prefix
):  # [B, S, vocab] (, aux)
    """Full-sequence forward with causal attention (flash or xla impl).

    ``input_embeds`` replaces the embedding lookup for the first P
    positions (same contract as ``prefill`` — the multimodal path)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = params["embed"][tokens]  # [B, S, D]
    if input_embeds is not None:
        P = input_embeds.shape[1]
        x = jnp.concatenate([input_embeds.astype(x.dtype), x[:, P:]], axis=1)
    cos, sin = layers.rotary_embedding(
        positions, cfg.head_dim, cfg.rope_theta, dtype=jnp.float32,
        rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None,
    )  # [B, S, hd/2]

    def layer_fn(x, scanned):
        layer = scanned[0] if lora is not None else scanned
        llayer = scanned[1] if lora is not None else None
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        attn_params = {k: layer[k] for k in ("wq", "wk", "wv", "wo")}
        h = layers.causal_self_attention(
            attn_params, h,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            cos=cos, sin=sin, causal=True, attn_impl=attn_impl,
            lora=llayer, lora_scale=lora_scale,
        )
        x = x + h
        h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        h, aux = _mlp_block(
            layer, h, cfg, lora=llayer, lora_scale=lora_scale, moe_impl=moe_impl
        )
        return x + h, aux

    xs = (
        (_layer_stack(params), lora["layers"]) if lora is not None
        else _layer_stack(params)
    )
    x, aux_per_layer = jax.lax.scan(layer_fn, x, xs)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = layers.mm(x, head)
    if return_aux:
        return logits, jnp.mean(aux_per_layer)
    return logits


# -- serving: prefill + paged decode ----------------------------------------


def prefill(
    params: dict,
    tokens: jax.Array,  # [B, S] padded
    k_pages: jax.Array,  # [L, n_pages, page_size, Hkv, hd]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    seq_lens: jax.Array,  # [B] true lengths
    cfg: LlamaConfig,
    attn_impl: str = "flash",  # "xla": the einsum reference path
    input_embeds: jax.Array | None = None,  # [B, P, D]: multimodal prefix
    mesh=None,  # jax Mesh with a "tensor" axis: flash runs per head shard
):
    """Process prompts, filling the paged KV cache; returns (logits_last,
    k_pages, v_pages). Padded positions write to reserved trash page 0.

    Under ``mesh=`` tensor parallelism the flash kernel runs inside
    ``shard_map`` over the kv-head axis (ops.sharded) — TP prefill keeps
    the Pallas fast path instead of downgrading to the XLA attention.

    ``input_embeds`` replaces the embedding lookup for the FIRST P
    positions — the multimodal path (models.vlm image tokens occupy
    positions 0..P-1; tokens[:, :P] are placeholders). Everything after the
    embedding — RoPE positions, causal attention, page scatter — already
    operates on the full sequence, so image tokens become ordinary KV cache
    entries and decode needs no changes at all (the LLaVA recipe, serving
    the reference's sglang_vlm.py workload)."""
    B, S = tokens.shape
    page_size = k_pages.shape[2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = positions < seq_lens[:, None]
    cos, sin = layers.rotary_embedding(
        positions, cfg.head_dim, cfg.rope_theta, dtype=jnp.float32,
        rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None,
    )
    x = params["embed"][tokens]
    if input_embeds is not None:
        P = input_embeds.shape[1]
        x = jnp.concatenate(
            [input_embeds.astype(x.dtype), x[:, P:]], axis=1
        )

    page_idx = jnp.take_along_axis(
        page_tables, positions // page_size, axis=1
    )  # [B, S]
    page_idx = jnp.where(valid, page_idx, 0)
    slot = jnp.where(valid, positions % page_size, 0)

    def layer_fn(x, layer, _li):
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        D = cfg.head_dim
        q = layers.mm(h, layer["wq"]).astype(x.dtype)
        k = layers.mm(h, layer["wk"]).astype(x.dtype)
        v = layers.mm(h, layer["wv"]).astype(x.dtype)
        q = q.reshape(B, S, cfg.n_heads, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
        if attn_impl == "flash":
            o = sharded_flash_attention(mesh, q, k, v, True)
        else:
            from ..ops import reference as _ref

            with jax.named_scope(_scopes.ATTENTION):
                o = _ref.attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * D)
        x = x + layers.mm(o, layer["wo"]).astype(x.dtype)
        h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        h, _ = _serving_mlp(layer, h, cfg, mesh=mesh)
        x = x + h
        # stack KV for a single scatter outside the scan: [Hkv, B, S, D]
        return x, (k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3))

    x, (k_all, v_all) = _moe.scan_layers(_layer_stack(params), layer_fn, x)
    # k_all: [L, Hkv, B, S, D] -> pages at (page_idx[b,s], slot[b,s])
    k_pages, v_pages = _scatter_pages(k_pages, v_pages, k_all, v_all, page_idx, slot)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last_idx = jnp.maximum(seq_lens - 1, 0)  # [B]
    x_last = jnp.take_along_axis(x, last_idx[:, None, None].repeat(x.shape[-1], -1), 1)[
        :, 0
    ]  # [B, D]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = layers.mm(x_last, head)
    return logits, k_pages, v_pages


def _scatter_pages(k_pages, v_pages, k_all, v_all, page_idx, slot):
    """Write [L, Hkv, B, S, D] new KV into [L, P, page_size, Hkv, D] pages
    at (page_idx[b,s], slot[b,s]). int8 (QuantizedKV) caches quantize at
    this write — per token-head amax/127 over D, fused by XLA into the
    prefill program — and scatter the f32 scale rows alongside."""
    # adjacent advanced indices (page_idx, slot) at dims 1, 2 keep their
    # position: the target block is [L, B, S, Hkv, D]
    upd_k = k_all.transpose(0, 2, 3, 1, 4)
    upd_v = v_all.transpose(0, 2, 3, 1, 4)
    k_pages = kv_scatter(k_pages, upd_k, page_idx, slot)
    v_pages = kv_scatter(v_pages, upd_v, page_idx, slot)
    return k_pages, v_pages


def prefill_chunk(
    params: dict,
    tokens: jax.Array,  # [B, C] — one chunk of the prompt
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    chunk_lens: jax.Array,  # [B] valid tokens in THIS chunk
    cfg: LlamaConfig,
    *,
    q_offset: int,  # global position of the chunk's first token (static)
    attn_impl: str = "flash",  # "xla": the einsum reference path
    mesh=None,  # jax Mesh with a "tensor" axis: flash runs per head shard
):
    """One chunk of a long prompt: attends to the already-cached prefix (via
    page gather) + itself (rectangular flash kernel with q_offset), writes
    its K/V into the pages. Bounded VMEM for arbitrarily long prompts —
    the chunked-prefill half of the serving engine (vLLM chunked prefill
    analog). Under ``mesh=`` the chunked flash kernel runs per head shard
    (ops.sharded), so TP chunked prefill stays on the fast path. Returns
    (last_logits [B, vocab], k_pages, v_pages)."""
    B, C = tokens.shape
    page_size = k_pages.shape[2]
    positions = q_offset + jnp.broadcast_to(jnp.arange(C), (B, C))
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]
    cos, sin = layers.rotary_embedding(
        positions, cfg.head_dim, cfg.rope_theta, dtype=jnp.float32,
        rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None,
    )
    x = params["embed"][tokens]

    page_idx = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
    page_idx = jnp.where(valid, page_idx, 0)
    slot = jnp.where(valid, positions % page_size, 0)

    # dense gather of the cached prefix (page-aligned: q_offset % page_size
    # == 0 by construction — chunks are bucket-sized)
    n_prefix_pages = q_offset // page_size
    prefix_tables = page_tables[:, :n_prefix_pages] if n_prefix_pages else None

    def layer_fn(x, layer, _li, k_pg, v_pg):  # pages: [P, ps, Hkv, D]
        D = cfg.head_dim
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = layers.mm(h, layer["wq"]).astype(x.dtype)
        k = layers.mm(h, layer["wk"]).astype(x.dtype)
        v = layers.mm(h, layer["wv"]).astype(x.dtype)
        q = q.reshape(B, C, cfg.n_heads, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, C, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, C, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)

        if n_prefix_pages:
            # [B, n_pp, ps, Hkv, D] -> [B, Hkv, prefix, D]; int8 caches
            # dequantize in the gather (one multiply at the chunk's dtype)
            pk = kv_gather(
                k_pg, prefix_tables, dtype=k.dtype
            ).transpose(0, 3, 1, 2, 4).reshape(
                B, cfg.n_kv_heads, n_prefix_pages * page_size, D
            )
            pv = kv_gather(
                v_pg, prefix_tables, dtype=v.dtype
            ).transpose(0, 3, 1, 2, 4).reshape(
                B, cfg.n_kv_heads, n_prefix_pages * page_size, D
            )
            k_full = jnp.concatenate([pk, k], axis=2)
            v_full = jnp.concatenate([pv, v], axis=2)
        else:
            k_full, v_full = k, v
        if attn_impl == "flash":
            o = sharded_flash_attention_chunked(
                mesh, q, k_full, v_full, q_offset=q_offset
            )
        else:
            from ..ops import reference as _ref

            with jax.named_scope(_scopes.ATTENTION):
                o = _ref.attention_chunked(
                    q, k_full, v_full, q_offset=q_offset
                )
        o = o.transpose(0, 2, 1, 3).reshape(B, C, cfg.n_heads * D)
        x = x + layers.mm(o, layer["wo"]).astype(x.dtype)
        h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        h, _ = _serving_mlp(layer, h, cfg, mesh=mesh)
        x = x + h
        return x, (k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3))

    x, (k_all, v_all) = _moe.scan_layers(
        _layer_stack(params), layer_fn, x, k_pages, v_pages
    )
    k_pages, v_pages = _scatter_pages(k_pages, v_pages, k_all, v_all, page_idx, slot)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last_idx = jnp.maximum(chunk_lens - 1, 0)
    x_last = jnp.take_along_axis(
        x, last_idx[:, None, None].repeat(x.shape[-1], -1), 1
    )[:, 0]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = layers.mm(x_last, head)
    return logits, k_pages, v_pages


_impl_downgrades_warned: set = set()


def tp_shard_ok(cfg: LlamaConfig, tp: int) -> bool:
    """Whether this model's heads divide the tensor-parallel degree — the
    ONE predicate behind every head-sharding legality decision of
    ``paged_impl_plan``."""
    return cfg.n_kv_heads % tp == 0 and cfg.n_heads % tp == 0


def paged_impl_plan(
    cfg: LlamaConfig,
    page_size: int,
    impl: str | None = None,
    scatter_impl: str = "xla",
    *,
    kv_dtype="bfloat16",
    mesh=None,
    warn: bool = True,
    expert_dtype=None,
) -> dict:
    """Resolve the decode structure that will ACTUALLY run for these shapes
    on the current backend — the single source of truth shared by
    ``decode_step`` and the engine's stats/metrics, so a requested pallas
    impl that gets shape-downgraded (sub-128 head_dim) is
    visible instead of silently benchmarking the XLA path (ADVICE r4).

    ``impl`` left unset (None) means the plan decides, from what it can
    observe: the ragged kernel (each slot's live pages DMAed once, straight
    from the cache) where the backend is a TPU, ``ragged_shapes_ok`` holds
    and the heads divide over the mesh; the chunked XLA loop everywhere
    else (the CPU, where the kernel would run in the interpreter; ``tiny``'s
    head width). One algorithm, an online softmax over a slot's pages, and
    two ways of fetching them. ``"pallas"`` / ``"xla"`` force one, and a
    forced kernel the shapes refuse is named in ``downgraded``.

    ``kv_dtype`` ("int8" = the quantized QuantizedKV cache) is reported;
    since PR 35 it no longer moves the variant (flat at Hkv%8 for both).

    ``mesh`` (a jax Mesh with a "tensor" axis) makes the plan PER-SHARD
    aware: under ``shard_map`` tensor parallelism the kernels see
    ``Hkv // tp`` / ``Hq // tp`` heads, so flat-variant legality and GQA
    grouping evaluate against the shard-local head counts — the plan
    reports the variant each device actually runs, with ``"tp"`` carrying
    the degree. Head counts not divisible by tp downgrade loudly to the
    auto-partitioned XLA paths (the only genuinely illegal sharding).

    ``expert_scan`` names the form of a routed model's tile loop in a decode
    step (None for a dense model): ``moe.expert_scan_form``'s choice for
    experts of ``expert_dtype`` (unset: the model's own) on one device, the
    XLA loop under tensor parallelism (``_serving_mlp``).

    Returns ``{"attention": "ragged"|"xla-gather",
    "ragged_variant": "flat"|"grouped"|None, "scatter": "pallas"|"xla",
    "kv_dtype": str, "tp": int, "downgraded": [...],
    "expert_scan": "pallas"|"xla"|None}``.
    """
    from ..ops.kv_quant import resolve_kv_dtype
    # legality predicates live with the kernels (ops.paged_attention) so the
    # plan and the wrappers cannot drift. Hkv does not gate the kernel:
    # Hkv%8 shapes (GQA's 8, MHA's 32) take the "flat" all-heads
    # formulation, smaller head shards the "grouped" per-kv-head one. Under
    # TP the SHARD-local Hkv decides: the kernel inside shard_map sees
    # Hkv // tp.
    from ..ops.paged_attention import ragged_shapes_ok, ragged_variant_for

    kvd = resolve_kv_dtype(kv_dtype)
    kvd_name = "int8" if kvd == "int8" else str(kvd)
    on_tpu = jax.default_backend() == "tpu"
    tp = mesh_tp_degree(mesh)
    shard_ok = tp_shard_ok(cfg, tp)
    hkv_shard = cfg.n_kv_heads // tp if shard_ok else cfg.n_kv_heads
    downgraded = []
    ragged_variant = None
    shapes_ok = ragged_shapes_ok(cfg.head_dim, page_size)
    if impl is None:
        ok = on_tpu and shapes_ok and shard_ok
    else:
        ok = impl == "pallas" and (not on_tpu or shapes_ok) and shard_ok
    attention = "ragged" if ok else "xla-gather"
    if ok:
        ragged_variant = ragged_variant_for(hkv_shard)
    elif impl == "pallas" and not shard_ok:
        downgraded.append(
            f"paged_impl=pallas -> xla-gather (n_kv_heads="
            f"{cfg.n_kv_heads}/n_heads={cfg.n_heads} not divisible by "
            f"tp={tp}: head-sharded kernels need whole heads per shard)"
        )
    elif impl == "pallas":
        downgraded.append(
            f"paged_impl=pallas -> xla-gather (head_dim={cfg.head_dim}, "
            f"page_size={page_size} fail D%128/ps%16 Mosaic tiling)"
        )
    scatter = "xla"
    if scatter_impl == "pallas":
        from ..ops.paged_attention import scatter_shapes_ok

        if (not on_tpu or scatter_shapes_ok(cfg.head_dim)) and shard_ok:
            scatter = "pallas"
        elif not shard_ok:
            downgraded.append(
                f"scatter_impl=pallas -> xla (n_kv_heads={cfg.n_kv_heads} "
                f"not divisible by tp={tp})"
            )
        else:
            downgraded.append(
                f"scatter_impl=pallas -> xla (head_dim={cfg.head_dim} "
                "fails D%128 tiling)"
            )
    expert_scan = None
    if cfg.n_experts > 0:
        expert_scan = "xla" if tp > 1 else _moe.expert_scan_form(
            1, cfg.dim, cfg.ffn_dim, expert_dtype or cfg.dtype
        )
    if warn and downgraded:
        import warnings

        for msg in downgraded:
            if msg not in _impl_downgrades_warned:
                _impl_downgrades_warned.add(msg)
                warnings.warn(
                    "requested Pallas impl downgraded: " + msg, stacklevel=2
                )
    return {
        "attention": attention, "ragged_variant": ragged_variant,
        "scatter": scatter, "kv_dtype": kvd_name, "tp": tp,
        "downgraded": downgraded, "expert_scan": expert_scan,
    }


def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 — current token per slot
    positions: jax.Array,  # [B] int32 — its position
    k_pages: jax.Array,  # [L, P, page_size, Hkv, hd]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool — live slots (dead slots write trash page 0)
    cfg: LlamaConfig,
    impl: str | None = None,
    scatter_impl: str = "xla",
    ragged_variant: str | None = None,  # None: auto (flat | grouped by Hkv)
    mesh=None,  # jax Mesh with a "tensor" axis: kernels run per head shard
    return_counts: bool = False,
):
    """One token of batched decode against the paged cache.

    Returns (logits [B, vocab], k_pages, v_pages) and, with
    ``return_counts``, [2] int32: the live slots' routed pairs over the
    layers, those on experts held here and all of them (the same: a llama
    model holds every expert its router names; zeros for a dense model).
    Pass donated pages for in-place updates under jit.

    ``impl`` selects the attention: None (the default) leaves it to
    ``paged_impl_plan`` (the ragged kernel on a TPU where the shapes allow
    it, the chunked loop elsewhere), "xla" / "pallas" force one. There is
    deliberately NO env-var fallback here: this function is jitted by its
    callers, an env read would happen at trace time and not be part of any
    jit cache key, so toggling the env after a trace would silently keep the
    previously compiled implementation (ADVICE r3/r4). The engine resolves
    MTPU_PAGED_IMPL once in ``LLMEngine.__init__`` and passes it explicitly;
    use ``paged_impl_plan`` to see what will actually run for given shapes.

    Structure (round-3 rework): the page arrays are READ-ONLY inside the
    layer scan — attention walks the cached prefix in chunks of the page
    table, as far as the step's longest live context reaches, with the
    current token's K/V still in registers
    (ops.paged_decode_attention_chunked) — and every layer's new KV is
    scattered into the pages in ONE update after the scan (the same shape
    ``prefill`` uses). Round 2 threaded the full caches through the scan as
    stacked ys, which XLA materialized as cache-slice copies every layer of
    every step — the main gap between the measured 28 ms decode step and the
    16.5 ms weight-streaming floor (NOTES.md round 2).

    The ragged plan keeps this same read-only structure but swaps
    the attention for the ragged kernel (ops.paged_decode_attention_ragged)
    — it reads exactly ceil(ctx/page_size) pages per sequence, straight
    from the cache, where the XLA
    loop reads every slot as far as the batch's longest context, rounded up
    to a chunk, into a gathered copy.
    """
    B = tokens.shape[0]
    page_size = k_pages.shape[2]
    # the ragged kernel and the chunked loop share ONE read-only-pages
    # structure (in-flight token as an extra softmax column, one scatter
    # after the scan); the choice, shape legality + downgrade reporting live
    # in paged_impl_plan (single source of truth with the engine's stats).
    # mesh= makes both per-shard aware: the pallas paths go through the
    # ops.sharded shard_map dispatchers, so TP serving keeps the kernels.
    kv_dtype = "int8" if is_quantized(k_pages) else str(k_pages.dtype)
    plan = paged_impl_plan(
        cfg, page_size, impl, scatter_impl, kv_dtype=kv_dtype, mesh=mesh
    )
    use_ragged = plan["attention"] == "ragged"
    x = params["embed"][tokens]  # [B, D]
    cos, sin = layers.rotary_embedding(
        positions[:, None], cfg.head_dim, cfg.rope_theta, dtype=jnp.float32,
        rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None,
    )  # [B, 1, hd/2]

    page_idx = jnp.take_along_axis(
        page_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    page_idx = jnp.where(active, page_idx, 0)
    slot = jnp.where(active, positions % page_size, 0)
    prefix_lens = jnp.where(active, positions, 0).astype(jnp.int32)

    def layer_fn(x, layer, li):
        D = cfg.head_dim
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = layers.mm(h, layer["wq"]).astype(x.dtype)
        k = layers.mm(h, layer["wk"]).astype(x.dtype)
        v = layers.mm(h, layer["wv"]).astype(x.dtype)
        q = q.reshape(B, 1, cfg.n_heads, D).transpose(0, 2, 1, 3)  # [B,H,1,D]
        k = k.reshape(B, 1, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, 1, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
        k_tok, v_tok = k[:, :, 0], v[:, :, 0]  # [B, Hkv, D]
        if use_ragged:
            # kernel reads exactly ceil(prefix/ps) pages straight from the
            # full [L, P, ...] cache (layer via scalar prefetch — no slice
            # copy, no gather materialization). Under mesh= TP the dispatch
            # shard_maps over the kv-head axis: each device's kernel reads
            # only its local head shard of the cache (auto-variant inside
            # the shard resolves against the LOCAL Hkv — what plan reports)
            o = sharded_ragged_decode(
                mesh, q[:, :, 0], k_pages, v_pages, li, page_tables,
                prefix_lens, k_tok, v_tok, variant=ragged_variant,
            )  # [B, H, D]
        else:
            # the table walked in chunks, as far as the longest live prefix
            # of THIS step reaches (the trip count is read from prefix_lens,
            # so inside a decode block it grows as pos + 1 crosses a chunk
            # edge); each chunk one gather from the full [L, P, ...] arrays
            # (layer scalar + table columns — no per-layer slice copy), an
            # int8 cache dequantizing in it
            o = paged_decode_attention_chunked(
                q[:, :, 0], k_pages, v_pages, li, page_tables, prefix_lens,
                k_tok, v_tok,
            )  # [B, H, D]
        o = o.reshape(B, cfg.n_heads * D)
        x = x + layers.mm(o, layer["wo"]).astype(x.dtype)
        h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        h, counts = _serving_mlp(layer, h, cfg, active, mesh)
        return x + h, (k_tok, v_tok, counts)

    x, (k_all, v_all, counts) = _moe.scan_layers(
        _layer_stack(params), layer_fn, x
    )
    # k_all: [L, B, Hkv, D] -> one scatter for every layer's token.
    # The pallas scatter (in-place strided DMAs) is opt-in
    # (scatter_impl="pallas", resolved above — callers that jit must pass
    # it explicitly, same trap as impl=); choosing between the two is the
    # benchmark's job (ROADMAP S2/D3). Independent of the attention impl —
    # both structures end in the same post-scan scatter; only the (Hkv, D)
    # minor-dim tile legality gates it.
    if plan["scatter"] == "pallas":
        k_pages, v_pages = sharded_scatter_kv_pages(
            mesh, k_pages, v_pages, k_all, v_all, page_idx, slot
        )
    else:
        # XLA scatter: adjacent advanced indices (dims 1, 2) keep their
        # position, so the [L, B, Hkv, D] scan ys line up directly.
        # Auto-partitionable (TP serving). int8 caches quantize at this
        # write (kv_scatter fuses the per token-head amax/127 into the
        # decode program and scatters data + scale rows).
        k_pages = kv_scatter(k_pages, k_all, page_idx, slot)
        v_pages = kv_scatter(v_pages, v_all, page_idx, slot)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = layers.mm(x, head)
    if return_counts:
        return logits, k_pages, v_pages, counts.sum(axis=0)
    return logits, k_pages, v_pages


def verify_step(
    params: dict,
    tokens: jax.Array,  # [B, T] int32 — chain: committed token then proposals
    positions0: jax.Array,  # [B] int32 — global position of tokens[:, 0]
    k_pages: jax.Array,  # [L, P, page_size, Hkv, hd]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq]
    active: jax.Array,  # [B] bool
    cfg: LlamaConfig,
    mesh=None,  # jax Mesh with a "tensor" axis: a routed layer keeps XLA's tile loop
):
    """T tokens of teacher-forced decode against the paged cache — the
    target-model scoring half of speculative decoding (the reference enables
    this engine-side: vllm_inference.py:196-205, sglang_low_latency.py:194).

    Writes KV for ALL T chain tokens at positions0..positions0+T-1 (rejected
    tokens' entries are overwritten by later steps and never attended past
    the accept point), and returns logits for every chain position:
    ``logits[:, t]`` is the target's distribution for position
    positions0+t+1. Returns (logits [B, T, vocab], k_pages, v_pages).
    """
    from ..ops import reference as _ref

    B, T = tokens.shape
    page_size = k_pages.shape[2]
    cap = page_tables.shape[1] * page_size
    positions = positions0[:, None] + jnp.arange(T)[None, :]  # [B, T]
    # positions beyond the table capacity write to the trash page (a slot
    # near max length can overshoot by <= T-1 rejected tokens)
    valid = active[:, None] & (positions < cap)
    pos_c = jnp.minimum(positions, cap - 1)
    cos, sin = layers.rotary_embedding(
        pos_c, cfg.head_dim, cfg.rope_theta, dtype=jnp.float32,
        rope_scaling=dict(cfg.rope_scaling) if cfg.rope_scaling else None,
    )  # [B, T, hd/2]
    x = params["embed"][tokens]  # [B, T, D]

    page_idx = jnp.take_along_axis(page_tables, pos_c // page_size, axis=1)
    page_idx = jnp.where(valid, page_idx, 0)
    slot = jnp.where(valid, pos_c % page_size, 0)

    def layer_fn(x, layer, _li, k_pg, v_pg):  # pages: [P, ps, Hkv, D]
        D = cfg.head_dim
        h = layers.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = layers.mm(h, layer["wq"]).astype(x.dtype)
        k = layers.mm(h, layer["wk"]).astype(x.dtype)
        v = layers.mm(h, layer["wv"]).astype(x.dtype)
        q = q.reshape(B, T, cfg.n_heads, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, cfg.n_kv_heads, D).transpose(0, 2, 1, 3)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
        # write the whole chain's KV, then attend (the per-t causal mask in
        # the verify attention keeps token t from seeing tokens > t).
        # Adjacent advanced indices (dims 0, 1): result is [B, T, Hkv, D].
        # int8 caches quantize the chain writes so verification scores
        # proposals against exactly the (dequantized) KV decode will read.
        k_pg = kv_scatter(k_pg, k.transpose(0, 2, 1, 3), page_idx, slot,
                          leading_layer=False)
        v_pg = kv_scatter(v_pg, v.transpose(0, 2, 1, 3), page_idx, slot,
                          leading_layer=False)
        o = _ref.paged_verify_attention(
            q.transpose(0, 2, 1, 3), k_pg, v_pg, page_tables, positions
        )  # [B, T, Hq, D]
        o = o.reshape(B, T, cfg.n_heads * D)
        x = x + layers.mm(o, layer["wo"]).astype(x.dtype)
        h = layers.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
        h, _ = _serving_mlp(layer, h, cfg, mesh=mesh)
        return x + h, (k_pg, v_pg)

    x, (k_pages, v_pages) = _moe.scan_layers(
        _layer_stack(params), layer_fn, x, k_pages, v_pages
    )
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = layers.mm(x, head)  # [B, T, vocab]
    return logits, k_pages, v_pages


# -- HF safetensors interop -------------------------------------------------


def load_hf_weights(
    model_dir: str | Path, cfg: LlamaConfig, dtype=None,
    quantization: str | None = None,
) -> dict:
    """Stream HF llama safetensors into this tree (no 2x RAM: tensors are
    read file-by-file and stacked per layer).

    ``quantization="int8"`` / ``"int4"`` quantizes each matmul weight ON
    THE HOST before the device transfer (models.quantize.
    quantize_weight_host), so a 7B load costs ~7 GB (int8) / ~3.5 GB (int4)
    of HBM — the bf16 tensors never exist on device.
    """
    import numpy as np
    from safetensors import safe_open

    quant_targets = set()
    quant_bits = 8
    if quantization is not None:
        from .quantize import LLAMA_TARGETS, bits_of, quantize_weight_host

        quant_bits = bits_of(quantization)
        # the ONE shared target set (models.quantize.LLAMA_TARGETS) plus the
        # head; router/norms stay high precision (tiny, precision-critical)
        quant_targets = set(LLAMA_TARGETS) | {"lm_head"}

    model_dir = Path(model_dir)
    dt = dtype or cfg.jnp_dtype
    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no safetensors under {model_dir}")

    raw: dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(str(f), framework="np") as sf:
            for name in sf.keys():
                raw[name] = sf.get_tensor(name)

    def dev(arr: np.ndarray, target: str):
        if target in quant_targets:
            return quantize_weight_host(arr, bits=quant_bits)
        return jnp.asarray(arr, dtype=dt)

    def t(name, target="_"):  # HF stores [out, in]; we use [in, out]
        return dev(raw.pop(name).T, target)

    def stack(fmt, transpose=True, target="_"):
        mats = []
        for li in range(cfg.n_layers):
            arr = raw.pop(fmt.format(li))
            mats.append(arr.T if transpose else arr)
        return dev(np.stack(mats), target)

    def stack_experts(fmt, target="_"):
        # [L, E, D, F] from per-(layer, expert) HF [F, D] matrices
        mats = [
            np.stack([raw.pop(fmt.format(li, e)).T for e in range(cfg.n_experts)])
            for li in range(cfg.n_layers)
        ]
        return dev(np.stack(mats), target)

    if cfg.n_experts > 0:
        # Mixtral layout: block_sparse_moe.gate (router) + experts.{e}.w1/w3/w2
        mlp = {
            "router": stack("model.layers.{}.block_sparse_moe.gate.weight"),
            "moe_gate": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w1.weight",
                "moe_gate",
            ),
            "moe_up": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w3.weight",
                "moe_up",
            ),
            "moe_down": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w2.weight",
                "moe_down",
            ),
        }
    else:
        mlp = {
            "gate": stack("model.layers.{}.mlp.gate_proj.weight", target="gate"),
            "up": stack("model.layers.{}.mlp.up_proj.weight", target="up"),
            "down": stack("model.layers.{}.mlp.down_proj.weight", target="down"),
        }
    params = {
        "embed": jnp.asarray(raw.pop("model.embed_tokens.weight"), dtype=dt),
        "layers": {
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", False),
            "wq": stack("model.layers.{}.self_attn.q_proj.weight", target="wq"),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight", target="wk"),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight", target="wv"),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight", target="wo"),
            "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight", False),
            **mlp,
        },
        "final_norm": jnp.asarray(raw.pop("model.norm.weight"), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = t("lm_head.weight", "lm_head")
    return params
