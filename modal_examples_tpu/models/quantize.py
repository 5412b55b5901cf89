"""Weight-only int8/int4 quantization for serving.

The reference's quantized-LLM story is bitsandbytes 4/8-bit (unsloth loads
4-bit, unsloth_finetune.py:187-197; misc/falcon_bitsandbytes.py is the
negative baseline). TPU-native: weights live in HBM as int8 (or packed
int4) with per-output-channel f32 scales (symmetric, AQT-style) — halving
(quartering) weight HBM traffic and footprint vs bf16 — and matmuls upcast
tiles to bf16 on the way into the MXU (XLA fuses the cast;
ops.quantized_matmul is the Pallas alternative when profiling says so).

int4 uses the native ``jnp.int4`` dtype (XLA packs two nibbles per byte in
TPU HBM); per-output-channel symmetric scaling is cruder than the
group-wise schemes real 4-bit checkpoints use (AWQ/GPTQ group 128), which
is acceptable for the bench's random weights and documented for real ones.

``QuantizedWeight`` is a pytree node, so quantized params flow through
scan/jit/sharding like any other weights.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedWeight:
    q: jax.Array  # int8, [..., din, dout]
    scale: jax.Array  # f32, [..., 1, dout]

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


#: quantization modes every entry point accepts (engine, loaders, CLI)
SUPPORTED = (None, "int8", "int4")


def _qmax(bits: int) -> float:
    if bits == 8:
        return 127.0
    if bits == 4:
        return 7.0
    raise ValueError(f"unsupported quantization bits {bits!r} (4 or 8)")


def quantize_weight(w: jax.Array, bits: int = 8) -> QuantizedWeight:
    """Symmetric per-output-channel int8/int4 over the contraction dim (-2)."""
    qmax = _qmax(bits)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -qmax, qmax)
    return QuantizedWeight(
        q=q.astype(jnp.int8 if bits == 8 else jnp.int4), scale=scale
    )


def dequantize_weight(qw: QuantizedWeight, dtype=jnp.bfloat16) -> jax.Array:
    return (qw.q.astype(jnp.float32) * qw.scale).astype(dtype)


#: the matmul weights worth quantizing in a llama tree — dense AND MoE expert
#: matmuls (norms/embeddings/router stay high precision: tiny, and
#: precision-critical). ONE list shared by every quantization entry point
#: (quantize_llama, init_quantized_llama, llama.load_hf_weights) so
#: quantization="int8" means the same precision tree no matter how the
#: params arrive (ADVICE r3).
LLAMA_TARGETS = (
    "wq", "wk", "wv", "wo", "gate", "up", "down",
    "moe_gate", "moe_up", "moe_down",
)


#: ... and in a DeepSeek-V2 tree (models/deepseek_v2.py): the two low-rank
#: query and key/value projections, the output projection, the dense, shared
#: and routed SwiGLUs; the router, like the norms, stays high precision
DEEPSEEK_V2_TARGETS = (
    "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "gate", "up", "down",
    "moe_gate", "moe_up", "moe_down", "shared_gate", "shared_up", "shared_down",
)


#: ... and in a Granite hybrid tree (models/granite_hybrid.py): the Mamba-2
#: mixer's two projections, the attention projections, the SwiGLU (a routed
#: model's shared expert) and the routed experts; the convolution, the norms,
#: the router and the per-head ``A_log`` / ``dt_bias`` / ``D`` stay
GRANITE_HYBRID_TARGETS = (
    "in_z", "in_xbc", "in_dt", "out_proj", "wq", "wk", "wv", "wo", "gate", "up", "down",
    "moe_gate", "moe_up", "moe_down",
)


#: ... and in an LFM2 tree (models/lfm2.py): the convolution mixer's two
#: projections, the attention projections, the dense and the routed SwiGLUs;
#: the taps, the norms, the router and its selection bias stay
LFM2_TARGETS = (
    "in_proj", "out_proj", "wq", "wk", "wv", "wo", "gate", "up", "down",
    "moe_gate", "moe_up", "moe_down",
)

#: ... and in a SmallThinker tree (models/smallthinker.py): the attention
#: projections and the routed ReGLUs; the router, the norms and the embedding
#: stay (``lm_head`` goes the way ``quantize_llama`` takes every tree's)
SMALLTHINKER_TARGETS = ("wq", "wk", "wv", "wo", "moe_gate", "moe_up", "moe_down")


def bits_of(quantization: str) -> int:
    if quantization not in ("int8", "int4"):
        raise ValueError(f"unknown quantization {quantization!r}")
    return 8 if quantization == "int8" else 4


def quantize_llama(
    params: dict, targets=LLAMA_TARGETS, *, bits: int = 8
) -> dict:
    """Quantize the layer matmuls (and lm_head) of a llama param tree, or
    of any tree whose layer stacks sit under keys that end in ``layers``
    (DeepSeek-V2's ``dense_layers`` / ``moe_layers`` with
    ``DEEPSEEK_V2_TARGETS``).

    Device-side path for caller-provided trees. Peak HBM is bf16 + int
    together; callers that own the tree outright should random-init via
    ``init_quantized_llama`` (fused, no bf16 peak) or load checkpoints via
    ``llama.load_hf_weights(quantization=...)`` (host-side quantize).
    """
    out = dict(params)
    for key, stack in params.items():
        if key.endswith("layers"):
            out[key] = {
                name: quantize_weight(w, bits) if name in targets else w
                for name, w in stack.items()
            }
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"], bits)
    return out


def init_quantized_llama(key, cfg, *, bits: int = 8) -> dict:
    """Random-init a quantized llama tree in ONE jitted program.

    init -> quantize as separate device steps peaks at bf16 + int together
    (~20 GB at 7B — over the v5e ceiling). Fusing both into a
    single executable makes every bf16 leaf an XLA-internal temporary: the
    compiler frees it inside the program, so peak HBM is the quantized tree
    plus one transient leaf.
    """
    return jax.jit(
        lambda k: quantize_llama(
            cfg.model.init_params(k, cfg), cfg.quant_targets, bits=bits
        )
    )(key)


def quantize_weight_host(w: "np.ndarray", bits: int = 8) -> QuantizedWeight:
    """Host-side (numpy) quantization: the checkpoint-load path. The bf16
    tensor never touches the device — only the int payload and scales are
    transferred, so loading a 7B model costs ~7 GB (int8) / ~3.5 GB (int4)
    of HBM, not 20."""
    import ml_dtypes
    import numpy as np

    qmax = _qmax(bits)
    wf = np.asarray(w, dtype=np.float32)
    amax = np.max(np.abs(wf), axis=-2, keepdims=True)
    scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale), -qmax, qmax)
    q = q.astype(np.int8 if bits == 8 else ml_dtypes.int4)
    return QuantizedWeight(q=jnp.asarray(q), scale=jnp.asarray(scale))


def param_bytes(params) -> int:
    """True HBM bytes of a param tree; int4 counts as 4 bits per element
    (XLA packs two nibbles per byte on TPU even though ml_dtypes reports
    itemsize 1)."""
    total = 0
    for x in jax.tree.leaves(params):
        if not hasattr(x, "size"):
            continue
        if str(x.dtype) == "int4":
            total += (x.size + 1) // 2
        else:
            total += x.size * x.dtype.itemsize
    return total
