"""ops — Pallas TPU kernels + XLA references.

The native-kernel surface replacing the reference's CUDA dependencies
(SURVEY.md §2.4): flash attention (flash-attn), ragged paged decode attention
(vLLM PagedAttention), int8 quantized matmul (bitsandbytes/unsloth), ring
attention (sequence parallelism the reference lacks).
"""

from .flash_attention import (
    flash_attention,
    flash_attention_chunked,
    flash_attention_with_lse,
)
from .kv_quant import (
    QuantizedKV,
    dequantize_kv,
    is_quantized,
    kv_empty,
    kv_gather,
    kv_scatter,
    quantize_kv,
)
from .paged_attention import (
    decode_chunk_pages,
    decode_chunk_trips,
    paged_decode_attention_chunked,
    paged_latent_decode_attention_chunked,
    paged_decode_attention_inflight,
    paged_decode_attention_ragged,
    paged_window_decode_attention_chunked,
    paged_window_decode_attention_ragged,
    ragged_kernel_sizes,
    ragged_pages_read,
    scatter_kv_pages,
    window_decode_span,
    window_decode_view,
    window_ring_pages,
)
from .quantized_matmul import dequantize_int8, quantize_int8, quantized_matmul
from .scan_loop import masked_scan
from .sharded import (
    mesh_tp_degree,
    shard_cache_pages,
    sharded_flash_attention,
    sharded_flash_attention_chunked,
    sharded_ragged_decode,
    sharded_scatter_kv_pages,
)
from .ring_attention import (
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
    ulysses_attention_sharded,
)
from . import reference

__all__ = [
    "QuantizedKV",
    "decode_chunk_pages",
    "decode_chunk_trips",
    "dequantize_int8",
    "dequantize_kv",
    "flash_attention",
    "flash_attention_chunked",
    "flash_attention_with_lse",
    "paged_decode_attention_chunked",
    "paged_latent_decode_attention_chunked",
    "paged_decode_attention_inflight",
    "paged_decode_attention_ragged",
    "paged_window_decode_attention_chunked",
    "paged_window_decode_attention_ragged",
    "ragged_kernel_sizes",
    "ragged_pages_read",
    "is_quantized",
    "kv_empty",
    "kv_gather",
    "kv_scatter",
    "masked_scan",
    "mesh_tp_degree",
    "scatter_kv_pages",
    "shard_cache_pages",
    "sharded_flash_attention",
    "sharded_flash_attention_chunked",
    "sharded_ragged_decode",
    "sharded_scatter_kv_pages",
    "quantize_int8",
    "quantize_kv",
    "quantized_matmul",
    "reference",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "window_decode_span",
    "window_decode_view",
    "window_ring_pages",
]
