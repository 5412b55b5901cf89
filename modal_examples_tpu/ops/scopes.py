"""Names for the parts of a layer, as ``jax.named_scope`` puts them on every
XLA operation traced inside: a device trace (the ``XLA Ops`` line of an
``.xplane.pb``) and the lowered text of a program then say which part of the
model an operation belongs to, whatever the compiler called its fusion.

One list, so that a reader of traces can import what the program writes:
``tpurun profile --xplane`` sums a trace's device time by these
(observability/xplane.py, docs/observability.md). A scope changes no
operation and no result. On a whole function:
``@jax.named_scope(ATTENTION)``.
"""

from __future__ import annotations

PAGE_GATHER = "mtpu.page_gather"  # paged KV pages -> a dense [B, ...] view
ATTENTION = "mtpu.attention"  # flash / chunked / paged-decode attention
DENSE_MLP = "mtpu.dense_mlp"  # SwiGLU feed-forward
ROUTER = "mtpu.router"  # MoE router logits, top-k, combine weights
EXPERT_SCAN = "mtpu.expert_scan"  # the experts' SwiGLU over the token block
KV_SCATTER = "mtpu.kv_scatter"  # new K/V rows -> cache pages
SAMPLING = "mtpu.sampling"  # logits -> next token
LATENT_EXPAND = "mtpu.latent_expand"  # MLA latents -> per-head keys and values
EXPERT_DISPATCH = "mtpu.expert_dispatch"  # routed pairs sorted to tiles and back
SSM_PROJ = "mtpu.ssm_proj"  # a Mamba-2 mixer's in_proj, gated norm and out_proj
SSM_SCAN = "mtpu.ssm_scan"  # prefill: the causal convolution and the chunked scan
SSM_STEP = "mtpu.ssm_step"  # decode: convolution shift, one state update, output
INDEXER = "mtpu.indexer"  # sparse attention: index projections, key gather, index scores
TOPK_SELECT = "mtpu.topk_select"  # ... and the exact top-k of the scores
CONV_MIX = "mtpu.conv_mix"  # a gated short convolution: projections, gates, taps, window
#: a sliding-window layer's attention, prefill and decode (a layer that sees
#: its whole context stays under ``mtpu.attention``)
WINDOW_ATTENTION = "mtpu.window_attention"

ALL = (
    PAGE_GATHER, ATTENTION, DENSE_MLP, ROUTER, EXPERT_SCAN, KV_SCATTER,
    SAMPLING, LATENT_EXPAND, EXPERT_DISPATCH, SSM_PROJ, SSM_SCAN, SSM_STEP,
    INDEXER, TOPK_SELECT, CONV_MIX, WINDOW_ATTENTION,
)

