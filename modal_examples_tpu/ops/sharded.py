"""shard_map dispatch for the Pallas fast paths under tensor parallelism.

A ``pallas_call`` cannot be auto-partitioned: under a sharded jit, GSPMD
either fails to compile the kernel or forces a full-cache gather onto every
device. Until this round the serving engine therefore refused
``mesh= + paged_impl="pallas"`` and silently downgraded prefill to the XLA
attention path — the moment serving went multi-chip, every decode-kernel win
from rounds 3–5 was lost (ROADMAP open item #2).

This module is the ONE dispatch layer that fixes that: each wrapper takes
the mesh alongside the kernel operands and

- with no mesh (or a 1-wide ``tensor`` axis) falls straight through to the
  plain kernel — the single-chip path is byte-for-byte what it always was;
- with a real ``tensor`` axis wraps the kernel in ``shard_map`` over the
  kv-head dimension, so every device runs the unmodified Mosaic kernel on
  its local head shard.

Why the kv-head axis: the Ragged Paged Attention kernel is explicitly
designed to shard there (PAPERS.md, arxiv 2604.15464) — decode attention is
fully head-local (query head ``h`` reads only kv head ``h // group``), so a
head-sharded cache means every page byte, its f32 scale row (int8 caches),
and all of its attention math stay on the chip that owns the head. There is
**no kernel-level collective**: outputs come back sharded on the head axis
(the concat over shards IS the epilogue), and the one reduction TP needs —
summing per-head partial outputs through the row-parallel ``wo`` — happens
in the surrounding auto-partitioned matmul exactly as on the XLA path.
The scatter is head-local for the same reason (pages shard on ``Hkv``; page
ids are global and un-sharded), and quantize-at-write stays bit-exact under
sharding because int8 scales are per (token, head).

Per-shard legality: inside ``shard_map`` the kernels see ``Hkv // tp`` and
``Hq // tp`` heads, so Mosaic shape legality — the flat variant's
``Hkv % 8`` (a page read as rows in place), GQA grouping — must be
evaluated against the LOCAL shard shapes. The wrappers do this implicitly
(the kernel sees local shapes); ``llama.paged_impl_plan(mesh=...)`` is the
reporting mirror, so a plan and the kernels can't drift.

Serving code (``models/llama.py``, ``serving/``) must reach Pallas ONLY
through these wrappers — a raw kernel call under the engine's
auto-partitioned jits is the exact bug class the old engine guard errored
on, and a static guard (tests/test_static.py) now makes it unrepresentable
instead.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import TENSOR
from .flash_attention import flash_attention, flash_attention_chunked
from .kv_quant import QuantizedKV, is_quantized
from .scopes import ATTENTION, KV_SCATTER
from .paged_attention import (
    paged_decode_attention_ragged,
    scatter_kv_pages,
)


def mesh_tp_degree(mesh, axis: str = TENSOR) -> int:
    """Size of the mesh's tensor axis (1 when mesh is None or the axis is
    absent) — the single helper every mesh-aware dispatch + plan uses."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(axis, 1))


def shard_cache_pages(mesh, k_pages, v_pages, *, axis: str = TENSOR):
    """Place a full [L, P, ps, Hkv, D] paged cache on the mesh with the
    canonical kv-head sharding (int8 caches: f32 scale rows ride the SAME
    head axis as their data) — the ONE placement rule behind the engine's
    ``_shard_cache`` and the TP microbench, so the two cannot drift.
    Returns the (k_pages, v_pages) pair; no-op placement when mesh is
    None."""
    from jax.sharding import NamedSharding

    from .kv_quant import shard_kv

    if mesh is None:
        return k_pages, v_pages
    data_sh = NamedSharding(mesh, P(None, None, None, axis, None))
    scale_sh = NamedSharding(mesh, P(None, None, None, axis))
    return (
        shard_kv(k_pages, data_sh, scale_sh),
        shard_kv(v_pages, data_sh, scale_sh),
    )


def _check_heads(tp: int, name_shapes: list[tuple[str, int]]) -> None:
    for name, n in name_shapes:
        if n % tp:
            raise ValueError(
                f"{name}={n} is not divisible by the tensor-parallel degree "
                f"{tp}: head-sharded kernels need whole heads per shard"
            )


def _pages_specs(quantized: bool, axis: str):
    """(in_specs, operand-flatten, rebuild) for one [L, P, ps, Hkv, D]
    page operand: plain arrays are one head-sharded leaf; QuantizedKV
    flattens to (int8 data, f32 scale) with the scale sharded on the SAME
    head axis so in-kernel dequant never crosses chips — the one place
    that data/scale pairing rule lives."""
    data = P(None, None, None, axis, None)
    if not quantized:
        return [data], lambda pg: [pg], lambda leaves: leaves[0]
    scale = P(None, None, None, axis)
    return (
        [data, scale],
        lambda pg: [pg.data, pg.scale],
        lambda leaves: QuantizedKV(data=leaves[0], scale=leaves[1]),
    )


@jax.named_scope(ATTENTION)
def sharded_ragged_decode(
    mesh,
    q,  # [B, Hq, D]
    k_pages,  # [L, P, ps, Hkv, D] array or QuantizedKV
    v_pages,
    layer,  # scalar int32
    page_tables,  # [B, pages_per_seq] int32 — GLOBAL page ids (P not sharded)
    prefix_lens,  # [B] int32
    k_new,  # [B, Hkv, D]
    v_new,
    *,
    sm_scale: float | None = None,
    variant: str | None = None,
    interpret: bool | None = None,
    axis: str = TENSOR,
):
    """Ragged paged decode attention (flat v3 / grouped v4, incl. int8-KV)
    under tensor parallelism: every device runs the kernel on its local
    kv-head shard of the cache; output comes back sharded on the query-head
    axis (no psum — attention is head-local; ``wo`` reduces outside).

    ``variant=None`` resolves per SHARD: inside ``shard_map`` the kernel
    sees ``Hkv // tp`` heads, so e.g. a 32-head cache runs "flat" on one
    chip and as a 16-head TP=2 shard, while an 8-head GQA cache is "flat" on
    one chip and "grouped" once sharded (flat wants Hkv%8 per shard) —
    exactly what ``llama.paged_impl_plan(mesh=...)`` reports.
    """
    tp = mesh_tp_degree(mesh, axis)
    if tp <= 1:
        return paged_decode_attention_ragged(
            q, k_pages, v_pages, layer, page_tables, prefix_lens, k_new,
            v_new, sm_scale=sm_scale, variant=variant, interpret=interpret,
        )
    _check_heads(
        tp, [("n_heads", q.shape[1]), ("n_kv_heads", k_new.shape[1])]
    )
    quantized = is_quantized(k_pages)
    pg_specs, flatten, rebuild = _pages_specs(quantized, axis)
    heads = P(None, axis, None)
    n_pg = len(pg_specs)

    def local(q, *rest):
        kp = rebuild(rest[:n_pg])
        vp = rebuild(rest[n_pg : 2 * n_pg])
        layer, tables, lens, k_new, v_new = rest[2 * n_pg :]
        return paged_decode_attention_ragged(
            q, kp, vp, layer, tables, lens, k_new, v_new,
            sm_scale=sm_scale, variant=variant, interpret=interpret,
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            heads, *pg_specs, *pg_specs, P(), P(None, None), P(None),
            heads, heads,
        ),
        out_specs=heads,
        check_vma=False,
    )
    return fn(
        q, *flatten(k_pages), *flatten(v_pages), layer, page_tables,
        prefix_lens, k_new, v_new,
    )


@jax.named_scope(KV_SCATTER)
def sharded_scatter_kv_pages(
    mesh,
    k_pages,  # [L, P, ps, Hkv, D] array or QuantizedKV
    v_pages,
    k_all,  # [L, B, Hkv, D]
    v_all,
    page_idx,  # [B] int32 — global page ids
    slot,  # [B] int32
    *,
    interpret: bool | None = None,
    axis: str = TENSOR,
):
    """Post-scan KV scatter under tensor parallelism: each device DMAs its
    own head columns into its local page shard (page ids are global; the
    page axis is replicated). int8 caches quantize INSIDE the shard — exact
    under sharding, because scales are per (token, head) over the local D
    row. Falls through to the plain kernel when there is no tensor axis."""
    tp = mesh_tp_degree(mesh, axis)
    if tp <= 1:
        return scatter_kv_pages(
            k_pages, v_pages, k_all, v_all, page_idx, slot,
            interpret=interpret,
        )
    _check_heads(tp, [("n_kv_heads", k_all.shape[2])])
    quantized = is_quantized(k_pages)
    pg_specs, flatten, rebuild = _pages_specs(quantized, axis)
    new_kv = P(None, None, axis, None)
    n_pg = len(pg_specs)

    def local(*args):
        kp = rebuild(args[:n_pg])
        vp = rebuild(args[n_pg : 2 * n_pg])
        k_all, v_all, page_idx, slot = args[2 * n_pg :]
        ok, ov = scatter_kv_pages(
            kp, vp, k_all, v_all, page_idx, slot, interpret=interpret
        )
        return tuple(flatten(ok)) + tuple(flatten(ov))

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            *pg_specs, *pg_specs, new_kv, new_kv, P(None), P(None),
        ),
        out_specs=tuple(pg_specs) + tuple(pg_specs),
        check_vma=False,
    )
    out = fn(
        *flatten(k_pages), *flatten(v_pages), k_all, v_all, page_idx, slot
    )
    return rebuild(list(out[:n_pg])), rebuild(list(out[n_pg:]))


@jax.named_scope(ATTENTION)
def sharded_flash_attention(
    mesh,
    q,  # [B, Hq, S, D]
    k,  # [B, Hkv, S, D]
    v,
    causal: bool = True,
    *,
    axis: str = TENSOR,
):
    """Flash prefill attention under tensor parallelism: heads shard over
    the tensor axis (GQA groups stay whole per shard), each device runs the
    unmodified Pallas kernel on its local heads — per-head math is
    IDENTICAL to the single-chip kernel, so sharded prefill is bit-exact
    per head, not merely close. Forward-only on the serving path."""
    tp = mesh_tp_degree(mesh, axis)
    if tp <= 1:
        return flash_attention(q, k, v, causal)
    _check_heads(tp, [("n_heads", q.shape[1]), ("n_kv_heads", k.shape[1])])
    heads = P(None, axis, None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal),
        mesh=mesh,
        in_specs=(heads, heads, heads),
        out_specs=heads,
        check_vma=False,
    )(q, k, v)


@jax.named_scope(ATTENTION)
def sharded_flash_attention_chunked(
    mesh,
    q,  # [B, Hq, C, D]
    k,  # [B, Hkv, S_kv, D]
    v,
    *,
    q_offset: int,
    axis: str = TENSOR,
):
    """Chunked-prefill flash (rectangular q chunk vs the full prefix) under
    tensor parallelism — same head sharding as ``sharded_flash_attention``,
    with the chunk's global ``q_offset`` passed through unchanged."""
    tp = mesh_tp_degree(mesh, axis)
    if tp <= 1:
        return flash_attention_chunked(q, k, v, q_offset=q_offset)
    _check_heads(tp, [("n_heads", q.shape[1]), ("n_kv_heads", k.shape[1])])
    heads = P(None, axis, None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention_chunked(q, k, v, q_offset=q_offset),
        mesh=mesh,
        in_specs=(heads, heads, heads),
        out_specs=heads,
        check_vma=False,
    )(q, k, v)
