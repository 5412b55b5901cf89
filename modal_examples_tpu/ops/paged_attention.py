"""Ragged paged decode attention for TPU (Pallas → Mosaic).

The TPU-native replacement for vLLM's PagedAttention CUDA kernels — the core
of the reference's north-star serving path (vllm_inference.py; SURVEY.md §7
hard part #1: "Ragged paged attention kernel + continuous batching in JAX").

Memory layout (TPU-first, v2):
- KV cache pages live in **HBM** as ``[n_pages, page_size, Hkv, D]`` — one
  page holds ALL kv heads contiguously (token-major, heads innermost), so a
  single DMA moves ``page_size * Hkv * D`` elements (128KB at 7B shapes)
  instead of one tiny (page_size, D) tile per head. v1's per-(seq, head)
  grid issued 4KB DMAs and was ~50x off the HBM bandwidth floor on a real
  v5e chip. Heads-innermost (round 4) keeps the token dim OUT of the
  packed minor tile dims, so single-token scatter writes are legal strided
  DMAs (bf16 HBM memrefs pack sublane pairs — slicing a token row of the
  old [.., Hkv, ps, D] layout cannot lower; Hkv < 16 pages pay sublane
  padding instead, acceptable because GQA caches are Hkv/Hq-fraction
  sized).
- Each sequence owns a list of physical page ids (its *page table*); pages
  are allocated/freed by the serving engine's block allocator.

Kernel design:
- grid = (batch,): decode attention is HBM-bandwidth-bound; fewer, fatter
  programs keep the DMA engine streaming instead of paying per-program and
  per-DMA latency. Page tables + context lengths arrive via scalar prefetch
  (SMEM) so the kernel computes its own DMA addresses — the "ragged" part:
  each sequence reads exactly ceil(ctx/page_size) pages.
- pages stream HBM→VMEM with double buffering, overlapped with the
  online-softmax update of the previous page.
- all heads in ONE MXU matmul per page: q rows (all Hq query heads) against
  the page's (Hkv*page_size, D) keys with a block-diagonal head mask —
  off-head logits are -inf so the p·V matmul accumulates per-head results
  exactly. The off-diagonal FLOPs are free (the MXU is idle in a
  bandwidth-bound kernel); what matters is that both contractions are
  single dense (Hq, Hkv*ps, D) matmuls instead of Hkv tiny ones.

Runs in interpreter mode off-TPU (CPU CI), with a dense XLA reference in
ops.reference for ground truth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_quant import QuantizedKV, is_quantized, kv_gather, quantize_kv
from .scopes import ATTENTION


@jax.named_scope(ATTENTION)
def paged_decode_attention_inflight(
    q: jax.Array,  # [B, Hq, D]
    ks: jax.Array,  # [B, pages_per_seq, page_size, Hkv, D] — gathered pages
    vs: jax.Array,
    prefix_lens: jax.Array,  # [B] int32 — tokens already IN the cache
    k_new: jax.Array,  # [B, Hkv, D] — current token's K (not yet written)
    v_new: jax.Array,
    *,
    sm_scale: float | None = None,
) -> jax.Array:  # [B, Hq, D]
    """Decode attention over the cached prefix PLUS the in-flight token.

    The round-2 decode step wrote each token's K/V into the page arrays
    *inside* the layer scan and returned the full caches as stacked scan
    ys — a structure XLA materializes as full cache-slice traffic every
    layer of every step (measured: the single biggest gap between the 28 ms
    step and the weight-streaming floor). Keeping the current token's K/V in
    registers lets the model scatter ALL layers' KV once per step, outside
    the scan, so the pages are read-only here: prefix scores come from the
    gathered pages, the current token contributes one extra logit column,
    and both share one softmax. Exact same math as attention over pages
    that already hold the token (``reference.paged_decode_attention`` with
    ``context_lens = prefix_lens + 1``).

    Since PR 25 ``decode_step`` runs ``paged_decode_attention_chunked``
    (the same softmax over the live part of the table only); this
    full-width form stays as what the ragged kernels and the chunked loop
    are tested against (tests, ops/probes.py).
    """
    B, Hq, D = q.shape
    _, pages_per_seq, page_size, Hkv, _ = ks.shape
    G = Hq // Hkv
    if sm_scale is None:
        sm_scale = D**-0.5
    qg = q.reshape(B, Hkv, G, D)
    # cache-dtype operands into the MXU, f32 accumulation — an astype(f32)
    # on the gathered pages materializes an f32 cache copy per layer per
    # step (the dominant decode cost in the round-4 ablation, see above)
    s = jnp.einsum(
        "bhgd,bpthd->bhgpt", qg, ks, preferred_element_type=jnp.float32
    ) * sm_scale
    pos = (
        jnp.arange(pages_per_seq)[:, None] * page_size
        + jnp.arange(page_size)[None, :]
    )  # [pp, ps]
    valid = pos[None] < prefix_lens[:, None, None]  # [B, pp, ps]
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    flat = s.reshape(B, Hkv, G, pages_per_seq * page_size)
    # the current token at cache dtype: the numerics of reading it back
    # from the cache, which is what the next step's attention will do
    s_new = jnp.einsum(
        "bhgd,bhd->bhg", qg, k_new.astype(ks.dtype),
        preferred_element_type=jnp.float32,
    )[..., None] * sm_scale  # [B, Hkv, G, 1]
    all_s = jnp.concatenate([flat, s_new], axis=-1)
    p = jax.nn.softmax(all_s, axis=-1)
    p_prefix = p[..., :-1].reshape(s.shape).astype(vs.dtype)
    p_new = p[..., -1]  # [B, Hkv, G] f32
    o = jnp.einsum(
        "bhgpt,bpthd->bhgd", p_prefix, vs,
        preferred_element_type=jnp.float32,
    )
    o = o + p_new[..., None] * (
        v_new.astype(vs.dtype).astype(jnp.float32)[:, :, None, :]
    )
    return o.reshape(B, Hq, D).astype(q.dtype)


#: positions (page_size x pages) one trip of the chunked decode loop reads
#: per slot: the loop's only size. Tuned once on the v5e at Mistral-7B's
#: cache shapes (16 slots x 256 pages of 16, bf16, 8 KV heads of 128; PERF.md
#: section 6, PR 25): 256 read the full table fastest (21.5 ms a step of 32
#: layers against 24.2 at 128, 31.3 at 512 and 45.4 for the single gather)
#: and a 1200-token context too; the compiler keeps a gathered chunk of
#: this size in VMEM.
_CHUNK_POSITIONS = 256


def decode_chunk_pages(page_size: int, pages_per_seq: int) -> int:
    """Table columns (pages) a trip of ``paged_decode_attention_chunked``
    gathers: ``_CHUNK_POSITIONS`` worth, at most the whole table."""
    return max(1, min(pages_per_seq, _CHUNK_POSITIONS // page_size))


def decode_chunk_trips(longest, page_size: int, pages_per_seq: int):
    """Trips the loop makes when the longest live prefix is ``longest``
    positions: the chunks up to and including the one that holds its last
    token, 0 when nothing is live. Whole-number arithmetic on whatever
    ``longest`` is (a traced scalar in the op, a numpy array of the steps of
    a block where the engine counts what the device will read)."""
    w = decode_chunk_pages(page_size, pages_per_seq)
    span = w * page_size
    xp = jnp if isinstance(longest, jax.Array) else np  # the host stays off JAX
    return xp.minimum((longest + span - 1) // span, -(-pages_per_seq // w))


@jax.named_scope(ATTENTION)
def paged_decode_attention_chunked(
    q: jax.Array,  # [B, Hq, D]
    k_pages,  # [L, n_pages, page_size, Hkv, D] — the cache, in place
    v_pages,
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, pages_per_seq] int32
    prefix_lens: jax.Array,  # [B] int32 — tokens already IN the cache
    k_new: jax.Array,  # [B, Hkv, D] — current token's K (not yet written)
    v_new: jax.Array,
    *,
    sm_scale: float | None = None,
) -> jax.Array:  # [B, Hq, D]
    """``paged_decode_attention_inflight`` over the live context only.

    The same contract and mathematics, but the page table is walked in
    chunks of ``decode_chunk_pages`` columns by a loop whose trip count is
    read from ``prefix_lens``: it stops after the chunk that holds the
    batch's longest live prefix, so a step reads what its contexts need,
    not every slot's whole ``max_model_len`` (at 16 slots x 256 pages that
    was 134 MB gathered, written and read again for K and for V in every
    layer, whatever the contexts: PERF.md section 6, PR 25). Each trip
    gathers its chunk with ``kv_gather`` (an int8 cache dequantises there),
    scores it with the layout-preserving einsums (cache-dtype operands, f32
    accumulation), masks ``pos < prefix_lens`` and folds it into a running
    (max, sum, accumulator) in f32: an online softmax, exact up to f32
    rounding. The running state starts from the in-flight token's column,
    which is always there, so the maximum is finite from the first trip on
    and a chunk a slot has nothing in changes nothing (alpha = 1, p = 0):
    what a slot gets does not depend on how far a longer neighbour makes the
    loop run. A ``while`` of gathers and einsums, so still auto-partitionable
    over the KV-head axis under a sharded jit.
    """
    B, Hq, D = q.shape
    _, _, page_size, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    pages_per_seq = page_tables.shape[1]
    if sm_scale is None:
        sm_scale = D**-0.5
    W = decode_chunk_pages(page_size, pages_per_seq)
    if pages_per_seq % W:
        # pad the table with the trash page: those columns lie past every
        # prefix, so the mask drops them
        page_tables = jnp.pad(page_tables, ((0, 0), (0, -pages_per_seq % W)))
    trips = decode_chunk_trips(jnp.max(prefix_lens), page_size, pages_per_seq)
    # what the gather hands the einsums: the cache's dtype, or the query's
    # for an int8 cache (dequantised at it)
    kv_dtype = q.dtype if is_quantized(k_pages) else k_pages.dtype
    qg = q.reshape(B, Hkv, G, D)
    in_chunk = (
        jnp.arange(W)[:, None] * page_size + jnp.arange(page_size)[None, :]
    )  # [W, ps]

    def chunk(c, carry):
        m, l, acc = carry  # [B, Hkv, G], [B, Hkv, G], [B, Hkv, G, D] f32
        cols = jax.lax.dynamic_slice_in_dim(page_tables, c * W, W, axis=1)
        ks = kv_gather(k_pages, cols, layer=layer, dtype=q.dtype)
        vs = kv_gather(v_pages, cols, layer=layer, dtype=q.dtype)
        s = jnp.einsum(
            "bhgd,bpthd->bhgpt", qg, ks, preferred_element_type=jnp.float32
        ) * sm_scale  # [B, Hkv, G, W, ps]
        valid = (c * W * page_size + in_chunk)[None] < prefix_lens[
            :, None, None
        ]  # [B, W, ps]
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=(-2, -1)))
        p = jnp.exp(s - m_new[..., None, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=(-2, -1))
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgpt,bpthd->bhgd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    # the in-flight token at cache dtype, as if read back from the cache
    s_new = jnp.einsum(
        "bhgd,bhd->bhg", qg, k_new.astype(kv_dtype),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    v_tok = v_new.astype(kv_dtype).astype(jnp.float32)[:, :, None, :]
    _, l, acc = jax.lax.fori_loop(
        0, trips, chunk,
        (s_new, jnp.ones_like(s_new), jnp.broadcast_to(v_tok, (B, Hkv, G, D))),
    )
    return (acc / l[..., None]).reshape(B, Hq, D).astype(q.dtype)



@jax.named_scope(ATTENTION)
def paged_latent_decode_attention_chunked(
    q_lat: jax.Array,  # [B, Hq, C] — queries absorbed into the latent space
    q_pe: jax.Array,  # [B, Hq, R] — their rotated slice
    c_pages,  # [L, n_pages, page_size, 1, C] — normalised latents, in place
    r_pages,  # [n_pages, page_size, 1, R] — this layer's rotated keys, shared
    #           by all heads (or [L, ...]: indexed by ``layer`` like c_pages)
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, pages_per_seq] int32
    prefix_lens: jax.Array,  # [B] int32 — tokens already IN the cache
    c_new: jax.Array,  # [B, C] — current token's latent (not yet written)
    r_new: jax.Array,  # [B, R]
    *,
    sm_scale: float,
) -> jax.Array:  # [B, Hq, C] — attention-weighted latents
    """Absorbed latent (MLA) decode attention over the live context only.

    ``paged_decode_attention_chunked``'s loop (the same chunk size, the same
    trip count read from ``prefix_lens``, the same online softmax in f32
    started from the in-flight token's column) over a cache whose leaves are
    one latent and one rotated key a token, shared by every head: scores are
    ``q_lat . c + q_pe . r``, values are the latents themselves. A gathered
    chunk is read once for all ``Hq`` heads. The caller absorbs ``W_kvb``'s
    key half into ``q_lat`` and applies its value half to the result.
    """
    B, Hq, C = q_lat.shape
    page_size = c_pages.shape[2]
    pages_per_seq = page_tables.shape[1]
    W = decode_chunk_pages(page_size, pages_per_seq)
    if pages_per_seq % W:
        page_tables = jnp.pad(page_tables, ((0, 0), (0, -pages_per_seq % W)))
    trips = decode_chunk_trips(jnp.max(prefix_lens), page_size, pages_per_seq)
    kv_dtype = c_pages.dtype
    q_lat, q_pe = q_lat.astype(kv_dtype), q_pe.astype(kv_dtype)
    # a page's rotated keys as one row of page_size * R lanes: gathered as
    # [page_size, 1, R] slabs the R = 64 minor dim is half a lane tile. The
    # caller hands this layer's slice (a layer scan's sliced input): over
    # the whole [L, ...] leaf the TPU compiler keeps a pages-minor layout
    # and relays all of it out (192 MiB at the benchmark's size) for every
    # gather; the slice it relays out is an eighth of that, once a layer
    if r_pages.ndim == 5:
        r_pages = r_pages[layer]
    r_rows = r_pages.reshape(r_pages.shape[0], -1)  # [n_pages, ps * R]
    in_chunk = (
        jnp.arange(W)[:, None] * page_size + jnp.arange(page_size)[None, :]
    )  # [W, ps]

    def chunk(c, carry):
        m, l, acc = carry  # [B, Hq], [B, Hq], [B, Hq, C] f32
        cols = jax.lax.dynamic_slice_in_dim(page_tables, c * W, W, axis=1)
        cs = kv_gather(c_pages, cols, layer=layer)[:, :, :, 0]  # [B, W, ps, C]
        rs = r_rows[cols].reshape(B, W, page_size, -1)  # [B, W, ps, R]
        s = (
            jnp.einsum("bhc,bptc->bhpt", q_lat, cs,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhr,bptr->bhpt", q_pe, rs,
                         preferred_element_type=jnp.float32)
        ) * sm_scale  # [B, Hq, W, ps]
        valid = (c * W * page_size + in_chunk)[None] < prefix_lens[
            :, None, None
        ]  # [B, W, ps]
        s = jnp.where(valid[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=(-2, -1)))
        p = jnp.exp(s - m_new[..., None, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=(-2, -1))
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhpt,bptc->bhc", p.astype(cs.dtype), cs,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    # the in-flight token at cache dtype, as if read back from the cache
    c_tok, r_tok = c_new.astype(kv_dtype), r_new.astype(kv_dtype)
    s_new = (
        jnp.einsum("bhc,bc->bh", q_lat, c_tok, preferred_element_type=jnp.float32)
        + jnp.einsum("bhr,br->bh", q_pe, r_tok, preferred_element_type=jnp.float32)
    ) * sm_scale
    acc0 = jnp.broadcast_to(c_tok.astype(jnp.float32)[:, None, :], (B, Hq, C))
    _, l, acc = jax.lax.fori_loop(
        0, trips, chunk, (s_new, jnp.ones_like(s_new), acc0)
    )
    return acc / l[..., None]


def _decode_kernel_ragged(
    # scalar prefetch
    layer_ref,  # (1,) int32, SMEM — which layer of the [L, P, ...] cache
    page_tables_ref,  # (B * pages_per_seq,) int32, SMEM
    prefix_lens_ref,  # (B,) int32, SMEM — tokens already IN the cache
    # inputs — FULL arrays as single constant-index blocks: Mosaic skips the
    # re-fetch when a block's index map is unchanged between grid steps, so
    # q/k_new/v_new stream into VMEM once per pallas_call instead of paying
    # 4 small block DMAs per program (measured ~18 us/program of pure
    # overhead at 7B shapes with per-program (1, H, D) blocks)
    q_ref,  # (B, Hq, D) VMEM
    k_new_ref,  # (B, Hkv, D) VMEM — current token's K (not yet written)
    v_new_ref,  # (B, Hkv, D) VMEM
    k_hbm,  # (L, n_pages, page_size, Hkv, D) ANY/HBM
    v_hbm,
    # quantized=True adds ks_ref/vs_ref: this sequence's per-page scale rows,
    # (1, pages_per_seq, page_size*Hkv) f32 VMEM blocks (see
    # _gathered_scale_rows). `*rest` keeps ONE kernel for both layouts.
    *rest,  # [ks_ref, vs_ref,] o_ref, k_scr, v_scr, acc_scr, sems
    page_size: int,
    pages_per_seq: int,
    group: int,  # Hq // Hkv
    sm_scale: float,
    quantized: bool = False,
):
    """Ragged decode attention v3: prefix pages + ONE in-flight column.

    A kernel that reads the current token back from the cache forces the
    model to scatter each layer's KV *before* attention — a scan-threaded
    cache, which XLA materializes as full cache copies (round-3 NOTES). v3
    keeps the pages READ-ONLY (the fast decode structure: one scatter per
    step, after the layer scan) by folding the current token's K/V — still in
    registers — into the online softmax as one extra logit column, exactly
    like ops.paged_decode_attention_inflight does in XLA. It also indexes the
    full [L, P, ...] cache via a prefetched layer scalar, so the layer scan
    never slices (= copies) a per-layer cache view. Reads exactly
    ceil(prefix/page_size) pages per sequence — the XLA gather formulation
    reads (and materializes) all pages_per_seq pages regardless of context,
    the dominant, superlinear-in-slots decode cost in a builder's round-4
    knock-out ablation (44 of 57 ms/step at 7B int8, 32 slots; not a driver
    record).

    With ``quantized=True`` the pages stream as int8 and the per-token-head
    scales arrive as lane-major rows (one f32 per logit column), so the
    dequant is a multiply on the (Hq, W) scores and probabilities instead of
    on every (W, D) page element — KV HBM traffic is halved, the online
    softmax math is unchanged.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, k_scr, v_scr, acc_scr, sems = rest
    else:
        o_ref, k_scr, v_scr, acc_scr, sems = rest
    b = pl.program_id(0)
    li = layer_ref[0]
    prefix, n_pages, depth, k_dma, v_dma = _ragged_ring_setup(
        li, page_tables_ref, prefix_lens_ref, b, k_hbm, v_hbm, k_scr, v_scr,
        sems, pages_per_seq,
    )

    acc_scr[:] = jnp.zeros_like(acc_scr)
    q = q_ref[b]  # (Hq, D) — stays in model dtype INTO the MXU (native
    # mixed-precision, f32 accumulate); sm_scale is applied to the f32
    # scores. Explicit astype(f32) on the page operands forced a Mosaic
    # retile of every page (measured ~0.6 us of the ~2.3 us/page cost).
    Hq, D = q.shape
    Hkv = k_scr.shape[2]
    W = page_size * Hkv  # token-major flatten: column c = (tok, head)

    # static (Hq, W) head-alignment mask: query row r (kv head r // group)
    # may only see columns of its own kv head (column c % Hkv). The
    # off-head MXU FLOPs are the price of one dense matmul per page; at
    # MHA (group=1, the 7B shape) that is Hkv x more logits than exist —
    # the measured per-page cost is ~2 us compute-bound (a VPU
    # mul+lane-reduce formulation measured the same, round 4).
    row_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 0) // group
    col_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 1) % Hkv
    head_ok = row_head == col_head
    col_tok = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 1) // Hkv

    def body(i, carry):
        m_prev, l_prev = carry  # (Hq, 1) each
        slot = jax.lax.rem(i, depth)

        # refill the slot consumed LAST iteration (its loads are done:
        # sequential loop order) with the page depth-1 ahead — keeps
        # depth-1 transfers in flight so the DMA engine streams
        # back-to-back instead of paying issue latency per page
        @pl.when(i + depth - 1 < n_pages)
        def _prefetch():
            nxt = jax.lax.rem(i + depth - 1, depth)
            k_dma(nxt, i + depth - 1).start()
            v_dma(nxt, i + depth - 1).start()

        k_dma(slot, i).wait()
        v_dma(slot, i).wait()
        k = k_scr[slot].reshape(W, D)  # cache dtype, no retile
        v = v_scr[slot].reshape(W, D)
        if quantized:
            # int8 values are exact at the query's compute dtype; their
            # scales multiply the scores (K) and probabilities (V) below
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (Hq, W) f32
        if quantized:
            s = s * ks_ref[0, pl.ds(i, 1), :]  # (1, W): column c's k scale
        valid = head_ok & (i * page_size + col_tok < prefix)
        s = jnp.where(valid, s, -jnp.inf)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(m_new), jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(
            jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
        )
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(i, 1), :]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # flash-attention numerics: f32 softmax, cache-dtype PV operands
        acc_scr[:] = acc_scr[:] * alpha + pv
        return m_new, l_new

    init = (
        jnp.full((Hq, 1), -jnp.inf, jnp.float32),
        jnp.zeros((Hq, 1), jnp.float32),
    )
    m_prev, l_prev = jax.lax.fori_loop(0, n_pages, body, init)

    _inflight_epilogue(
        q, k_new_ref, v_new_ref, b, o_ref, acc_scr, m_prev, l_prev, group,
        sm_scale,
    )


def ragged_shapes_ok(head_dim: int, page_size: int) -> bool:
    """Mosaic legality for the ragged decode kernels on TPU: pages must be
    whole (16, 128) bf16 tiles for the HBM→VMEM DMAs. Single source of
    truth shared by the kernel wrappers (hard error) and
    ``llama.paged_impl_plan`` (soft downgrade to the XLA gather)."""
    return head_dim % 128 == 0 and page_size % 16 == 0


def flat_variant_hkv_multiple(kv_dtype: str = "bfloat16") -> int:
    """The Hkv multiple the "flat" variant's (ps, Hkv, D) -> (ps*Hkv, D)
    page flatten needs: the sublane count of one packed Mosaic tile —
    16 for bf16, 32 for int8 ((32, 128) tiles)."""
    return 32 if str(kv_dtype) == "int8" else 16


def ragged_variant_for(n_kv_heads: int, kv_dtype: str = "bfloat16") -> str:
    """Default kernel formulation: "flat" (one all-heads matmul) needs the
    (ps, Hkv, D) -> (ps*Hkv, D) flatten, legal only at Hkv % tile-sublanes
    (16 bf16, 32 int8); everything else (GQA) takes "grouped" (per-kv-head
    contractions)."""
    return (
        "flat"
        if n_kv_heads % flat_variant_hkv_multiple(kv_dtype) == 0
        else "grouped"
    )


def scatter_shapes_ok(head_dim: int) -> bool:
    """Mosaic legality for scatter_kv_pages' strided (Hkv, D) DMAs."""
    return head_dim % 128 == 0


def _ragged_ring_setup(
    li, page_tables_ref, prefix_lens_ref, b, k_hbm, v_hbm, k_scr, v_scr,
    sems, pages_per_seq,
):
    """v3 (flat) DMA-ring prologue: page-id lookup, K/V copy factories,
    and the warm-up that puts depth-1 page transfers in flight. The
    grouped kernel streams at CHUNK granularity with clamped page ids and
    owns its own inlined version."""
    prefix = prefix_lens_ref[b]
    page_size = k_scr.shape[1]
    n_pages = pl.cdiv(prefix, page_size)

    def page_id(i):
        return page_tables_ref[b * pages_per_seq + i]

    def k_dma(slot, i):
        return pltpu.make_async_copy(
            k_hbm.at[li, page_id(i)], k_scr.at[slot], sems.at[slot, 0]
        )

    def v_dma(slot, i):
        return pltpu.make_async_copy(
            v_hbm.at[li, page_id(i)], v_scr.at[slot], sems.at[slot, 1]
        )

    depth = k_scr.shape[0]
    for j in range(depth - 1):
        @pl.when(j < n_pages)
        def _(j=j):
            k_dma(j, j).start()
            v_dma(j, j).start()

    return prefix, n_pages, depth, k_dma, v_dma


def _inflight_epilogue(
    q, k_new_ref, v_new_ref, b, o_ref, acc_scr, m_prev, l_prev, group,
    sm_scale,
):
    """Shared v3/v4 epilogue: fold the current token's K/V (still in
    registers, not yet written to the cache) into the online softmax as one
    extra column, normalize, and write the output row. Per q row r the only
    valid kv head is r // group — selected via a (Hq, Hkv) mask so both
    contractions stay dense MXU matmuls (the waste is one column)."""
    Hq = q.shape[0]
    k_new = k_new_ref[b]  # (Hkv, D) cache dtype
    v_new = v_new_ref[b].astype(jnp.float32)
    Hkv = k_new.shape[0]
    s_all = jax.lax.dot_general(
        q, k_new, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (Hq, Hkv)
    rh = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv), 0) // group
    ch = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv), 1)
    own = rh == ch
    s_new = jnp.sum(jnp.where(own, s_all, 0.0), axis=-1, keepdims=True)

    m_new = jnp.maximum(m_prev, s_new)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    p_new = jnp.exp(s_new - m_new)  # (Hq, 1)
    l_final = l_prev * alpha + p_new
    p_mat = jnp.where(own, p_new, 0.0)  # (Hq, Hkv)
    pv_new = jax.lax.dot_general(
        p_mat, v_new, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Hq, D)
    acc = acc_scr[:] * alpha + pv_new
    l_safe = jnp.where(l_final > 0, l_final, 1.0)
    o_ref[b] = (acc / l_safe).astype(o_ref.dtype)


def _decode_kernel_ragged_grouped(
    # scalar prefetch
    layer_ref,  # (1,) int32, SMEM
    page_tables_ref,  # (B * pages_per_seq,) int32, SMEM
    prefix_lens_ref,  # (B,) int32, SMEM
    # inputs (same constant-index full-array blocks as v3)
    q_ref,  # (B, Hq, D) VMEM
    k_new_ref,  # (B, Hkv, D) VMEM
    v_new_ref,  # (B, Hkv, D) VMEM
    k_hbm,  # (L, n_pages, page_size, Hkv, D) ANY/HBM
    v_hbm,
    # quantized=True adds ks_ref/vs_ref: this sequence's per-head scale rows,
    # (1, Hkv, n_chunks, chunk*page_size) f32 VMEM blocks (see
    # _gathered_scale_rows)
    *rest,  # [ks_ref, vs_ref,] o_ref, k_scr, v_scr, acc_scr, sems
    page_size: int,
    pages_per_seq: int,
    group: int,
    sm_scale: float,
    chunk: int,
    quantized: bool = False,
):
    """Ragged decode attention v4 ("grouped"): per-kv-head contractions
    over CHUNKS of pages.

    Differences from v3 (`_decode_kernel_ragged`), same online-softmax
    math:
    - logits come from Hkv unrolled (G, D) x (D, chunk*page_size) matmuls
      — one per kv head — instead of one (Hq, page_size*Hkv, D)
      block-diagonal matmul. Computes EXACTLY the real logits: v3 computes
      Hkv x more than exist at MHA, and the per-page cost evidence says
      the masked logits' `exp`s are what the ~2 us/page buys (NOTES r5
      "attention cost analysis").
    - no (ps, Hkv, D) -> (ps*Hkv, D) flatten, so the Hkv % 16 Mosaic
      relayout constraint disappears: GQA models (llama-3.1's Hkv=8) run
      the kernel instead of falling back to the XLA gather (the
      reference's serving targets are GQA-era, vllm_inference.py:54-58).
    - `chunk` pages per softmax update: the logits tile is
      (Hq, chunk*ps) — chunk=8 at ps=16 fills all 128 VPU lanes (a
      single-page (Hq, 16) tile wastes 7/8 of each vreg) and amortizes
      the per-iteration sem-wait/loop overhead by chunk x. The DMA ring
      is two half-buffers of `chunk` pages (scratch depth = 2*chunk):
      the next chunk streams while the current one computes.
    The trade: Hkv small matmuls per chunk at G-row MXU utilization.

    ``quantized=True`` streams int8 pages and multiplies each head's scores
    and probabilities by its lane-major scale row — same online softmax,
    half the KV HBM traffic.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, k_scr, v_scr, acc_scr, sems = rest
    else:
        o_ref, k_scr, v_scr, acc_scr, sems = rest
    b = pl.program_id(0)
    li = layer_ref[0]
    prefix = prefix_lens_ref[b]
    C = chunk
    # chunk-granular streaming: a processed chunk loads ALL C of its page
    # slots — trailing lanes past the context clamp to a real table entry
    # (a duplicate page), so scratch never holds uninitialized data. The
    # duplicate's logits are masked to -inf, which matters in the p.V
    # matmul: 0 x finite = 0, whereas a garbage (NaN) page would poison
    # the contraction despite the mask.
    n_chunks = pl.cdiv(prefix, C * page_size)
    n_pages = pl.cdiv(prefix, page_size)

    def page_id(i):
        # clamp into the sequence's ALLOCATED pages (n_pages >= 1 whenever
        # any DMA is issued, since n_chunks > 0 implies prefix > 0): table
        # entries beyond the allocation may be caller padding
        return page_tables_ref[
            b * pages_per_seq + jax.lax.min(i, n_pages - 1)
        ]

    def k_dma(slot, i):
        return pltpu.make_async_copy(
            k_hbm.at[li, page_id(i)], k_scr.at[slot], sems.at[slot, 0]
        )

    def v_dma(slot, i):
        return pltpu.make_async_copy(
            v_hbm.at[li, page_id(i)], v_scr.at[slot], sems.at[slot, 1]
        )

    # warm-up: chunk 0 into half 0 (every chunk's start has exactly one
    # matching wait in the body: warmup pairs with iteration 0)
    @pl.when(n_chunks > 0)
    def _():
        for j in range(C):
            k_dma(j, j).start()
            v_dma(j, j).start()

    acc_scr[:] = jnp.zeros_like(acc_scr)
    q = q_ref[b]  # (Hq, D) model dtype into the MXU, f32 accumulate
    Hq, D = q.shape
    Hkv = k_scr.shape[2]
    G = group
    ps = page_size
    W = C * ps  # chunk row = (page_in_chunk, token_in_page), row-major
    col_tok = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 1)

    def body(i, carry):
        m_prev, l_prev = carry  # (Hq, 1) each
        base = jax.lax.rem(i, 2) * C
        nxt_base = jax.lax.rem(i + 1, 2) * C

        # stream the NEXT chunk into the other half while this one computes
        @pl.when(i + 1 < n_chunks)
        def _():
            for j in range(C):
                k_dma(nxt_base + j, (i + 1) * C + j).start()
                v_dma(nxt_base + j, (i + 1) * C + j).start()
        # wait this chunk's pages (all C were started: warmup or prefetch)
        for j in range(C):
            k_dma(base + j, i * C + j).wait()
            v_dma(base + j, i * C + j).wait()

        def head_slice(scr, h):
            """The head's (chunk*ps, D) keys/values; int8 values are exact
            at the query's compute dtype (their scales multiply the scores
            and probabilities instead)."""
            x = scr[pl.ds(base, C), :, h, :]
            if quantized:
                x = x.astype(q.dtype)
            return x.reshape(W, D)

        def head_rows(scale_ref):
            """(Hq, W) scale rows for this chunk: row h*G+g carries kv head
            h's per-column scales."""
            return jnp.concatenate(
                [
                    jnp.broadcast_to(scale_ref[0, h, pl.ds(i, 1), :], (G, W))
                    for h in range(Hkv)
                ],
                axis=0,
            )

        # per-kv-head: query rows h*G:(h+1)*G against the head's
        # (chunk*ps, D) keys — static head slices, unrolled over Hkv
        s_parts = []
        for h in range(Hkv):
            s_parts.append(
                jax.lax.dot_general(
                    q[h * G : (h + 1) * G], head_slice(k_scr, h),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        s = jnp.concatenate(s_parts, axis=0) * sm_scale  # (Hq, W) f32
        if quantized:
            s = s * head_rows(ks_ref)
        s = jnp.where(i * W + col_tok < prefix, s, -jnp.inf)

        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(m_new), jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(
            jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
        )
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * head_rows(vs_ref)
        pv_parts = []
        for h in range(Hkv):
            v_h = head_slice(v_scr, h)
            pv_parts.append(
                jax.lax.dot_general(
                    p[h * G : (h + 1) * G].astype(v_h.dtype), v_h,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        acc_scr[:] = acc_scr[:] * alpha + jnp.concatenate(pv_parts, axis=0)
        return m_new, l_new

    init = (
        jnp.full((Hq, 1), -jnp.inf, jnp.float32),
        jnp.zeros((Hq, 1), jnp.float32),
    )
    m_prev, l_prev = jax.lax.fori_loop(0, n_chunks, body, init)
    _inflight_epilogue(
        q, k_new_ref, v_new_ref, b, o_ref, acc_scr, m_prev, l_prev, group,
        sm_scale,
    )


def _gathered_scale_rows(scale, layer, page_tables, variant, chunk):
    """Each sequence's int8-KV scales as lane-major rows, one row per
    softmax update of the kernel, gathered by XLA outside it.

    The cache keeps scales as ``[L, P, page_size, Hkv]`` f32. Mosaic cannot
    DMA a page's ``(page_size, Hkv)`` slab out of that (an HBM slice must
    cover whole 128-lane tiles of the minor dim, and Hkv is 8..32), and the
    kernels want the scales along the LOGIT axis anyway — one f32 per score
    column — so they arrive as an ordinary VMEM-blocked input:

    - ``flat``: ``[B, pages_per_seq, page_size*Hkv]`` — row i is page i's
      scales in the kernel's column order c = tok*Hkv + head;
    - ``grouped``: ``[B, Hkv, n_chunks, chunk*page_size]`` — row (h, i) is
      kv head h's scales for chunk i's columns (page_in_chunk, tok).

    Unlike the pages this reads all ``pages_per_seq`` rows whatever the
    context; scales are 1/32 of the int8 page bytes at D=128."""
    B, pp = page_tables.shape
    ps, Hkv = scale.shape[2:]
    rows = scale[layer, page_tables]  # [B, pp, ps, Hkv]
    if variant == "flat":
        return rows.reshape(B, pp, ps * Hkv)
    n_chunks = -(-pp // chunk)
    rows = jnp.pad(rows, ((0, 0), (0, n_chunks * chunk - pp), (0, 0), (0, 0)))
    return rows.transpose(0, 3, 1, 2).reshape(B, Hkv, n_chunks, chunk * ps)


def paged_decode_attention_ragged(
    q: jax.Array,  # [B, Hq, D]
    k_pages: jax.Array,  # [L, n_pages, page_size, Hkv, D] — the FULL cache
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32 — which layer to attend against
    page_tables: jax.Array,  # [B, pages_per_seq] int32
    prefix_lens: jax.Array,  # [B] int32 — tokens already in the cache
    k_new: jax.Array,  # [B, Hkv, D] — current token's K (cache dtype)
    v_new: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    variant: str | None = None,  # None: "flat" if Hkv%16==0 else "grouped"
) -> jax.Array:  # [B, Hq, D]
    """Pallas ragged decode attention over prefix pages + the in-flight
    token. Drop-in exact match for ``paged_decode_attention_inflight``
    given ``ks = k_pages[layer, page_tables]``.

    Two kernel formulations share the DMA/online-softmax structure:
    - ``"flat"`` (v3, `_decode_kernel_ragged`): one block-diagonal
      all-heads matmul per page; needs Hkv%16 for the page flatten.
    - ``"grouped"`` (v4, `_decode_kernel_ragged_grouped`): Hkv per-kv-head
      matmuls — only real logits, any Hkv (GQA's Hkv=8 included).
    Default picks flat where legal (the round-4 measured configuration)
    and grouped otherwise; pass ``variant=`` explicitly to A/B.

    ``k_pages``/``v_pages`` may be int8 :class:`~.kv_quant.QuantizedKV`
    caches: both variants then DMA the int8 pages and apply the scales
    (:func:`_gathered_scale_rows`) to the scores and probabilities —
    tolerance-accurate vs the f32 cache (the accuracy contract in
    docs/kv_cache.md), half the KV HBM traffic.
    """
    B, Hq, D = q.shape
    quantized = is_quantized(k_pages)
    L, n_pages, page_size, Hkv, _ = k_pages.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must be a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    pages_per_seq = page_tables.shape[1]
    if sm_scale is None:
        sm_scale = D**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kv_dtype = "int8" if quantized else "bfloat16"
    if variant is None:
        variant = ragged_variant_for(Hkv, kv_dtype)
    if variant not in ("flat", "grouped"):
        raise ValueError(f"unknown variant {variant!r}: flat | grouped")
    if not interpret and not ragged_shapes_ok(D, page_size):
        # fail with the constraint instead of an opaque Mosaic lowering
        # error: pages must be whole (16, 128) bf16 / (32, 128) int8 tiles
        raise ValueError(
            f"paged_decode_attention_ragged needs head_dim%128==0 and "
            f"page_size%16==0 on TPU; got D={D}, page_size={page_size}"
        )
    flat_mult = flat_variant_hkv_multiple(kv_dtype)
    if not interpret and variant == "flat" and Hkv % flat_mult:
        raise ValueError(
            f"variant='flat' needs n_kv_heads%{flat_mult}==0 on TPU for "
            f"{kv_dtype} pages (the (ps, Hkv, D) -> (ps*Hkv, D) flatten); "
            f"got Hkv={Hkv} — use variant='grouped' (the default for this "
            "shape)"
        )

    # int8 caches compute at (and fold the in-flight token at) the query's
    # dtype; plain caches keep their own dtype into the MXU exactly as
    # before (no retile, bit-identical default path)
    compute_dtype = q.dtype if quantized else k_pages.dtype
    # DMA ring depth: enough in-flight pages to hide issue latency (measured
    # ~2.3 us/page at depth 2), capped so K+V scratch stays ~<=4 MB of VMEM.
    # int8 pages are half the bytes, so the same budget holds twice the ring
    page_bytes = page_size * Hkv * D * k_pages.dtype.itemsize
    depth = max(2, min(pages_per_seq, (2 * 1024 * 1024) // max(page_bytes, 1)))
    chunk = 1
    if variant == "grouped":
        # chunked updates: up to 8 pages per softmax step (8*ps=128 lanes
        # at ps=16 — a full vreg row), double-buffered halves
        chunk = max(1, min(8, pages_per_seq, depth // 2))
        depth = 2 * chunk

    def _const3(shape):
        return pl.BlockSpec(
            shape, lambda b, *_refs: (0, 0, 0), memory_space=pltpu.VMEM
        )

    # full arrays, constant index maps: fetched into VMEM once per call,
    # not once per program (see _decode_kernel_ragged docstring)
    in_specs = [
        _const3((B, Hq, D)),
        _const3((B, Hkv, D)),
        _const3((B, Hkv, D)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        q,
        k_new.astype(compute_dtype),
        v_new.astype(compute_dtype),
    ]
    if quantized:
        scale_rows = [
            _gathered_scale_rows(
                pages.scale, layer, page_tables, variant, chunk
            )
            for pages in (k_pages, v_pages)
        ]
        block = (1,) + scale_rows[0].shape[1:]
        in_specs += [
            pl.BlockSpec(
                block, lambda b, *_refs: (b,) + (0,) * (len(block) - 1),
                memory_space=pltpu.VMEM,
            )
        ] * 2
        operands += [k_pages.data, v_pages.data, *scale_rows]
    else:
        operands += [k_pages, v_pages]
    scratch = [
        pltpu.VMEM((depth, page_size, Hkv, D), k_pages.dtype),
        pltpu.VMEM((depth, page_size, Hkv, D), v_pages.dtype),
        pltpu.VMEM((Hq, D), jnp.float32),
        pltpu.SemaphoreType.DMA((depth, 2)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (B, Hq, D), lambda b, *_refs: (0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=scratch,
    )
    kernel_kw = dict(
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        group=G,
        sm_scale=sm_scale,
        quantized=quantized,
    )
    if variant == "flat":
        kernel = functools.partial(_decode_kernel_ragged, **kernel_kw)
    else:
        kernel = functools.partial(
            _decode_kernel_ragged_grouped, chunk=chunk, **kernel_kw
        )
    scale_bytes = 4 * page_size * Hkv if quantized else 0
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * Hq * pages_per_seq * page_size * D),
            bytes_accessed=int(
                2 * B * pages_per_seq
                * (Hkv * page_size * D * k_pages.dtype.itemsize + scale_bytes)
            ),
            transcendentals=int(B * Hq * pages_per_seq * page_size),
        ),
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        page_tables.reshape(-1).astype(jnp.int32),
        prefix_lens.astype(jnp.int32),
        *operands,
    )
    return out


def _kv_scatter_kernel(
    # scalar prefetch
    page_idx_ref,  # (B,) int32
    slot_ref,  # (B,) int32
    k_src,  # (L, B, Hkv, D) ANY/HBM — new K per layer per slot
    v_src,
    _k_pages_in,  # aliased to the outputs: the update is in place
    _v_pages_in,
    k_out,  # (L, P, page_size, Hkv, D) ANY/HBM
    v_out,
    sems,  # DMA sems (2, 2)
):
    """One strided HBM->HBM DMA per (slot, array): copies the [L, Hkv, D]
    column of new KV into (page_idx[b], slot[b]) of every layer's pages.

    XLA's scatter for the same update measured 4.8 ms/step at 7B/32 slots
    (a builder's round-4 knock-out ablation, not a driver record) — it
    rewrites far more than the 33 MB it touches. Dead slots all target
    trash page 0 slot 0; those writes race harmlessly (the trash page's
    content is never attended).
    """
    b = pl.program_id(0)
    nb = pl.num_programs(0)

    def copies(bb):
        pid = page_idx_ref[bb]
        sl = slot_ref[bb]
        buf = jax.lax.rem(bb, 2)
        return [
            pltpu.make_async_copy(
                src.at[:, bb], out.at[:, pid, sl], sems.at[buf, a]
            )
            for a, (src, out) in enumerate(((k_src, k_out), (v_src, v_out)))
        ]

    # two-deep pipeline: start this program's copies, wait the previous
    # program's (issued last grid step) so issue latency overlaps transfer
    for c in copies(b):
        c.start()

    @pl.when(b > 0)
    def _():
        for c in copies(b - 1):
            c.wait()

    @pl.when(b == nb - 1)
    def _():
        for c in copies(b):
            c.wait()


def scatter_kv_pages(
    k_pages: jax.Array,  # [L, P, ps, Hkv, D]
    v_pages: jax.Array,
    k_all: jax.Array,  # [L, B, Hkv, D] — new KV per layer per slot
    v_all: jax.Array,
    page_idx: jax.Array,  # [B] int32 — target page per slot
    slot: jax.Array,  # [B] int32 — position within the page
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Write every layer's new KV into the paged cache in place (one strided
    DMA per slot per array) — the Pallas replacement for the post-scan XLA
    scatter in llama.decode_step. Exact same semantics as
    ``pages.at[:, page_idx, slot].set(...)`` for distinct targets; dead
    slots (all pointed at trash page 0) may race, which is harmless.

    int8 caches quantize HERE (per token-head amax/127, fused by XLA into
    the producing program). The int8 K/V columns go through the DMA
    pipeline; their f32 scale columns ([L, Hkv] per slot — a minor dim far
    below the 128 lanes a Mosaic DMA slice must cover) take the XLA scatter,
    which for an array 1/32 the size of the pages is the cheap part."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = is_quantized(k_pages)
    L, B, Hkv, D = k_all.shape
    if not interpret and not scatter_shapes_ok(D):
        raise ValueError(
            f"scatter_kv_pages needs head_dim%128==0 on TPU for the "
            f"strided (Hkv, D) minor-dim DMAs; got D={D}. Use "
            f"llama.decode_step / paged_impl_plan for automatic fallback "
            "to the XLA scatter."
        )
    if quantized:
        qk, qv = quantize_kv(k_all), quantize_kv(v_all)
        srcs = [qk.data, qv.data]
        pages = [k_pages.data, v_pages.data]
    else:
        srcs = [k_all.astype(k_pages.dtype), v_all.astype(v_pages.dtype)]
        pages = [k_pages, v_pages]
    if interpret:
        # interpreter-mode DMAs of doubly-indexed HBM views are flaky; the
        # XLA scatter is exact and CPU tests only check semantics. Adjacent
        # advanced indices (dims 1, 2) keep their position: result [L, B,
        # Hkv, D] lines up with k_all directly.
        outs = [p.at[:, page_idx, slot].set(s) for p, s in zip(pages, srcs)]
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA((2, 2))],
        )
        outs = pl.pallas_call(
            _kv_scatter_kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pages
            ],
            # operands: 2 scalar-prefetch, 2 sources, then the page arrays —
            # aliased through so the update is in place
            input_output_aliases={4: 0, 5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(
            page_idx.astype(jnp.int32),
            slot.astype(jnp.int32),
            *srcs,
            *pages,
        )
    if quantized:
        return (
            QuantizedKV(
                data=outs[0],
                scale=k_pages.scale.at[:, page_idx, slot].set(qk.scale),
            ),
            QuantizedKV(
                data=outs[1],
                scale=v_pages.scale.at[:, page_idx, slot].set(qv.scale),
            ),
        )
    return outs[0], outs[1]
