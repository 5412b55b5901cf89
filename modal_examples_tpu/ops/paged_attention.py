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
- pages stream HBM→VMEM a chunk at a time through a ring of two halves
  that carries over from one sequence to the next, overlapped with the
  online-softmax updates of the chunk before (`_decode_kernel_ragged`).
- all heads in ONE MXU matmul per update ("flat"): q rows (all Hq query
  heads) against the pages' (Hkv*page_size, D) keys with a block-diagonal
  head mask — off-head logits are -inf so the p·V matmul accumulates
  per-head results exactly. The off-diagonal FLOPs are free (the MXU is
  idle in a bandwidth-bound kernel); what matters is that both operands are
  the pages as they lie, where a per-head slice of a token-major page is a
  relayout of every element.

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
from .scopes import ATTENTION, WINDOW_ATTENTION


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


def _decode_attention_chunked(
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
    starts: jax.Array | None = None,  # [B] int32 — first position a slot sees
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

    ``starts`` (``paged_window_decode_attention_chunked``'s): table
    positions below a slot's start are masked like those past its prefix,
    and the trips' gathers are plain indexing under the caller's scope
    instead of ``kv_gather``'s ``mtpu.page_gather``: a window layer's whole
    cost, fetching its ring included, reads under ``mtpu.window_attention``
    (its roofline share holds the ring's bytes against that time). Unset,
    the program is what it was.
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
        if starts is None:
            ks = kv_gather(k_pages, cols, layer=layer, dtype=q.dtype)
            vs = kv_gather(v_pages, cols, layer=layer, dtype=q.dtype)
        else:
            ks, vs = k_pages[layer, cols], v_pages[layer, cols]
        s = jnp.einsum(
            "bhgd,bpthd->bhgpt", qg, ks, preferred_element_type=jnp.float32
        ) * sm_scale  # [B, Hkv, G, W, ps]
        pos = (c * W * page_size + in_chunk)[None]
        valid = pos < prefix_lens[:, None, None]  # [B, W, ps]
        if starts is not None:
            valid = valid & (pos >= starts[:, None, None])
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
def paged_decode_attention_chunked(
    q, k_pages, v_pages, layer, page_tables, prefix_lens, k_new, v_new, *,
    sm_scale: float | None = None,
) -> jax.Array:
    return _decode_attention_chunked(
        q, k_pages, v_pages, layer, page_tables, prefix_lens, k_new, v_new,
        sm_scale=sm_scale,
    )


paged_decode_attention_chunked.__doc__ = _decode_attention_chunked.__doc__


@jax.named_scope(WINDOW_ATTENTION)
def paged_window_decode_attention_chunked(
    q: jax.Array,  # [B, Hq, D]
    k_pages,  # [Lw, n_window_pages, page_size, Hkv, D]: the window group's pages
    v_pages,
    layer: jax.Array,  # scalar int32: the layer's row in the group
    window_tables: jax.Array,  # [B, ring] int32: each slot's ring of pages
    positions: jax.Array,  # [B] int32: the token's position (0 for a dead slot)
    k_new: jax.Array,  # [B, Hkv, D]
    v_new: jax.Array,
    *,
    window: int,
    sm_scale: float | None = None,
) -> jax.Array:  # [B, Hq, D]
    """The chunked decode loop over a sliding-window layer's ring of pages:
    the token at ``positions[b]`` attends to itself and the ``window - 1``
    positions before it. The table is walked from the oldest page the
    window reaches (``window_decode_view``), so the trips cover a window and
    its page of slack whatever the context."""
    if is_quantized(k_pages):
        raise NotImplementedError("a window group's pages in int8")
    tables, prefix_lens, starts = window_decode_view(
        window_tables, positions, window, k_pages.shape[2]
    )
    return _decode_attention_chunked(
        q, k_pages, v_pages, layer, tables, prefix_lens, k_new, v_new,
        sm_scale=sm_scale, starts=starts,
    )


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


def window_ring_pages(window: int, page_size: int) -> int:
    """Pages a sequence holds in a sliding-window layer's page group: the
    pages ``window`` positions span wherever they start (a query at ``t``
    sees ``t - window + 1 .. t``), which is the window's own pages and one
    page of slack. The cache manager's claim, the programs' ring arithmetic
    and the engine's counts all read it here."""
    return -(-window // page_size) + 1


def window_decode_span(positions, window: int, page_size: int, ring: int):
    """Where a window layer's decode step reads in a sequence's ring. A
    sequence uses its ``ring`` window pages as a ring: the page of position
    ``p`` is ``window_tables[b, (p // page_size) % ring]``, so a page is
    written over once the window has left it. For a step whose token is at
    ``positions[b]`` (that many positions cached): ``first_page``, the ring
    column of the oldest page the window can reach (the walk goes
    ``(first_page + i) % ring``), and, counted from that page's first
    position, the prefix length and the first position inside the window.
    Returns (first_page [B], prefix_lens [B], starts [B]); whole-number
    arithmetic, on traced arrays in the op or numpy ones where the engine
    counts."""
    xp = jnp if isinstance(positions, jax.Array) else np
    oldest = xp.maximum(positions // page_size - (ring - 1), 0)
    first = oldest * page_size
    starts = xp.maximum(positions - (window - 1) - first, 0)
    return (
        (oldest % ring).astype(xp.int32), (positions - first).astype(xp.int32),
        starts.astype(xp.int32),
    )


def window_decode_view(window_tables, positions, window: int, page_size: int):
    """A window layer's page table as the chunked loop walks it: the
    table's columns rolled so that column 0 is the oldest page the window
    can reach (``window_decode_span``), with the prefix length and the first
    position inside the window on that footing. Returns (tables [B, ring],
    prefix_lens [B], starts [B])."""
    xp = jnp if isinstance(positions, jax.Array) else np
    ring = window_tables.shape[1]
    first_page, prefix_lens, starts = window_decode_span(positions, window, page_size, ring)
    cols = (first_page[:, None] + xp.arange(ring)[None, :]) % ring
    return xp.take_along_axis(window_tables, cols, axis=1), prefix_lens, starts


def ragged_shapes_ok(head_dim: int, page_size: int) -> bool:
    """Mosaic legality for the ragged decode kernels on TPU: pages must be
    whole (16, 128) bf16 tiles for the HBM→VMEM DMAs. Single source of
    truth shared by the kernel wrappers (hard error) and
    ``llama.paged_impl_plan`` (soft downgrade to the XLA gather)."""
    return head_dim % 128 == 0 and page_size % 16 == 0


#: the KV-head multiple the "flat" variant needs: it reads a page as
#: ``(ps*Hkv, D)`` rows, a view XLA makes of the ``[L, P, ps, Hkv, D]`` cache
#: in HBM, and that reshape is a bitcast (no copy of the cache) when a token's
#: ``(Hkv, D)`` slab is whole ``T(8, 128)`` tiles: 8 rows, for bf16 and for
#: int8 pages alike (compiled for the v5e, PR 35; until then the flatten was
#: done in VMEM and wanted 16 / 32). **At 4 heads** the view is free too, by
#: another road (``flat_view_is_free``; compiled for the v5e, PR 42): the
#: compiler lays a leaf whose second-minor dimension is 4 out in tiles of 4
#: rows, ``T(4, 128)(2, 1)`` for bf16, and two such tiles one after the other
#: are the bytes of one ``T(8, 128)(2, 1)`` tile of the flattened rows (two
#: tokens to a tile, row ``r`` K/V head ``r % 4``), so the reshape is a
#: bitcast again. 12 heads are neither: the compiler copies the leaf.
FLAT_VARIANT_HKV_MULTIPLE = 8


def flat_view_is_free(n_kv_heads: int) -> bool:
    """Whether the cache reaches the "flat" form as ``(ps*Hkv, D)`` rows
    without a copy (``FLAT_VARIANT_HKV_MULTIPLE``'s comment has the rule)."""
    return n_kv_heads == 4 or n_kv_heads % FLAT_VARIANT_HKV_MULTIPLE == 0


def ragged_variant_for(n_kv_heads: int) -> str:
    """Default kernel formulation: "flat" (one all-heads block-diagonal
    matmul over pages read as ``(ps*Hkv, D)`` rows) where a token's heads
    are whole tiles (``FLAT_VARIANT_HKV_MULTIPLE``): on the v5e at 8 KV heads
    of 128 it runs 1.9x faster than "grouped" (per-kv-head contractions),
    whose head slices of a token-major page are a relayout of every K/V
    element (PERF.md section 6, PR 35). Everything else (a head shard of 1,
    2 or 4 KV heads under tensor parallelism) takes "grouped". A model of 4
    K/V heads on one chip asks for "flat" itself
    (``smallthinker.paged_impl_plan``: the view is free there too and
    "grouped" loses to the XLA loop, PERF.md section 6, PRs 41 and 42); a
    4-head *shard* has not been measured and keeps what it had."""
    return "grouped" if n_kv_heads % FLAT_VARIANT_HKV_MULTIPLE else "flat"


def scatter_shapes_ok(head_dim: int) -> bool:
    """Mosaic legality for scatter_kv_pages' strided (Hkv, D) DMAs."""
    return head_dim % 128 == 0


def ragged_pages_read(prefix, page_size: int):
    """Pages of a slot the ragged kernel DMAs for a prefix of ``prefix``
    positions: the live ones, none for a dead slot. The kernel's loop bounds
    and the engine's count of what a decode step reads
    (``mtpu_decode_kv_positions_total``) both come from here; whole-number
    arithmetic on a traced scalar, a Python int or a numpy array."""
    return (prefix + page_size - 1) // page_size


#: the ragged kernel's sizes, tuned once on the v5e at Mistral-7B's cache
#: shapes (16 slots, 8 KV heads of 128, pages of 16, bf16; PERF.md section 6,
#: PR 35, ``benchmarks/ragged_micro.py``). The ring: K and V, two halves of
#: ``chunk`` pages each; 2 MiB is 16 pages a half there (a half in flight
#: behind the one computed: 32 pages ran no faster, 8 pages 6% slower).
_RING_BYTES = 2 * 1024 * 1024
#: logit columns of one softmax update of the flat form (pages x ps x Hkv:
#: 16 pages = 256 positions at 8 KV heads; 128 positions ran 8% slower,
#: 64 positions 33% slower) and positions of one of the grouped form
_FLAT_UPDATE_COLUMNS = 2048
_GROUPED_UPDATE_POSITIONS = 128


def ragged_kernel_sizes(
    variant: str, page_size: int, n_kv_heads: int, head_dim: int,
    itemsize: int, pages_per_seq: int,
) -> tuple[int, int]:
    """``(chunk, update)``: pages one half of the DMA ring holds, and pages
    one online-softmax update covers (``update`` divides ``chunk``)."""
    if variant == "flat":
        update = _FLAT_UPDATE_COLUMNS // (page_size * n_kv_heads)
    else:
        update = _GROUPED_UPDATE_POSITIONS // page_size
    update = max(1, min(update, pages_per_seq))
    page_bytes = page_size * n_kv_heads * head_dim * itemsize
    updates = max(1, _RING_BYTES // (4 * page_bytes * update))
    return update * min(updates, -(-pages_per_seq // update)), update


def _inflight_epilogue(
    q, k_new_ref, v_new_ref, b, o_ref, acc_scr, m_prev, l_prev, group,
    sm_scale,
):
    """Fold the current token's K/V (still in
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


def _decode_kernel_ragged(
    # scalar prefetch
    layer_ref,  # (1,) int32, SMEM — which layer of the [L, P, ...] cache
    page_tables_ref,  # (B * pages_per_seq,) int32, SMEM
    prefix_lens_ref,  # (B,) int32, SMEM — tokens already IN the cache; ``ring``:
    #   (3 * B,), then each slot's first table column and first seen position
    # inputs — FULL arrays as single constant-index blocks: Mosaic skips the
    # re-fetch when a block's index map is unchanged between grid steps, so
    # q/k_new/v_new stream into VMEM once per pallas_call instead of paying
    # 4 small block DMAs per program (measured ~18 us/program of pure
    # overhead at 7B shapes with per-program (1, H, D) blocks)
    q_ref,  # (B, Hq, D) VMEM
    k_new_ref,  # (B, Hkv, D) VMEM — current token's K (not yet written)
    v_new_ref,  # (B, Hkv, D) VMEM
    k_hbm,  # ANY/HBM: flat (L, n_pages, page_size*Hkv, D), grouped
    v_hbm,  #          (L, n_pages, page_size, Hkv, D)
    # quantized=True adds ks_ref/vs_ref: this sequence's scale rows, one
    # row per softmax update (see _gathered_scale_rows)
    *rest,  # [ks_ref, vs_ref,] o_ref, k_scr, v_scr, acc_scr, sems, state
    variant: str,
    page_size: int,
    pages_per_seq: int,
    n_kv_heads: int,
    group: int,  # Hq // Hkv
    sm_scale: float,
    chunk: int,  # pages one half of the ring holds
    update: int,  # pages one softmax update covers; divides chunk
    quantized: bool = False,
    ring: bool = False,  # the table is a ring walked from a slot's first column
    products: bool = True,  # False: the fetch alone (benchmarks/ragged_micro.py)
):
    """Ragged decode attention: one grid step a sequence, its live pages
    DMAed once from the [L, P, ...] cache, the in-flight token folded in.

    The pages stay READ-ONLY (the fast decode structure: one scatter per
    step, after the layer scan): the current token's K/V, still in
    registers, joins the online softmax as one extra logit column, exactly
    like ``paged_decode_attention_inflight`` does in XLA, and the layer
    scan never slices (= copies) a per-layer cache view: the layer is a
    prefetched scalar.

    *The fetch.* A ring of two halves of ``chunk`` pages for K and for V.
    A sequence's pages are fetched a chunk at a time, ``ceil(prefix /
    page_size)`` of them in all (the last chunk only as far as it is live),
    one DMA a page, all of a half's K (V) pages on ONE semaphore; a full
    chunk is awaited by one wait sized to the half, a partial one page by
    page. While a chunk is computed the next is in flight, and the next of a
    sequence's LAST chunk is the first chunk of the next live sequence: the
    ring and ``state`` (which half holds it) persist across grid steps, so
    no sequence but the first starts with nothing in flight (the cold start
    was 15% of the call at 4-5 chunks a sequence; PERF.md section 6, PR 35).
    Rows of a half past a partial chunk hold an earlier chunk's pages or the
    zeros written at the first grid step: finite, so a masked probability
    (exactly 0) times them is 0.

    *A ring* (``ring=True``, a sliding-window layer's table: the wrapper's
    ``first_pages`` / ``starts``). The same walk, begun at the slot's first
    column and wrapping at the table's end, ``(first + i) % pages_per_seq``;
    positions count from that page's first, and those below the slot's
    ``start`` (the head of the first page, which the window has left) are
    masked like those past the prefix. Both ride the prefix lengths' scalar
    array. Unset, neither is read and the kernel is what it was.

    *The products*, per update of ``update`` pages, same online softmax:
    - ``"flat"``: one block-diagonal all-heads matmul. A page is
      ``(ps*Hkv, D)`` rows (token-major), q's Hq rows meet all of them and
      a static mask keeps row r's own head (column c % Hkv == r // group).
      The off-head logits are the price of operands that need no relayout:
      Hkv x more exps than exist, on a VPU that has the time.
    - ``"grouped"``: Hkv unrolled (G, D) x (D, update*ps) matmuls, only
      real logits; each head's slice of the token-major pages is a strided
      relayout of the whole chunk, which is what bounds it.

    With ``quantized=True`` the pages stream as int8 and the per-token-head
    scales arrive as lane-major rows (one f32 per logit column), so the
    dequant is a multiply on the scores and probabilities instead of on
    every page element — KV HBM traffic is halved, the online softmax math
    is unchanged.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, k_scr, v_scr, acc_scr, sems, state = rest
    else:
        o_ref, k_scr, v_scr, acc_scr, sems, state = rest
    b = pl.program_id(0)
    B = q_ref.shape[0]
    li = layer_ref[0]
    C, U, ps, pp = chunk, update, page_size, pages_per_seq
    Hkv, G = n_kv_heads, group
    prefix = prefix_lens_ref[b]
    n_pages = ragged_pages_read(prefix, ps)
    n_chunks = pl.cdiv(n_pages, C)

    def start_chunk(seq, i, half):
        """Start the DMAs of ``seq``'s live pages in its chunk ``i``."""
        live = jnp.minimum(C, ragged_pages_read(prefix_lens_ref[seq], ps) - i * C)

        def one(j, _):
            if ring:  # first column < pp and i * C + j < pp: one turn at most
                col = prefix_lens_ref[B + seq] + i * C + j
                page = page_tables_ref[seq * pp + jnp.where(col >= pp, col - pp, col)]
            else:
                page = page_tables_ref[seq * pp + i * C + j]
            pltpu.make_async_copy(
                k_hbm.at[li, page], k_scr.at[half * C + j], sems.at[half, 0]
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[li, page], v_scr.at[half * C + j], sems.at[half, 1]
            ).start()
            return 0

        jax.lax.fori_loop(0, live, one, 0)

    def wait_chunk(live, half):
        # a wait takes its byte count from the descriptor, not its address
        @pl.when(live == C)
        def _():
            for scr, hbm, kv in ((k_scr, k_hbm, 0), (v_scr, v_hbm, 1)):
                pltpu.make_async_copy(
                    hbm.at[li, pl.ds(0, C)], scr.at[pl.ds(half * C, C)],
                    sems.at[half, kv],
                ).wait()

        @pl.when(live < C)
        def _():
            def one(j, _):
                for scr, hbm, kv in ((k_scr, k_hbm, 0), (v_scr, v_hbm, 1)):
                    pltpu.make_async_copy(
                        hbm.at[li, 0], scr.at[half * C + j], sems.at[half, kv]
                    ).wait()
                return 0

            jax.lax.fori_loop(0, live, one, 0)

    @pl.when(b == 0)
    def _():
        state[0] = 0  # the half that holds the next live sequence's chunk 0
        state[1] = 0  # whether an earlier grid step started that chunk
        v_scr[...] = jnp.zeros_like(v_scr)

    fetched = state[1] == 1
    half0 = jnp.where(fetched, state[0], 0)
    # the next live sequence, B if there is none
    nxt_seq = jax.lax.while_loop(
        lambda j: jnp.logical_and(
            j < B, prefix_lens_ref[jnp.minimum(j, B - 1)] == 0
        ),
        lambda j: j + 1,
        b + 1,
    )

    @pl.when(jnp.logical_and(n_chunks > 0, jnp.logical_not(fetched)))
    def _():
        start_chunk(b, 0, 0)

    acc_scr[:] = jnp.zeros_like(acc_scr)
    q = q_ref[b]  # (Hq, D) — stays in model dtype INTO the MXU (native
    # mixed-precision, f32 accumulate); sm_scale is applied to the f32
    # scores. Explicit astype(f32) on the page operands forces a Mosaic
    # retile of every page.
    Hq, D = q.shape
    if variant == "flat":
        R = ps * Hkv  # a page's rows: row = (token, head)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Hq, U * R), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (Hq, U * R), 0)
        own_head = (rows // G) == (cols % Hkv)
        col_tok = cols // Hkv
    else:
        W = U * ps  # an update's columns: (page, token in page)
        col_tok = jax.lax.broadcasted_iota(jnp.int32, (Hq, W), 1)

    def operand(scr, page0, h=None):
        """An update's keys/values at scratch page ``page0`` (one head's for
        the grouped form). int8 values are exact at the query's dtype; their
        scales multiply the scores and probabilities instead."""
        if h is None:
            x = scr[pl.ds(page0, U)]
        else:
            x = scr[pl.ds(page0, U), :, h, :]
        if quantized:
            x = x.astype(q.dtype)
        return x.reshape(-1, D)

    def scale_rows(scale_ref, u):
        """(1 or Hq, columns) scales of the sequence's update ``u``."""
        if variant == "flat":
            return scale_ref[0, pl.ds(u, 1), :]
        return jnp.concatenate(
            [
                jnp.broadcast_to(scale_ref[0, h, pl.ds(u, 1), :], (G, W))
                for h in range(Hkv)
            ],
            axis=0,
        )

    def softmax_update(carry, page0, u):
        m_prev, l_prev = carry  # (Hq, 1) each
        nt = (((1,), (1,)), ((), ()))
        nn = (((1,), (0,)), ((), ()))
        if variant == "flat":
            s = jax.lax.dot_general(
                q, operand(k_scr, page0), nt,
                preferred_element_type=jnp.float32,
            )  # (Hq, U * R)
        else:
            s = jnp.concatenate(
                [
                    jax.lax.dot_general(
                        q[h * G : (h + 1) * G], operand(k_scr, page0, h), nt,
                        preferred_element_type=jnp.float32,
                    )
                    for h in range(Hkv)
                ],
                axis=0,
            )  # (Hq, W)
        s = s * sm_scale
        if quantized:
            s = s * scale_rows(ks_ref, u)
        pos = u * (U * ps) + col_tok
        valid = pos < prefix
        if ring:
            valid = valid & (pos >= prefix_lens_ref[2 * B + b])
        if variant == "flat":
            valid = own_head & valid
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
            p = p * scale_rows(vs_ref, u)
        # flash-attention numerics: f32 softmax, cache-dtype PV operands
        if variant == "flat":
            v = operand(v_scr, page0)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, nn, preferred_element_type=jnp.float32
            )
        else:
            parts = []
            for h in range(Hkv):
                v = operand(v_scr, page0, h)
                parts.append(
                    jax.lax.dot_general(
                        p[h * G : (h + 1) * G].astype(v.dtype), v, nn,
                        preferred_element_type=jnp.float32,
                    )
                )
            pv = jnp.concatenate(parts, axis=0)
        acc_scr[:] = acc_scr[:] * alpha + pv
        return m_new, l_new

    def chunk_body(i, carry):
        half = jax.lax.rem(half0 + i, 2)
        more = i + 1 < n_chunks

        # into the half computed last: this sequence's next chunk, or after
        # its last the next live sequence's first
        @pl.when(jnp.logical_or(more, nxt_seq < B))
        def _():
            start_chunk(
                jnp.where(more, b, jnp.minimum(nxt_seq, B - 1)),
                jnp.where(more, i + 1, 0),
                1 - half,
            )

        live = jnp.minimum(C, n_pages - i * C)
        wait_chunk(live, half)
        if not products:
            return carry
        return jax.lax.fori_loop(
            0, pl.cdiv(live, U),
            lambda k, carry: softmax_update(
                carry, half * C + k * U, i * (C // U) + k
            ),
            carry,
        )

    init = (
        jnp.full((Hq, 1), -jnp.inf, jnp.float32),
        jnp.zeros((Hq, 1), jnp.float32),
    )
    m_prev, l_prev = jax.lax.fori_loop(0, n_chunks, chunk_body, init)

    @pl.when(n_chunks > 0)
    def _():
        state[0] = jax.lax.rem(half0 + n_chunks, 2)
        state[1] = (nxt_seq < B).astype(jnp.int32)

    _inflight_epilogue(
        q, k_new_ref, v_new_ref, b, o_ref, acc_scr, m_prev, l_prev, group,
        sm_scale,
    )


def _gathered_scale_rows(scale, layer, page_tables, variant, update):
    """Each sequence's int8-KV scales as lane-major rows, one row per
    softmax update of the kernel (``update`` pages), gathered by XLA
    outside it.

    The cache keeps scales as ``[L, P, page_size, Hkv]`` f32. Mosaic cannot
    DMA a page's ``(page_size, Hkv)`` slab out of that (an HBM slice must
    cover whole 128-lane tiles of the minor dim, and Hkv is 8..32), and the
    kernels want the scales along the LOGIT axis anyway — one f32 per score
    column — so they arrive as an ordinary VMEM-blocked input:

    - ``flat``: ``[B, n_updates, update*page_size*Hkv]`` — row u is update
      u's scales in the kernel's column order c = (page, tok, head);
    - ``grouped``: ``[B, Hkv, n_updates, update*page_size]`` — row (h, u)
      is kv head h's scales for update u's columns (page, tok).

    Unlike the pages this reads all ``pages_per_seq`` rows whatever the
    context; scales are 1/32 of the int8 page bytes at D=128."""
    B, pp = page_tables.shape
    ps, Hkv = scale.shape[2:]
    rows = scale[layer, page_tables]  # [B, pp, ps, Hkv]
    n_updates = -(-pp // update)
    rows = jnp.pad(rows, ((0, 0), (0, n_updates * update - pp), (0, 0), (0, 0)))
    if variant == "flat":
        return rows.reshape(B, n_updates, update * ps * Hkv)
    return rows.transpose(0, 3, 1, 2).reshape(B, Hkv, n_updates, update * ps)


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
    variant: str | None = None,  # None: ragged_variant_for(Hkv)
    chunk_pages: int | None = None,  # None: ragged_kernel_sizes (to A/B)
    update_pages: int | None = None,
    first_pages: jax.Array | None = None,  # [B] int32: a ring's first column
    starts: jax.Array | None = None,  # [B] int32: ... and first position seen
    products: bool = True,  # False: the fetch alone, to time it
) -> jax.Array:  # [B, Hq, D]
    """Pallas ragged decode attention over prefix pages + the in-flight
    token. Drop-in exact match for ``paged_decode_attention_inflight``
    given ``ks = k_pages[layer, page_tables]``; reads ``ragged_pages_read``
    pages a sequence, nothing for a dead slot.

    Two formulations of the products share the fetch and the online softmax
    (`_decode_kernel_ragged`): ``"flat"`` (one block-diagonal all-heads
    matmul; on the chip where ``flat_view_is_free``: 4 K/V heads or a
    multiple of 8) and ``"grouped"`` (Hkv per-kv-head matmuls, any Hkv).
    ``variant=`` / ``chunk_pages=`` / ``update_pages=`` override what
    ``ragged_variant_for`` and ``ragged_kernel_sizes`` pick, to A/B
    (``benchmarks/ragged_micro.py``). The flat form pads the query heads to
    whole 8-row tiles (28 heads run as 32 rows; a padded row has no K/V head
    of its own and comes out 0).

    ``first_pages`` and ``starts`` (both or neither) make each row of
    ``page_tables`` a ring (``window_decode_span``): slot b's walk starts at
    column ``first_pages[b]`` and wraps, ``prefix_lens`` counts from that
    page's first position, and positions below ``starts[b]`` are masked.

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
    if variant is None:
        variant = ragged_variant_for(Hkv)
    if variant not in ("flat", "grouped"):
        raise ValueError(f"unknown variant {variant!r}: flat | grouped")
    if not interpret and not ragged_shapes_ok(D, page_size):
        # fail with the constraint instead of an opaque Mosaic lowering
        # error: pages must be whole (16, 128) bf16 / (32, 128) int8 tiles
        raise ValueError(
            f"paged_decode_attention_ragged needs head_dim%128==0 and "
            f"page_size%16==0 on TPU; got D={D}, page_size={page_size}"
        )
    if not interpret and variant == "flat" and not flat_view_is_free(Hkv):
        raise ValueError(
            f"variant='flat' needs n_kv_heads == 4 or n_kv_heads%"
            f"{FLAT_VARIANT_HKV_MULTIPLE}==0 on TPU (a page read as "
            f"(ps*Hkv, D) rows without a copy of the cache); got Hkv={Hkv} "
            "— use variant='grouped' (the default for this shape)"
        )
    ring = first_pages is not None
    if ring != (starts is not None):
        raise ValueError("a ring takes first_pages= and starts= together")
    if ring and quantized:
        raise NotImplementedError("a ring of int8 pages")

    # int8 caches compute at (and fold the in-flight token at) the query's
    # dtype; plain caches keep their own dtype into the MXU (no retile)
    compute_dtype = q.dtype if quantized else k_pages.dtype
    k_data, v_data = (
        (k_pages.data, v_pages.data) if quantized else (k_pages, v_pages)
    )
    chunk, update = ragged_kernel_sizes(
        variant, page_size, Hkv, D, k_data.dtype.itemsize, pages_per_seq
    )
    if update_pages is not None:
        update = update_pages
        chunk = max(update, chunk // update * update)
    if chunk_pages is not None:
        chunk = chunk_pages
    else:  # the wait of a whole half is described over that many pages
        chunk = max(update, min(chunk, n_pages // update * update))
    if chunk % update or chunk > n_pages:
        raise ValueError(
            f"chunk_pages={chunk} must be a multiple of update_pages="
            f"{update} and at most the cache's {n_pages} pages"
        )
    page_shape = (page_size, Hkv, D)
    rows = Hq  # q's rows in the kernel
    if variant == "flat":
        # a bitcast in HBM (FLAT_VARIANT_HKV_MULTIPLE), so the kernel's
        # operands need no relayout in VMEM
        page_shape = (page_size * Hkv, D)
        k_data = k_data.reshape(L, n_pages, *page_shape)
        v_data = v_data.reshape(L, n_pages, *page_shape)
        rows = -(-Hq // 8) * 8
        if rows != Hq:
            q = jnp.pad(q, ((0, 0), (0, rows - Hq), (0, 0)))
    lens = prefix_lens.astype(jnp.int32)
    if ring:
        lens = jnp.concatenate(
            [lens, first_pages.astype(jnp.int32), starts.astype(jnp.int32)]
        )

    def _const3(shape):
        return pl.BlockSpec(
            shape, lambda b, *_refs: (0, 0, 0), memory_space=pltpu.VMEM
        )

    # full arrays, constant index maps: fetched into VMEM once per call,
    # not once per program (see _decode_kernel_ragged)
    in_specs = [
        _const3((B, rows, D)),
        _const3((B, Hkv, D)),
        _const3((B, Hkv, D)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        q,
        k_new.astype(compute_dtype),
        v_new.astype(compute_dtype),
        k_data,
        v_data,
    ]
    if quantized:
        scale_rows = [
            _gathered_scale_rows(
                pages.scale, layer, page_tables, variant, update
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
        operands += scale_rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (B, rows, D), lambda b, *_refs: (0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2 * chunk, *page_shape), k_data.dtype),
            pltpu.VMEM((2 * chunk, *page_shape), v_data.dtype),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_ragged,
        variant=variant,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        n_kv_heads=Hkv,
        group=G,
        sm_scale=sm_scale,
        chunk=chunk,
        update=update,
        quantized=quantized,
        ring=ring,
        products=products,
    )
    scale_bytes = 4 * page_size * Hkv if quantized else 0
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the ring and its state carry from one grid step to the next
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * Hq * pages_per_seq * page_size * D),
            bytes_accessed=int(
                2 * B * pages_per_seq
                * (Hkv * page_size * D * k_data.dtype.itemsize + scale_bytes)
            ),
            transcendentals=int(B * Hq * pages_per_seq * page_size),
        ),
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        page_tables.reshape(-1).astype(jnp.int32),
        lens,
        *operands,
    )
    return out if rows == Hq else out[:, :Hq]


@jax.named_scope(WINDOW_ATTENTION)
def paged_window_decode_attention_ragged(
    q: jax.Array,  # [B, Hq, D]
    k_pages: jax.Array,  # [Lw, n_window_pages, page_size, Hkv, D]: the window group's pages
    v_pages: jax.Array,
    layer: jax.Array,  # scalar int32: the layer's row in the group
    window_tables: jax.Array,  # [B, ring] int32: each slot's ring of pages
    positions: jax.Array,  # [B] int32: the token's position (0 for a dead slot)
    k_new: jax.Array,  # [B, Hkv, D]
    v_new: jax.Array,
    *,
    window: int,
    sm_scale: float | None = None,
    **kernel,  # paged_decode_attention_ragged's (variant=, interpret=, ...)
) -> jax.Array:  # [B, Hq, D]
    """``paged_window_decode_attention_chunked``'s contract through the
    ragged kernel: a slot's ring read in place from the first page its
    window reaches (``window_decode_span``), that page's head masked by
    ``starts``; no rolled table, no gathered copy."""
    first_pages, prefix_lens, starts = window_decode_span(
        positions, window, k_pages.shape[2], window_tables.shape[1]
    )
    return paged_decode_attention_ragged(
        q, k_pages, v_pages, layer, window_tables, prefix_lens, k_new, v_new,
        sm_scale=sm_scale, first_pages=first_pages, starts=starts, **kernel,
    )


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
