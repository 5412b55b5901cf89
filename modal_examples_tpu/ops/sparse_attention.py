"""Learned sparse attention: a lightning indexer's scores, an exact top-k
selection, and attention over the selected positions only
(docs/sparse_attention.md; GLM-5.2's ``glm_moe_dsa``, after DeepSeek's
sparse-attention indexer).

For a query ``t`` and a cached position ``s <= t`` the indexer gives

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        j = 1..index heads

and the query attends to ``S_t``: the ``k`` positions of largest ``I[t, s]``
(all of them while ``t < k``; ties go to the lowest position). What the
model's attention then computes is its softmax over ``S_t`` and nothing else:
no window, no approximate top-k.

Two forms serve it (``benchmarks/sparse_micro.py`` measures each against the
other form on the chip; PERF.md section 6, PR 34 has the readings):

- **prefill, masked-dense**: the scores of a chunk's queries against every
  cached key, blocked over keys (:func:`index_scores`; never the
  ``[heads, C, S]`` tensor whole); the selection as a mask from the k-th
  largest score of each row, found by bisection on the scores' bits
  (:func:`select_mask`: 32 counting passes, no sort); and the flash kernel of
  :func:`selected_attention`, which takes the mask as an input, skips the
  tiles it leaves empty, and needs no offset of its own: causality is in the
  mask. Keys and values reach it in blocks of :func:`key_block` positions, as
  the blocked expansion of a long latent prefix leaves them. (A gathered
  form moves ``k`` latents a *query*: 4.8 GB a layer and chunk at 2048 x
  2048 x 576 bf16; in XLA it ran 4x slower.)
- **decode, gathered**: scores over the slot's live pages of the indexer's
  leaf (:func:`paged_index_scores`), ``lax.top_k`` (:func:`select_positions`)
  and the absorbed attention over the ``k`` gathered latents alone
  (:func:`paged_latent_decode_attention_selected`): a step reads ``k``
  latents a sequence, whatever its context. (The chunked loop over every
  live page under a keep mask took as long at 12k positions and grows with
  the context.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _MASK, _use_interpret
from .scopes import ATTENTION, INDEXER, PAGE_GATHER, TOPK_SELECT

#: cached positions a trip of the index-score loops takes
_KEY_BLOCK = 1024


# -- index scores ------------------------------------------------------------------


def _block_scores(q_idx, w, k_block):
    """[B, C, Hi, Di], [B, C, Hi] f32, [B, K, Di] -> [B, C, K] f32."""
    s = jnp.einsum("bchd,bkd->bchk", q_idx, k_block, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)


@jax.named_scope(INDEXER)
def index_scores(
    q_idx: jax.Array,  # [B, C, Hi, Di] — the queries' index heads
    w: jax.Array,  # [B, C, Hi] f32 — the heads' weights, scales folded in
    k_idx: jax.Array,  # [B, S, Di] — the indexer's keys of every position
    *,
    block_k: int = _KEY_BLOCK,
) -> jax.Array:  # [B, C, S] f32
    """``I[t, s]`` of a block of queries against ``S`` keys, a block of keys
    at a time: what is alive at once is ``[B, Hi, C, block_k]``."""
    B, S, Di = k_idx.shape
    if S <= block_k or S % block_k:
        return _block_scores(q_idx, w, k_idx)
    blocks = k_idx.reshape(B, S // block_k, block_k, Di).transpose(1, 0, 2, 3)
    out = jax.lax.map(lambda kb: _block_scores(q_idx, w, kb), blocks)  # [n, B, C, K]
    return out.transpose(1, 2, 0, 3).reshape(B, q_idx.shape[1], S)


@jax.named_scope(INDEXER)
def paged_index_scores(
    q_idx: jax.Array,  # [B, Hi, Di] — one query a slot
    w: jax.Array,  # [B, Hi] f32
    idx_pages: jax.Array,  # [Lf, n_pages, page_size, 1, Di] — in place
    layer: jax.Array,  # scalar int32: which of the leaf's layers
    page_tables: jax.Array,  # [B, pages_per_seq]
    prefix_lens: jax.Array,  # [B] — tokens already in the cache
    k_new: jax.Array,  # [B, Di] — the current token's key (not yet written)
) -> jax.Array:  # [B, pages_per_seq * page_size] f32; -inf where s > t
    """Decode's index scores over the live pages: a loop over the table,
    ``_KEY_BLOCK`` positions a trip, as far as the longest live context."""
    B, Hi, Di = q_idx.shape
    page_size = idx_pages.shape[2]
    pages_per_seq = page_tables.shape[1]
    W = max(1, min(pages_per_seq, _KEY_BLOCK // page_size))
    if pages_per_seq % W:
        page_tables = jnp.pad(page_tables, ((0, 0), (0, -pages_per_seq % W)))
    n_pos = page_tables.shape[1] * page_size
    span = W * page_size
    trips = jnp.minimum(
        (jnp.max(prefix_lens) + span - 1) // span, page_tables.shape[1] // W
    )
    q_idx = q_idx.astype(idx_pages.dtype)

    def trip(c, scores):
        cols = jax.lax.dynamic_slice_in_dim(page_tables, c * W, W, axis=1)
        ks = idx_pages[layer, cols].reshape(B, span, Di)  # the leaf's gather: the indexer's
        s = _block_scores(q_idx[:, None], w[:, None], ks)[:, 0]  # [B, span]
        return jax.lax.dynamic_update_slice_in_dim(scores, s, c * span, axis=1)

    scores = jax.lax.fori_loop(0, trips, trip, jnp.zeros((B, n_pos), jnp.float32))
    own = _block_scores(
        q_idx[:, None], w[:, None], k_new.astype(idx_pages.dtype)[:, None]
    )[:, 0, 0]  # [B]
    pos = jnp.arange(n_pos)[None, :]
    scores = jnp.where(pos == prefix_lens[:, None], own[:, None], scores)
    return jnp.where(pos <= prefix_lens[:, None], scores, -jnp.inf)[
        :, : pages_per_seq * page_size
    ]


# -- the selection -------------------------------------------------------------------


def _ordered_bits(scores):
    """f32 -> uint32 that orders as the floats do."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))  # signed order
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


@jax.named_scope(TOPK_SELECT)
def select_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The ``k`` valid positions of largest score in each row of ``scores``
    [..., S], as a mask (every valid one where a row has at most ``k``); a
    tie at the k-th score goes to the lowest positions. Exact, and no sort:
    the k-th largest value's bits are found one at a time, each by counting
    the row's entries at or above a candidate (32 passes over the scores),
    then the ties below the count are taken in position order."""
    S = scores.shape[-1]
    if S <= k:
        return valid
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))  # 0: below every score

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = u > kth
    tied = u == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return valid & (above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room)))


@jax.named_scope(TOPK_SELECT)
def select_positions(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``scores`` [B, S] with -inf where a position may not be attended to
    -> (positions [B, k] int32, which of them count [B, k] bool): the same
    set :func:`select_mask` gives (``lax.top_k`` puts the lower index first
    among equals)."""
    S = scores.shape[-1]
    if S < k:
        scores = jnp.pad(scores, ((0, 0), (0, k - S)), constant_values=-jnp.inf)
    top, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), top > -jnp.inf


# -- prefill: the flash kernel under a selection mask ---------------------------------


def _selected_kernel(live_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
                     *, sm_scale: float, heads: int):
    b = pl.program_id(0) // heads
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(live_ref[b, qi, ki] > 0)  # a tile with nothing selected takes no step
    def _step():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        # a finite mask value: a row with nothing selected yet keeps m at it,
        # sums garbage, and its first selected score wipes that (alpha = 0)
        s = jnp.where(mask_ref[0] != 0, s, _MASK)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def key_block(n_keys: int) -> int:
    """Keys a tile of :func:`selected_attention` takes, of ``n_keys``: up to
    1024. The caller lays its keys and values out in blocks of this many
    (and pads them to a multiple: a padded key is in no selection)."""
    return _tile(n_keys)


def _tile(n: int) -> int:
    for t in (1024, 512, 256, 128):
        if n % t == 0:
            return t
    return n


@jax.named_scope(ATTENTION)
def selected_attention(
    q: jax.Array,  # [B, H, C, D]
    k: jax.Array,  # [B, S / block, H, block, D] — blocks of ``key_block(S)`` positions
    v: jax.Array,  # [B, S / block, H, block, Dv]
    mask: jax.Array,  # [B, C, S] bool — what each query attends to, all heads alike
    *,
    sm_scale: float,
    impl: str = "flash",
) -> jax.Array:  # [B, H, C, Dv]
    """Softmax attention of each query over the keys its ``mask`` row names
    (every row names at least one). Keys and values come in blocks of
    positions, as the blocked expansion of a long prefix leaves them.
    ``impl="flash"``: the Pallas kernel, tiles of up to 1024 x 1024, the mask
    one more tiled input, a tile the mask leaves empty skipped; anything else
    the plain XLA form."""
    B, H, C, D = q.shape
    nk, block_k, Dv = k.shape[1], k.shape[3], v.shape[4]
    S = nk * block_k
    if impl != "flash":
        k, v = (a.transpose(0, 2, 1, 3, 4).reshape(B, H, S, -1) for a in (k, v))
        s = jnp.einsum("bhcd,bhsd->bhcs", q, k, preferred_element_type=jnp.float32) * sm_scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhcs,bhsd->bhcd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)
    block_q = _tile(C)
    nq = C // block_q
    live = mask.reshape(B, nq, block_q, nk, block_k).any(axis=(2, 4)).astype(jnp.int32)
    kernel = functools.partial(_selected_kernel, sm_scale=sm_scale, heads=H)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda bh, qi, ki, live: (bh, qi, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda bh, qi, ki, live: (ki, bh, 0, 0)),
                pl.BlockSpec((1, 1, block_k, Dv), lambda bh, qi, ki, live: (ki, bh, 0, 0)),
                pl.BlockSpec((1, block_q, block_k), lambda bh, qi, ki, live: (bh // H, qi, ki)),
            ],
            out_specs=pl.BlockSpec((1, block_q, Dv), lambda bh, qi, ki, live: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, C, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20,
        ),
        interpret=_use_interpret(),
    )(
        # the key blocks outermost: [S / block, B * H, block, .]
        live, q.reshape(B * H, C, D),
        k.transpose(1, 0, 2, 3, 4).reshape(nk, B * H, block_k, D),
        v.transpose(1, 0, 2, 3, 4).reshape(nk, B * H, block_k, Dv),
        mask.astype(jnp.int8),
    )
    return o.reshape(B, H, C, Dv)


# -- decode: the absorbed attention over the selected latents ------------------------


@jax.named_scope(ATTENTION)
def paged_latent_decode_attention_selected(
    q_lat: jax.Array,  # [B, Hq, C] — queries absorbed into the latent space
    q_pe: jax.Array,  # [B, Hq, R]
    c_pages: jax.Array,  # [L, n_pages, page_size, 1, C] — in place
    r_pages: jax.Array,  # [L, n_pages, page_size, 1, R] — in place
    layer: jax.Array,  # scalar int32: which layer of both leaves
    page_tables: jax.Array,  # [B, pages_per_seq]
    selected: jax.Array,  # [B, k] int32 — positions, the current one maybe among them
    counts: jax.Array,  # [B, k] bool — which of them count
    positions: jax.Array,  # [B] — the current token's position
    c_new: jax.Array,  # [B, C] — its latent (not yet written)
    r_new: jax.Array,  # [B, R]
    *,
    sm_scale: float,
) -> jax.Array:  # [B, Hq, C] f32
    """``paged_latent_decode_attention_chunked`` over the selected positions
    alone: their latents and rotated keys are gathered row by row (``k`` a
    sequence, whatever its context), the current token's taken from the
    arguments where it is among them."""
    page_size = c_pages.shape[2]
    kv_dtype = c_pages.dtype
    with jax.named_scope(PAGE_GATHER):
        # rows of the leaves laid flat: an index into [L, n_pages, page_size, 1, w]
        # itself makes the device lay the 64-wide leaf out anew, whole, a step
        page = jnp.take_along_axis(page_tables, selected // page_size, axis=1)
        row = (layer * c_pages.shape[1] + page) * page_size + selected % page_size
        cs = c_pages.reshape(-1, c_pages.shape[-1])[row]  # [B, k, C]
        rs = r_pages.reshape(-1, r_pages.shape[-1])[row]  # [B, k, R]
    own = (selected == positions[:, None])[..., None]
    cs = jnp.where(own, c_new.astype(kv_dtype)[:, None], cs)
    rs = jnp.where(own, r_new.astype(kv_dtype)[:, None], rs)
    s = (
        jnp.einsum("bhc,bkc->bhk", q_lat.astype(kv_dtype), cs,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhr,bkr->bhk", q_pe.astype(kv_dtype), rs,
                     preferred_element_type=jnp.float32)
    ) * sm_scale
    p = jax.nn.softmax(jnp.where(counts[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(kv_dtype), cs,
                      preferred_element_type=jnp.float32)
