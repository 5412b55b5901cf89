"""Blockwise flash attention for TPU (Pallas → Mosaic).

The training-side replacement for the reference's flash-attn CUDA wheel
(02_building_containers/install_flash_attn.py:19-33, learn_math.py:29-32) and
the SDPA inside its torch models (hp_sweep src/model.py:14-30).

Design (TPU-first, not a CUDA translation):
- grid = (batch*kv_heads*group, q_blocks, k_blocks); the LAST grid axis is
  sequential on TPU, so the online-softmax state (m, l, acc) lives in VMEM
  scratch carried across k-block steps — no atomics, no cross-block sync.
- the forward is bound by its grid steps, not its flops, so its tiles are
  the largest divisors of the lengths that fit a VMEM budget
  (``choose_blocks``: 1024 x 1024 at the serving shapes, a short bucket call
  one tile of its own length); the f32 scratch tiles align to (8, 128).
- both dots take their operands in the input's dtype (bf16 straight into
  the MXU) and accumulate in f32; the scale multiplies the f32 scores, p is
  cast to v's dtype for the second dot, m / l / acc stay f32. Float32 inputs
  keep float32 dots.
- causal masking: the k grid stops at the last key any query of the call
  sees, and a query tile's K/V index map stays on that tile's own last block,
  so the blocks above the diagonal are not fetched (a repeated block index
  is no new DMA) and their steps do nothing but count; the elementwise
  triangle mask is built only in the tiles the diagonal crosses, the ones
  wholly below it skip it.
- GQA folds the query-head group into the batch dimension; K/V blocks are
  indexed by kv head, so the group's consecutive grid rows read the same K/V.
- backward: dedicated Pallas kernels (dq with sequential k-blocks, dk/dv
  with sequential q-blocks) sharing per-block dS math, including the lse
  output's cotangent so ring/ulysses merges differentiate through the
  kernels; MTPU_FLASH_BWD=recompute switches to an XLA-recompute fallback.
  They keep 128 x 128 blocks and float32 dots.

Runs in interpreter mode off-TPU so CPU CI exercises the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import reference

_LANES = 128  # f32 scratch tile: (8, 128); m/l are broadcast across lanes

# Masked scores take a large finite value, not -inf: exp(mask - m) is 0 for
# any finite m and no -inf - -inf can arise, so the step needs no isfinite
# guards (under causal masking every query row sees key 0 in its first block).
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)

# ---------------------------------------------------------------------------
# Forward tiles. The kernel is bound by its grid steps (a 128 x 128 step took
# 0.58 us on a v5e for work the MXU does in 0.04), so a call wants the
# fewest, fattest steps that fit VMEM: choose_blocks() picks them from the
# call's own shapes.
# ---------------------------------------------------------------------------

#: what one grid step may hold, by block_footprint()'s count: room for a
#: 1024 x 1024 bf16 tile at the serving widths (18.5 MiB at D = Dv = 128, 19.0
#: at 192 / 128), the fastest of the nine tiles swept on a v5e (PERF.md §6,
#: PR 30; a key block of 512 rows costs 1.6-1.9x one of 1024). The call asks
#: Mosaic for _VMEM_LIMIT of scoped VMEM (a v5e core has 128 MiB; the default
#: of 16 refuses such a tile), which leaves the compiler's own temporaries
#: half as much again.
_VMEM_BUDGET = 20 * 2**20
_VMEM_LIMIT = 32 * 2**20


def block_footprint(block_q: int, block_k: int, D: int, Dv: int, itemsize: int) -> int:
    """VMEM bytes of one forward grid step: the q/k/v/o blocks and the lse
    block double-buffered by the pipeline, the m/l/acc scratch, and the score
    tile three times in f32 (s, p, the select) and once in the input dtype
    (p into the second dot)."""
    blocks = 2 * (block_q + block_k) * (D + Dv) * itemsize
    stats = (2 * _LANES + 2 * _LANES + Dv) * block_q * 4  # lse x2, m, l, acc
    tile = block_q * block_k * (3 * 4 + itemsize)
    return blocks + stats + tile


def _tiles(S: int) -> list[int]:
    """Block lengths a sequence of S may take, largest first: 128 * 2^i where
    that divides S; else (short or odd lengths, never a serving shape) the
    sublane-aligned divisors; else S whole."""
    tiles = [t for t in (2048, 1024, 512, 256, 128) if S % t == 0]
    if not tiles:
        tiles = [d for d in range(S, 7, -1) if S % d == 0 and d % 8 == 0]
    return tiles or [S]


#: the key block beyond which the kernel gains nothing (PERF.md section 6, PR 30)
_LONG_KEY_BLOCK = 1024


def padded_kv_len(Skv: int) -> int:
    """The key length ``flash_attention_chunked`` runs a causal call of
    ``Skv`` keys at: the next multiple of the long key block, once there is
    more than one such block of keys (a shorter call is one tile or two)."""
    if Skv <= _LONG_KEY_BLOCK:
        return Skv
    return -(-Skv // _LONG_KEY_BLOCK) * _LONG_KEY_BLOCK


def choose_blocks(
    Sq: int, Skv: int, D: int, Dv: int, itemsize: int,
    budget: int = _VMEM_BUDGET,
) -> tuple[int, int]:
    """(block_q, block_k) for a forward call: the divisors of Sq and Skv with
    the largest tile whose footprint fits ``budget``. Ties go to the squarer
    tile (the diagonal wastes least of it), then to the longer key block
    (fewer rescales of the accumulator); the smallest pair if none fits."""
    pairs = [(bq, bk) for bq in _tiles(Sq) for bk in _tiles(Skv)]
    fits = [
        p for p in pairs if block_footprint(*p, D, Dv, itemsize) <= budget
    ]
    if not fits:
        return pairs[-1]
    return max(fits, key=lambda p: (p[0] * p[1], min(p), p[1]))


def _window_first_block(q_start, window: int, block_k: int):
    """The first key block a query tile that starts at ``q_start`` reaches
    under a window of ``window`` positions (a query sees itself and the
    ``window - 1`` before it): whole-number arithmetic on a Python int (the
    grid's extent) or a traced scalar (the kernel, the index maps)."""
    first = q_start - (window - 1)
    first = max(first, 0) if isinstance(first, int) else jnp.maximum(first, 0)
    return first // block_k


def _fwd_kernel(
    q_ref,  # (1, block_q, D)
    k_ref,  # (1, block_k, D)
    v_ref,  # (1, block_k, Dv): values may be narrower than q/k
    o_ref,  # (1, block_q, Dv)
    lse_ref,  # (1, block_q, LANES) — row stats ride a 128-lane dim: Mosaic
    #           requires output tiles shaped (8k, 128m); a bare (1, block_q)
    #           block fails lowering (the official TPU flash kernel pads the
    #           same way)
    m_scr,  # (block_q, LANES) f32
    l_scr,  # (block_q, LANES) f32
    acc_scr,  # (block_q, Dv) f32
    *,
    sm_scale: float,
    causal: bool,
    q_offset: int = 0,
    window: int | None = None,
    # (1,) int32 in SMEM: keys below this index are no one's (a gathered
    # prefix's rows from before the sequence began)
    k_first_ref=None,
):
    has_k_first = k_first_ref is not None
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # q_offset shifts query GLOBAL positions (chunked prefill: this q chunk
    # starts at q_offset within the full sequence the K/V cover).
    q_start = qi * block_q + q_offset
    bounded = window is not None or has_k_first  # then always causal
    # under a window the k axis counts from the first block the tile reaches
    first_k = _window_first_block(q_start, window, block_k) if window is not None else 0
    k_start = (ki + first_k) * block_k if window is not None else ki * block_k

    def step(masked: bool):
        # operands as stored (bf16 straight into the MXU), sums in f32
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (block_q, block_k)
        if masked:
            # row - col >= k_start - q_start: the iota difference is the
            # same in every tile, only the scalar moves
            diff = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            ) - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = diff >= k_start - q_start
            if window is not None:  # row - col < window, in global positions
                keep = jnp.logical_and(keep, diff < window + k_start - q_start)
            if has_k_first:
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                keep = jnp.logical_and(keep, col >= k_first_ref[0] - k_start)
            s = jnp.where(keep, s, _MASK)
        m_prev = m_scr[:, :1]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if bounded:
        # as below, and the k axis starts late: trip ki is key block
        # first_k + ki, the trips past the diagonal's block are skipped. A
        # tile pays for the mask where the diagonal, the window's edge (its
        # first key is out of the last row's window) or k_first crosses it.
        # A row whose first tiles are all masked carries m = _MASK and sums
        # that the first real score's alpha = 0 wipes
        last_k = (q_start + block_q - 1) // block_k - first_k
        crossed = k_start + block_k - 1 > q_start
        if window is not None:
            crossed = jnp.logical_or(crossed, k_start <= q_start + block_q - 1 - window)
        if has_k_first:
            crossed = jnp.logical_or(crossed, k_start < k_first_ref[0])
        pl.when(jnp.logical_and(ki <= last_k, jnp.logical_not(crossed)))(
            functools.partial(step, False)
        )
        pl.when(jnp.logical_and(ki <= last_k, crossed))(
            functools.partial(step, True)
        )
    elif causal:
        # the last key block this query tile sees; the ones after it are
        # neither fetched (the index map stays on last_k) nor computed.
        # Only a tile the diagonal crosses pays for the mask.
        last_k = (q_start + block_q - 1) // block_k
        crossed = k_start + block_k - 1 > q_start
        pl.when(jnp.logical_and(ki <= last_k, jnp.logical_not(crossed)))(
            functools.partial(step, False)
        )
        pl.when(jnp.logical_and(ki <= last_k, crossed))(
            functools.partial(step, True)
        )
    else:
        last_k = pl.num_programs(2) - 1
        step(False)

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[:, :1]  # >= 1: the row's largest score adds exp(0)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(l), lse_ref.shape[1:]
        )


def _fwd_kernel_k_first(k_first_ref, *refs, **static):
    """``_fwd_kernel`` for a call whose first input is ``k_first``."""
    _fwd_kernel(*refs, k_first_ref=k_first_ref, **static)


def _flash_forward(
    q, k, v, *, causal: bool, sm_scale: float, interpret: bool,
    block_q: int | None = None, block_k: int | None = None, q_offset: int = 0,
    window: int | None = None, k_first=None,
):
    """``block_q`` / ``block_k`` None: chosen from the shapes. ``window``
    (static) and ``k_first`` (an int32 scalar, traced or not) are
    ``flash_attention_chunked``'s; a call with neither builds what it always
    built."""
    B, Hq, S, D = q.shape  # S = query length
    Hkv, Skv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]  # values may be narrower than q/k (latent attention)
    chosen_q, chosen_k = choose_blocks(S, Skv, D, Dv, q.dtype.itemsize)
    block_q = min(block_q or chosen_q, S)
    block_k = min(block_k or chosen_k, Skv)
    if S % block_q or Skv % block_k:
        raise ValueError(
            f"lengths (q={S}, kv={Skv}) must be multiples of block sizes "
            f"({block_q}, {block_k}); pad sequences at the model layer"
        )
    if Hq % Hkv:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hkv}")
    if causal and q_offset + S > Skv:
        raise ValueError(
            f"q_offset {q_offset} + q len {S} exceeds kv len {Skv}"
        )
    group = Hq // Hkv
    # fold (B, Hkv, group) into one leading grid axis; kv index drops `group`
    qf = q.reshape(B * Hkv * group, S, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, Dv)

    # causal: no query sees a key past q_offset + S, so the grid stops there,
    # and a query tile's k index stops at its own last block: a block index
    # that repeats is not fetched again
    kv_len = min(Skv, q_offset + S) if causal else Skv
    bounded = window is not None or k_first is not None
    if bounded and not causal:
        raise ValueError("a window or k_first needs a causal call")

    def k_blocks(qi: int) -> tuple[int, int]:
        """(first, last) key block of query tile ``qi``, as the kernel has them."""
        q_start = qi * block_q + q_offset
        first = _window_first_block(q_start, window, block_k) if window is not None else 0
        return first, (q_start + block_q - 1) // block_k

    if bounded:
        # the k axis holds the widest tile's blocks, first to diagonal
        n_k = max(last - first + 1 for first, last in map(k_blocks, range(S // block_q)))
    else:
        n_k = pl.cdiv(kv_len, block_k)
    grid = (B * Hkv * group, S // block_q, n_k)

    def kv_index(bh, qi, ki):
        if bounded:
            q_start = qi * block_q + q_offset
            if window is not None:
                ki = ki + _window_first_block(q_start, window, block_k)
            ki = jnp.minimum(ki, (q_start + block_q - 1) // block_k)
        elif causal:
            ki = jnp.minimum(ki, (qi * block_q + q_offset + block_q - 1) // block_k)
        return (bh // group, ki, 0)

    pairs = S * q_offset + S * (S + 1) // 2 if causal else S * Skv
    if bounded:
        if window is not None:
            pairs = sum(min(t + 1, window) for t in range(q_offset, q_offset + S))
        kernel = functools.partial(
            _fwd_kernel if k_first is None else _fwd_kernel_k_first,
            sm_scale=sm_scale, causal=True, q_offset=q_offset, window=window,
        )
    else:
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, q_offset=q_offset
        )
    scalars = () if k_first is None else (jnp.reshape(k_first, (1,)).astype(jnp.int32),)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars) + [
            pl.BlockSpec(
                (1, block_q, D), lambda bh, qi, ki: (bh, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((1, block_k, D), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, Dv), lambda bh, qi, ki: (bh, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_q, _LANES), lambda bh, qi, ki: (bh, qi, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv * group, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv * group, S, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Hq * pairs * (D + Dv),
            bytes_accessed=(
                qf.size + kf.size + vf.size + B * Hq * S * Dv
            ) * q.dtype.itemsize,
            transcendentals=B * Hq * pairs,
        ),
        interpret=interpret,
    )(*scalars, qf, kf, vf)
    return o.reshape(B, Hq, S, Dv), lse[:, :, 0].reshape(B, Hq, S)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Backward kernels. With S_scaled = scale*Q@K^T, P = exp(S_scaled - lse):
#   dV = P^T dO
#   dP = dO V^T
#   dS = P * (dP - D),  D_i = rowsum(dO_i * O_i)
#   dQ = scale * dS K          (accumulated over k blocks)
#   dK = scale * dS^T Q        (accumulated over q blocks)
# Two kernels: dq (grid bh, qi, ki — ki sequential into scratch) and dkv
# (grid bh, ki, qi — qi sequential into scratch). lse/delta ride along as
# per-row statistics; causal blocks above the diagonal are skipped.
# ---------------------------------------------------------------------------


def _bwd_block_ds(q, k, lse_row, delta_row, dlse_row, do, v, *, sm_scale,
                  causal, q_start, k_start):
    """Shared per-block math: returns (p, ds) both (block_q, block_k) f32.

    dS has two sources: the output path p*(dP - D), and the lse output's own
    cotangent (d lse/dS = p), so dS = p * (dP - D + dLSE) — the latter is
    what makes ring/ulysses merges (which consume lse) kernel-differentiable.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        s = jnp.where(rows >= cols, s, -jnp.inf)
    finite = jnp.isfinite(lse_row)
    p = jnp.where(finite, jnp.exp(s - jnp.where(finite, lse_row, 0.0)), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_row + dlse_row)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
               dq_ref, dq_scr, *, sm_scale, causal, block_q):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    block_k = k_ref.shape[1]
    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = jnp.logical_or(not causal, k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse_row = lse_ref[0][:, :1]
        delta_row = delta_ref[0][:, :1]
        dlse_row = dlse_ref[0][:, :1]
        _, ds = _bwd_block_ds(
            q, k, lse_row, delta_row, dlse_row, do, v, sm_scale=sm_scale,
            causal=causal, q_start=q_start, k_start=k_start,
        )
        dq_scr[:] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal, block_k):
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    block_q = q_ref.shape[1]
    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = jnp.logical_or(not causal, q_start + block_q - 1 >= k_start)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse_row = lse_ref[0][:, :1]
        delta_row = delta_ref[0][:, :1]
        dlse_row = dlse_ref[0][:, :1]
        p, ds = _bwd_block_ds(
            q, k, lse_row, delta_row, dlse_row, do, v, sm_scale=sm_scale,
            causal=causal, q_start=q_start, k_start=k_start,
        )
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, *, causal, sm_scale, block_q, block_k,
                    interpret, g_lse=None):
    """Pallas backward: returns (dq, dk, dv) with GQA group reduction.
    ``g_lse`` carries the lse output's cotangent (ring/ulysses merges)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    BHq = B * Hq
    qf = q.reshape(BHq, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    dof = g.reshape(BHq, S, D)
    # per-row stats ride a 128-lane dim (same Mosaic tiling constraint as the
    # forward's lse output; the kernels read lane 0)
    lsef = jnp.broadcast_to(lse.reshape(BHq, S)[:, :, None], (BHq, S, _LANES))
    dlsef = (
        jnp.zeros((BHq, S, _LANES), jnp.float32)
        if g_lse is None
        else jnp.broadcast_to(
            g_lse.astype(jnp.float32).reshape(BHq, S)[:, :, None],
            (BHq, S, _LANES),
        )
    )
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        .reshape(BHq, S)[:, :, None],
        (BHq, S, _LANES),
    )

    kv_index = lambda bh, g=group: bh // g

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q
        ),
        grid=(BHq, pl.cdiv(S, block_q), pl.cdiv(S, block_k)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (kv_index(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (kv_index(bh), ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta, dlsef)

    # dk/dv per QUERY head (kv blocks replicated across the group), then
    # group-summed outside the kernel
    dkv_grid = (BHq, pl.cdiv(S, block_k), pl.cdiv(S, block_q))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, block_k=block_k
        ),
        grid=dkv_grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (kv_index(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (kv_index(bh), ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, S, D), k.dtype),
            jax.ShapeDtypeStruct((BHq, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta, dlsef)

    dq = dq.reshape(B, Hq, S, D)
    dk = dk_h.reshape(B, Hkv, group, S, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Fused attention: q [B,Hq,S,D], k/v [B,Hkv,S,D] (GQA when Hkv < Hq).
    ``block_q`` / ``block_k`` None: the forward's tiles are chosen from the
    shapes (``choose_blocks``), the backward kernels keep their 128."""
    o, _ = _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k)
    return o


def _resolve_scale(q, sm_scale):
    return q.shape[-1] ** -0.5 if sm_scale is None else sm_scale


def _bwd_blocks(S, block_q=None, block_k=None):
    """The backward kernels' blocks: 128 unless given; their grids walk whole
    blocks only, so the length has to divide."""
    bq, bk = min(block_q or 128, S), min(block_k or 128, S)
    if S % bq or S % bk:
        raise ValueError(
            f"length {S} must be a multiple of block sizes ({bq}, {bk}); "
            "pad sequences at the model layer"
        )
    return bq, bk


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    scale = _resolve_scale(q, sm_scale)
    _bwd_blocks(q.shape[2], block_q, block_k)
    o, lse = _flash_forward(
        q, k, v, causal=causal, sm_scale=scale,
        block_q=block_q, block_k=block_k, interpret=_use_interpret(),
    )
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, o, lse = res
    scale = _resolve_scale(q, sm_scale)
    bq, bk = _bwd_blocks(q.shape[2], block_q, block_k)
    import os as _os

    if _os.environ.get("MTPU_FLASH_BWD", "kernel") == "recompute":
        # XLA-recompute fallback (numerically identical; debugging aid)
        def ref(q, k, v):
            return reference.attention(q, k, v, causal=causal, sm_scale=scale)

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)
    return _flash_backward(
        q, k, v, o, lse, g, causal=causal, sm_scale=scale,
        block_q=bq, block_k=bk, interpret=_use_interpret(),
    )


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_with_lse(q, k, v, causal, sm_scale):
    _bwd_blocks(q.shape[2])
    return _flash_forward(
        q, k, v, causal=causal, sm_scale=sm_scale, interpret=_use_interpret(),
    )


def _flash_with_lse_fwd(q, k, v, causal, sm_scale):
    out = _flash_with_lse(q, k, v, causal, sm_scale)
    return out, (q, k, v, *out)


def _flash_with_lse_fwd_res(q, k, v, causal, sm_scale):
    o, lse = _flash_with_lse(q, k, v, causal, sm_scale)
    return (o, lse), (q, k, v, o, lse)


def _flash_with_lse_bwd(causal, sm_scale, res, cots):
    # dedicated Pallas backward; the lse output's cotangent (nonzero inside
    # ring/ulysses softmax merges) feeds the kernels' dS term directly
    q, k, v, o, lse = res
    g_o, g_lse = cots
    bq, bk = _bwd_blocks(q.shape[2])
    import os as _os

    if _os.environ.get("MTPU_FLASH_BWD", "kernel") == "recompute":
        _, vjp = jax.vjp(
            lambda q, k, v: reference.attention_with_lse(
                q, k, v, causal=causal, sm_scale=sm_scale
            ),
            q, k, v,
        )
        return vjp(cots)
    return _flash_backward(
        q, k, v, o, lse, g_o, causal=causal, sm_scale=sm_scale,
        block_q=bq, block_k=bk, interpret=_use_interpret(), g_lse=g_lse,
    )


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def flash_attention_with_lse(q, k, v, *, causal=True, sm_scale=None):
    """Variant also returning the per-row logsumexp (used by ring attention
    to combine partial results across shards). Differentiable through the
    Pallas backward kernels, the lse output's cotangent included."""
    return _flash_with_lse(q, k, v, causal, _resolve_scale(q, sm_scale))


def flash_attention_chunked(
    q: jax.Array,  # [B, Hq, S_chunk, D] — queries at positions
                   # [q_offset, q_offset + S_chunk) of the full sequence
    k: jax.Array,  # [B, Hkv, S_kv, D] — the full (or so-far) K
    v: jax.Array,
    *,
    q_offset: int,
    causal: bool = True,
    sm_scale: float | None = None,
    window: int | None = None,
    k_first=None,
) -> jax.Array:
    """Rectangular attention for chunked prefill: one query chunk against a
    longer K/V prefix (the engine processes long prompts chunk by chunk with
    bounded VMEM; also the building block for prefix-cache reuse). Forward
    only — prefill needs no gradients.

    A prefix plus a short last chunk (2048 + 128 keys) divides into no key
    block longer than the chunk, and the key block's length decides the
    kernel's time: past ``_LONG_KEY_BLOCK`` keys the call pads K/V with zero
    rows to the next multiple of it. They lie after every query's position,
    so the causal mask covers them and no step is taken for a block of them
    alone (0.36 -> 0.21 ms a call at 128 rows over 2048, PERF.md section 6).

    ``window`` (static; sliding-window attention): a query sees itself and
    the ``window - 1`` positions before it, and the k grid of a query tile
    starts at the first block any of its rows reaches as well as stopping at
    the diagonal. ``k_first`` (an int32 scalar, traced or not): keys below
    that index are masked for every query, for a caller whose prefix rows
    were gathered at a static length longer than the sequence (their tiles
    are computed and masked, not skipped: the grid is static). A call with
    neither lowers to what it did before they existed."""
    pad = padded_kv_len(k.shape[2]) - k.shape[2] if causal else 0
    if pad:
        k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (k, v))
    o, _ = _flash_forward(
        q, k, v, causal=causal, sm_scale=_resolve_scale(q, sm_scale),
        interpret=_use_interpret(), q_offset=q_offset,
        **({} if window is None else {"window": window}),
        **({} if k_first is None else {"k_first": k_first}),
    )
    return o
