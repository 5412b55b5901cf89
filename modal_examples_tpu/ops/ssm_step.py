"""A Mamba-2 layer's decode step over its per-slot state, in one pass
(Pallas -> Mosaic).

    h' = a h + (dt x) (x) B        y = sum_n h' C

over a layer's ``[slots, heads, d_head, d_state]`` float32 state, which at
Granite-4.0-H's widths and 64 slots is 134 MB a layer and nearly all of a
decode step's bytes. XLA writes ``h'`` through the in-place update of the
stacked leaf and reduces it in a second fusion, which reads it again
(``ssm_step_xla``, the form the CPU and every shape the kernel refuses run):
three passes over the state. The kernel makes two: a tile of slots x heads
is read, updated, written back to the block it came from (the leaf is
aliased in and out) and reduced to its share of ``y`` while it is in VMEM.

- grid = (slots / ts, heads / th); a step's tile is ``ts x th`` states of
  ``[d_head, d_state]``, each a whole number of (8, 128) float32 vregs
  (``ssm_step_shapes_ok``). The layer index, which ``lax.scan`` traces,
  arrives by scalar prefetch and is read by the index maps, as the paged
  decode kernel takes its layer (ops.paged_attention).
- the decays ``a [slots, heads]`` arrive by scalar prefetch too: a scalar
  times a vreg is what the VPU does natively, and a ``(ts, th)`` block of
  them would be no legal VMEM tile.
- ``dt x`` arrives with ``d_head`` on the lanes, as XLA's prologue leaves
  it; a head's row becomes a column by one sublane-chunk broadcast a vreg.
  ``B`` and ``C`` stay ``[slots, groups, d_state]``: a head reads its
  group's row.
- per state vreg: a load, three multiplies, an add, a store, a reload and
  one cross-lane add (the XLU's); the eight sums of a vreg's rows are
  gathered to ``y``'s lanes once a head. The update of a tile's heads comes
  first and their sums after it, from the block just written: interleaved
  head by head the two kinds of XLU work serialise on each other's latency
  and the kernel turns compute-bound. No MXU: a float32 ``[4096, 128] x
  [128, 1]`` product a slot would cost more than the step.

The arithmetic is the XLA form's, in its order and in float32 (``y`` from
``h'``, not from ``a (h . C) + dt x (B . C)``); the two differ by the order
of the 128-term sum. A slot that is not live has ``dt = 0`` (``a = 1``,
``dt x = 0``): its state is written back as it was read. Runs in interpreter
mode off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: a tile's state bytes: in and out, double-buffered, are four of these in
#: VMEM. What the sweep on the chip gave (PERF.md section 6, PR 37)
TILE_BYTES = 2 * 2**20
#: heads a trip of the kernel's two loops, unrolled: all of a tile's 64 cost
#: the decode block 5 s of lowering a boot (five segments, each its own
#: call) for nothing the DMA does not hide (PERF.md section 6, PR 37)
UNROLL = 16


def ssm_step_shapes_ok(d_head: int, d_state: int, dtype) -> bool:
    """Whether Mosaic takes a ``[d_head, d_state]`` state of ``dtype`` as a
    tile: float32 in whole (8, 128) vregs. The one predicate behind
    ``granite_hybrid.paged_impl_plan``'s choice and the wrapper's refusal."""
    return jnp.dtype(dtype) == jnp.float32 and d_state % 128 == 0 and d_head % 8 == 0


def ssm_step_tile(n_slots: int, n_heads: int, d_head: int, d_state: int) -> tuple[int, int]:
    """(slots, heads) a grid step: the most heads of one slot that keep the
    tile within ``TILE_BYTES`` (a multiple of 8, or all of them: ``dt x`` and
    ``y`` are blocked ``(slots, heads, d_head)``), then whole slots while
    they fit. Divisors only, so no block hangs over the leaf's edge."""
    one = d_head * d_state * 4
    heads = [h for h in range(1, n_heads + 1)
             if n_heads % h == 0 and (h % 8 == 0 or h == n_heads)]
    th = max((h for h in heads if h * one <= TILE_BYTES), default=heads[0])
    slots = [s for s in range(1, n_slots + 1)
             if th == n_heads and n_slots % s == 0 and s * th * one <= TILE_BYTES]
    return max(slots, default=1), th


def ssm_step_xla(ssm, layer, decay, dtx, B, C):
    """The step as XLA runs it: ``h'`` formed, reduced against ``C`` and set
    into layer ``layer`` of the stacked leaf. Shapes as ``ssm_step``."""
    H, G = decay.shape[1], B.shape[1]
    B, C = (jnp.repeat(a, H // G, axis=1) for a in (B, C))
    h_new = (
        decay[..., None, None] * ssm[layer].astype(jnp.float32)
        + dtx[..., None] * B[:, :, None, :]
    )
    y = jnp.einsum("shpn,shn->shp", h_new, C)
    return ssm.at[layer].set(h_new.astype(ssm.dtype)), y


def _ssm_step_kernel(
    # scalar prefetch
    layer_ref,  # (1,) int32: read by the index maps
    decay_ref,  # (S * H,) f32
    h_ref,  # (ts, th, P, N) VMEM: this tile of the layer's state
    dtx_ref,  # (ts, th, P)
    b_ref,  # (ts, G, N)
    c_ref,
    h_out,  # (ts, th, P, N): the same block of the aliased leaf
    y_out,  # (ts, th, P)
    *,
    n_slots: int,
    n_heads: int,
    group_heads: int,
):
    del layer_ref
    ts, th, P, _ = h_ref.shape
    slot0, head0 = pl.program_id(0) * ts, pl.program_id(1) * th
    unroll = max(u for u in range(1, UNROLL + 1) if th % u == 0)
    # a block that hangs over the edge (a tile that does not divide the leaf)
    # computes on padding, dropped on the way out: its indices are held inside
    inside = (lambda i, n: i) if n_slots % ts == n_heads % th == 0 else (
        lambda i, n: jnp.minimum(i, n - 1))

    def heads(body):
        """``body(j, head, group)`` over the tile's heads, ``unroll`` a trip."""

        def trip(k, carry):
            for u in range(unroll):
                j = k * unroll + u
                head = inside(head0 + j, n_heads)
                body(j, head, 0 if group_heads == n_heads else head // group_heads)
            return carry

        jax.lax.fori_loop(0, th // unroll, trip, None)

    def slot(s, carry):
        at = inside(slot0 + s, n_slots) * n_heads

        def update(j, head, group):
            col = dtx_ref[s, pl.ds(j, 1), :].reshape(P, 1)
            b = b_ref[s, pl.ds(group, 1), :]
            h_out[s, j] = decay_ref[at + head] * h_ref[s, j] + col * b

        def reduce(j, head, group):
            c = c_ref[s, pl.ds(group, 1), :]
            y_out[s, pl.ds(j, 1), :] = jnp.sum(h_out[s, j] * c, axis=-1)[None, :]

        # two passes over the tile's heads: a head's column broadcast and its
        # cross-lane sums are both the XLU's, and done head by head each
        # waits out the other's latency (488 us a layer of compute alone
        # against 253 so, under a DMA of 448: PERF.md section 6, PR 37)
        heads(update)
        heads(reduce)
        return carry

    jax.lax.fori_loop(0, ts, slot, None)


def ssm_step(
    ssm: jax.Array,  # [L, S, H, P, N] f32: the stacked per-slot leaf
    layer,  # int32 scalar (traced under lax.scan)
    decay: jax.Array,  # [S, H] f32: exp(dt A); 1 where the slot is not live
    dtx: jax.Array,  # [S, H, P] f32: dt x; 0 where the slot is not live
    B: jax.Array,  # [S, G, N] f32
    C: jax.Array,  # [S, G, N] f32
    *,
    tile: tuple[int, int] | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of layer ``layer``: returns (the leaf with that
    layer's state advanced in place, ``y [S, H, P]`` f32). Every other layer
    of the leaf is left as it is. Pass the leaf donated under jit.

    ``tile`` (slots, heads a grid step) is ``ssm_step_tile``'s unless given;
    ``interpret`` is taken from the backend at trace time."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, S, H, P, N = ssm.shape
    G = B.shape[1]
    if not interpret and not ssm_step_shapes_ok(P, N, ssm.dtype):
        raise ValueError(
            f"ssm_step needs a float32 state with d_state%128==0 and d_head%8==0 on "
            f"TPU; got {ssm.dtype}[{P}, {N}]. granite_hybrid.paged_impl_plan falls "
            "back to the XLA form (ssm_step_xla)."
        )
    ts, th = tile or ssm_step_tile(S, H, P, N)
    state_block = pl.BlockSpec(
        (None, ts, th, P, N), lambda s, h, layer, _: (layer[0], s, h, 0, 0)
    )
    rows_block = pl.BlockSpec((ts, th, P), lambda s, h, *_: (s, h, 0))
    group_block = pl.BlockSpec((ts, G, N), lambda s, h, *_: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(S, ts), pl.cdiv(H, th)),
        in_specs=[state_block, rows_block, group_block, group_block],
        out_specs=[state_block, rows_block],
    )
    kernel = functools.partial(
        _ssm_step_kernel, n_slots=S, n_heads=H, group_heads=H // G
    )
    tile_bytes = ts * th * P * N * 4
    ssm, y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
            jax.ShapeDtypeStruct((S, H, P), jnp.float32),
        ],
        # operands: 2 scalar-prefetch, then the leaf: updated in place
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state's tile in and out, each double-buffered, and the rest
            vmem_limit_bytes=4 * tile_bytes + 8 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(5 * S * H * P * N),
            bytes_accessed=int(2 * S * H * P * N * 4),
            transcendentals=0,
        ),
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        decay.reshape(-1),
        ssm, dtx, B, C,
    )
    return ssm, y
