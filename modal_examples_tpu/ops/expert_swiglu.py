"""The routed experts' SwiGLU over sorted rows, as one grouped matmul
(Pallas -> Mosaic): the decode-shaped half of ``moe.moe_swiglu_sparse``.

    y[tile i] = (silu(x_i W_gate[e_i]) * (x_i W_up[e_i])) W_down[e_i]

``moe_swiglu_sparse`` sorts a call's (token, expert) pairs by expert and pads
each expert's run to whole tiles; tile ``i`` is ``tile`` rows of one expert
``e_i``. The XLA form runs a ``fori_loop`` of a trip a tile, three matmul
fusions a trip, each of which starts its weight stream when it starts and
drains it before the next: 17.9 us a trip where an LFM2 expert's 9.44 MB of
int8 read takes 11.5 (PERF.md section 6, PR 39). Here the tiles are a grid and the
experts' matrices its blocks, so Pallas's pipeline fetches tile ``i + 1``'s
expert under tile ``i``'s products.

- grid = (tiles, F blocks), both static. ``layer``, ``tile_expert [tiles]``
  and ``n_tiles`` arrive by scalar prefetch and the weights' index maps pick
  ``[layer, tile_expert[i], :, f]`` out of the **whole** ``[L, E, D, F]``
  stacks (``q`` and ``scale`` alike): no layer's slice of a stack is ever
  materialised (``moe.scan_layers``), and an expert no pair reaches is never
  read.
- a trip past ``n_tiles`` holds every index at the last live trip's (Pallas
  fetches no block again whose index did not change) and ``pl.when`` skips its
  body: it costs a grid step and nothing else. Tiles of one expert that
  follow each other read its matrices once where F is one block.
- per tile the arithmetic of ``layers.mm`` in the loop's places: int8 goes to
  the activations' dtype on its way into the MXU, ``a`` and ``b`` are float32
  and scaled there, ``silu(a) * b`` is rounded to the activations' dtype, the
  down product is summed in float32 over the F blocks (in the output's block,
  which stays in VMEM while ``f`` runs) and scaled once. Only the order of
  that float32 sum over F differs from XLA's.
- the rows of a tile past ``n_tiles`` are never written: the caller reads
  only rows of live tiles.

Runs in interpreter mode off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: an expert's three weight blocks of one grid step, in bytes: what the F
#: block is the largest divisor under. Two of these are in VMEM at a time
#: (this step's and the next one's in flight) beside the converted operands
BLOCK_BYTES = 10 * 2**20


def expert_swiglu_shapes_ok(d_model: int, d_ff: int, dtype) -> bool:
    """Whether Mosaic takes an expert's ``[d_model, d_ff]`` matrices of
    ``dtype`` in lane-aligned F blocks: int8 (under a float32 scale a
    column), bfloat16 or float32, both widths whole 128-lane vregs. The one
    predicate behind ``moe.expert_scan_form``'s choice and the wrapper's
    refusal."""
    return (
        jnp.dtype(dtype) in (jnp.dtype(jnp.int8), jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        and d_model % 128 == 0 and d_ff % 128 == 0
    )


def expert_swiglu_block(d_model: int, d_ff: int, dtype, block_bytes: int = BLOCK_BYTES) -> int:
    """Columns of F a grid step: the largest divisor of ``d_ff`` in whole
    128-lane vregs whose three blocks keep within ``block_bytes`` (at least
    the smallest such; all of a ``d_ff`` that has none). An LFM2 expert's
    9.4 MB goes whole; a Mixtral expert's 176 MB in 28 blocks of 512."""
    one = 3 * d_model * jnp.dtype(dtype).itemsize
    blocks = [f for f in range(128, d_ff + 1, 128) if d_ff % f == 0] or [d_ff]
    return max((f for f in blocks if f * one <= block_bytes), default=blocks[0])


#: the gate's activation, by the name a caller gives (``activation=``):
#: SwiGLU's ``silu`` (written out: ``a * sigmoid(a)``), ReGLU's ``relu``
ACTIVATIONS = {
    "silu": lambda a: a * jax.nn.sigmoid(a),
    "relu": lambda a: jnp.maximum(a, 0.0),
}


def _expert_swiglu_kernel(*refs, n_f: int, quantized: bool, activation: str = "silu"):
    # scalar prefetch: layer (read by the index maps), tile_expert (likewise), n_tiles
    _, _, n_tiles = refs[:3]
    if quantized:
        x_ref, gate, gate_s, up, up_s, down, down_s, o_ref = refs[3:]
    else:
        x_ref, gate, up, down, o_ref = refs[3:]
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < n_tiles[0])
    def _():
        x = x_ref[...]
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        a, b = dot(x, gate[...].astype(x.dtype)), dot(x, up[...].astype(x.dtype))
        if quantized:
            a, b = a * gate_s[...], b * up_s[...]
        y = dot((ACTIVATIONS[activation](a) * b).astype(x.dtype), down[...].astype(x.dtype))
        if n_f == 1:
            o_ref[...] = y * down_s[...] if quantized else y
            return

        @pl.when(f == 0)
        def _():
            o_ref[...] = y

        @pl.when(f > 0)
        def _():
            o_ref[...] += y

        if quantized:
            @pl.when(f == n_f - 1)
            def _():
                o_ref[...] *= down_s[...]


def expert_swiglu(
    w_gate,  # [L, E, D, F]: plain, or a QuantizedWeight's (q int8, scale f32 [L, E, 1, F])
    w_up,
    w_down,  # [L, E, F, D]
    rows: jax.Array,  # [tiles * tile, D]: the sorted pairs' token rows, tile by tile
    tile_expert: jax.Array,  # [tiles] int32: the expert of each tile
    n_tiles,  # int32 scalar: tiles in use; the rest are skipped
    layer,  # int32 scalar (traced under lax.scan)
    *,
    tile: int,
    block_f: int | None = None,
    interpret: bool | None = None,
    activation: str = "silu",  # the gate's: a key of ACTIVATIONS (static)
) -> jax.Array:
    """``[tiles * tile, D]`` float32: each live tile's rows through its
    expert's gated FFN, ``act(x W_gate) * (x W_up)`` through ``W_down``
    (SwiGLU unless ``activation`` says ReGLU). Rows of tiles past ``n_tiles``
    are not written.

    ``block_f`` (columns of F a grid step) is ``expert_swiglu_block``'s
    unless given; ``interpret`` is taken from the backend at trace time."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = hasattr(w_gate, "scale")
    gate_q = w_gate.q if quantized else w_gate
    _, _, D, F = gate_q.shape
    tiles = tile_expert.shape[0]
    if rows.shape != (tiles * tile, D):
        raise ValueError(f"rows {rows.shape} are not {tiles} tiles of {tile} x {D}")
    if not interpret and not expert_swiglu_shapes_ok(D, F, gate_q.dtype):
        raise ValueError(
            f"expert_swiglu needs int8, bfloat16 or float32 experts with d_model%128==0 "
            f"and d_ff%128==0 on TPU; got {gate_q.dtype}[{D}, {F}]. "
            "moe.expert_scan_form falls back to the XLA loop."
        )
    bf = block_f or expert_swiglu_block(D, F, gate_q.dtype)
    if F % bf:
        raise ValueError(f"block_f {bf} does not divide d_ff {F}")
    n_f = F // bf

    def block(shape, pick):
        """A block whose index ``pick(tile, (layer, expert), f)`` gives; a
        trip past n_tiles stays on the last live trip's: nothing is fetched."""

        def index(i, f, layer, tile_expert, n_tiles):
            f = jnp.where(i < n_tiles[0], f, n_f - 1)
            i = jnp.maximum(jnp.minimum(i, n_tiles[0] - 1), 0)
            return pick(i, (layer[0], tile_expert[i]), f)

        return pl.BlockSpec(shape, index)

    rows_block = block((tile, D), lambda i, at, f: (i, 0))
    up_block = block((None, None, D, bf), lambda i, at, f: (*at, 0, f))  # gate's and up's
    up_scale = block((None, None, 1, bf), lambda i, at, f: (*at, 0, f))
    down_block = block((None, None, bf, D), lambda i, at, f: (*at, f, 0))
    down_scale = block((None, None, 1, D), lambda i, at, f: (*at, 0, 0))
    if quantized:
        in_specs = [rows_block, up_block, up_scale, up_block, up_scale, down_block, down_scale]
        weights = (w_gate.q, w_gate.scale, w_up.q, w_up.scale, w_down.q, w_down.scale)
    else:
        in_specs = [rows_block, up_block, up_block, down_block]
        weights = (w_gate, w_up, w_down)
    wbytes = 3 * D * bf * gate_q.dtype.itemsize
    xbytes = rows.dtype.itemsize
    return pl.pallas_call(
        functools.partial(
            _expert_swiglu_kernel, n_f=n_f, quantized=quantized, activation=activation
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, n_f),
            in_specs=in_specs,
            out_specs=rows_block,
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the weights' blocks double-buffered, their copies in the
            # activations' dtype on the way into the MXU, and the rest
            vmem_limit_bytes=2 * wbytes + 3 * D * bf * xbytes + 16 * 2**20,
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(6 * tiles * tile * D * F),
            bytes_accessed=int(tiles * (3 * D * F * gate_q.dtype.itemsize + tile * D * (xbytes + 4))),
            transcendentals=int(tiles * tile * F),
        ),
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        tile_expert.astype(jnp.int32),
        jnp.reshape(n_tiles, (1,)).astype(jnp.int32),
        rows, *weights,
    )
