"""The decode state step's kernel (``ops/ssm_step.py``) in interpret mode
against XLA's form of the same step (``ssm_step_xla``) on the same inputs.

Tolerances: the two compute ``h' = a h + (dt x) (x) B`` in float32 in the same
order (1e-6 of a state of size ~4: one rounding, a fused multiply-add on the
CPU) and ``y = sum_n h' C`` with the 128 terms summed in another order (1e-4
of sums of size ~40).
"""

import numpy as np
import pytest

L, S, H, P, N = 3, 5, 8, 16, 128
DEAD = (1, 4)  # slots that are not live: dt = 0


def _inputs(groups, seed=0):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    live = ~jnp.isin(jnp.arange(S), jnp.asarray(DEAD))
    ssm = jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32)
    decay = jnp.where(live[:, None], jax.random.uniform(ks[1], (S, H), jnp.float32, 0.5, 1.0), 1.0)
    dtx = jnp.where(live[:, None, None], jax.random.normal(ks[2], (S, H, P), jnp.float32), 0.0)
    B, C = (jax.random.normal(k, (S, groups, N), jnp.float32) for k in ks[3:])
    return ssm, decay, dtx, B, C


@pytest.mark.parametrize("tile", [(1, 8), (2, 3)], ids=["tile-divides", "tile-hangs-over"])
@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("groups", [1, 2])
def test_the_kernel_is_the_xla_step_in_place(groups, layer, tile):
    """``h'`` and ``y`` of layer ``layer`` as the XLA form gives them; a slot
    that is not live keeps its state bit for bit (its ``y`` is the XLA
    form's like any other's); every other layer of the
    stacked leaf is bit-identical (in place means in place). ``(2, 3)``
    divides neither the 5 slots nor the 8 heads: the last blocks hang over
    the leaf's edge."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.ssm_step import ssm_step, ssm_step_xla

    ssm, *small = _inputs(groups)
    want_ssm, want_y = jax.jit(ssm_step_xla)(ssm, jnp.int32(layer), *small)
    got_ssm, got_y = jax.jit(
        lambda ssm, i, *rest: ssm_step(ssm, i, *rest, tile=tile, interpret=True)
    )(ssm, jnp.int32(layer), *small)
    assert float(jnp.abs(want_y).max()) > 10.0
    np.testing.assert_allclose(got_ssm[layer], want_ssm[layer], atol=1e-6 * 4)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4)
    dead = np.asarray(DEAD)
    np.testing.assert_array_equal(got_ssm[layer][dead], ssm[layer][dead])
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(got_ssm[jnp.asarray(others)], ssm[jnp.asarray(others)])
    assert float(jnp.abs(got_ssm[layer] - ssm[layer]).max()) > 0.1  # the live rows moved


@pytest.mark.parametrize(
    "shape",
    [(64, 64, 64, 128), (64, 128, 64, 128), (5, 8, 16, 128), (3, 24, 64, 256)],
    ids=["granite-micro-64-slots", "128-heads", "tiny-heads", "24-heads-of-256"],
)
def test_the_chosen_tile_divides_the_leaf_and_is_a_legal_block(shape):
    """Divisors of slots and heads (no block hangs over), heads a multiple of
    8 or all of them (``dt x`` and ``y`` are blocked ``(slots, heads, P)``),
    within ``TILE_BYTES`` unless one state alone is over it."""
    from modal_examples_tpu.ops.ssm_step import TILE_BYTES, ssm_step_tile

    n_slots, n_heads, d_head, d_state = shape
    ts, th = ssm_step_tile(*shape)
    assert n_slots % ts == 0 and n_heads % th == 0
    assert th % 8 == 0 or th == n_heads
    one = d_head * d_state * 4
    assert ts * th * one <= TILE_BYTES or (ts, th) == (1, min(
        h for h in range(1, n_heads + 1) if n_heads % h == 0 and (h % 8 == 0 or h == n_heads)))
    assert ts == 1 or th == n_heads  # whole slots only once a slot's heads all fit


@pytest.mark.parametrize(
    "d_head,d_state,dtype,ok",
    [(64, 128, "float32", True), (64, 128, "bfloat16", False), (16, 16, "float32", False),
     (64, 256, "float32", True), (12, 128, "float32", False)],
)
def test_which_states_mosaic_takes_as_tiles(d_head, d_state, dtype, ok):
    from modal_examples_tpu.ops.ssm_step import ssm_step_shapes_ok

    assert ssm_step_shapes_ok(d_head, d_state, dtype) is ok
