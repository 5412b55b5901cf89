"""Compile-only checks for the TPU: libtpu is installed here, and a described
(not attached) v5e topology takes ``jit(...).lower(...).compile()``, which
raises what Mosaic would raise on the chip — a tile over VMEM, a slice off
the tiling. Nothing runs, so this says nothing about results or times.

All such tests live in THIS file: one process loads the TPU's library, and
under pytest-xdist a file goes to one worker. The topology is described
inside a fixture, never at import.
"""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    import jax
    from jax.sharding import SingleDeviceSharding

    env = pytest.MonkeyPatch()
    for name, value in {
        "TPU_LOG_DIR": "disabled",
        "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        "TPU_WORKER_HOSTNAMES": "localhost",
        "TPU_SKIP_MDS_QUERY": "1",
    }.items():
        if name not in os.environ:
            env.setenv(name, value)
    # a program compiled for a described device is written to the persistent
    # cache and cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        env.undo()


@pytest.mark.parametrize(
    "Hq,Hkv,D,Dv",
    [(32, 8, 128, 128), (128, 128, 192, 128)],
    ids=["gqa-32-8-128", "mla-128-192-128"],
)
@pytest.mark.parametrize("q_offset", [0, 2048])
def test_flash_chunk_compiles_for_v5e(one_chip, Hq, Hkv, D, Dv, q_offset):
    """The docqa cells' chunk calls (2048 query rows at offset 0 and 2048,
    bf16) with the tiles the kernel chooses: Mosaic takes them inside the
    VMEM limit the call asks for."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.flash_attention import _flash_forward

    C = 2048
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: _flash_forward(
        q, k, v, causal=True, sm_scale=D**-0.5, interpret=False,
        q_offset=q_offset,
    )
    compiled = jax.jit(fn).lower(
        shape(1, Hq, C, D), shape(1, Hkv, q_offset + C, D),
        shape(1, Hkv, q_offset + C, Dv),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
