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
