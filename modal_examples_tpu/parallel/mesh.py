"""Device mesh construction — the TPU-native replacement for process groups.

The reference's distributed story is NCCL process groups wired up by torchrun
(14_clusters/simple_torch_cluster.py:67,118-130). On TPU the unit is a
``jax.sharding.Mesh`` over the slice's chips: axes are *named* (data / fsdp /
tensor / seq / expert), shardings are ``NamedSharding`` partition specs, and
XLA inserts the collectives (psum over ICI, etc.) — nothing in workload code
ever names a transport. This module builds meshes from ``tpu=`` specs or raw
device lists and is the single place axis-name conventions live.

Mental model follows the public scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert collectives.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.resources import TPUSpec, parse_tpu_spec

# Canonical axis names. Order matters: earlier axes get the slower-varying
# device dimension (DCN/across-host first, ICI/within-host last), so tensor/
# seq axes land on the fastest interconnect.
DATA = "data"
FSDP = "fsdp"
TENSOR = "tensor"
SEQ = "seq"
EXPERT = "expert"
AXIS_ORDER = (DATA, FSDP, EXPERT, SEQ, TENSOR)


def resolve_axes(
    axes: dict[str, int] | None, n_devices: int
) -> dict[str, int]:
    """Resolve an axis spec against a device count. One axis may be -1
    (fill); omitted spec means pure data parallelism."""
    if not axes:
        return {DATA: n_devices}
    axes = dict(axes)
    fill_keys = [k for k, v in axes.items() if v == -1]
    if len(fill_keys) > 1:
        raise ValueError(f"only one axis may be -1, got {fill_keys}")
    fixed = math.prod(v for v in axes.values() if v != -1)
    if fill_keys:
        if n_devices % fixed:
            raise ValueError(
                f"device count {n_devices} not divisible by fixed axes {axes}"
            )
        axes[fill_keys[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"axes {axes} multiply to {fixed}, but mesh has {n_devices} devices"
        )
    return axes


def make_mesh(
    axes: dict[str, int] | None = None,
    *,
    devices: Sequence | None = None,
    spec: TPUSpec | str | None = None,
) -> Mesh:
    """Build a named mesh.

    ``axes`` maps axis name -> size (one may be -1 to fill). ``devices``
    defaults to all visible devices; ``spec`` (e.g. "v5e-8") validates the
    request against the slice size when given.
    """
    if devices is None:
        devices = jax.devices()
        if axes:
            # a fully-specified request smaller than the machine takes a
            # prefix of the devices (e.g. a seq-4 mesh on an 8-chip host)
            want = math.prod(v for v in axes.values() if v != -1)
            if all(v != -1 for v in axes.values()) and want <= len(devices):
                devices = devices[:want]
    if spec is not None:
        if isinstance(spec, str):
            spec = parse_tpu_spec(spec)
        if len(devices) != spec.chips:
            raise ValueError(
                f"tpu spec {spec} wants {spec.chips} chips but "
                f"{len(devices)} devices are visible"
            )
    resolved = resolve_axes(axes, len(devices))
    # order axes canonically so cross-host axes vary slowest
    names = sorted(
        resolved,
        key=lambda n: AXIS_ORDER.index(n) if n in AXIS_ORDER else len(AXIS_ORDER),
    )
    shape = tuple(resolved[n] for n in names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(names))


def fsdp_specs(params, mesh: Mesh, *, axis: str = FSDP, min_size: int = 2**12):
    """Derive ZeRO-3/FSDP PartitionSpecs for an arbitrary param pytree: every
    sufficiently large leaf is sharded along its largest axis-divisible dim
    over ``axis``; small leaves (norms, biases) stay replicated.

    Under jit, GSPMD turns these annotations into exactly the FSDP schedule
    the reference delegates to torch FSDP/verl (grpo_verl.py:176-202,
    SURVEY.md §2.3): per-layer all-gather of the shard on use, reduce-scatter
    of the gradients, and optimizer state that lives sharded — optax init
    under jit propagates the param shardings to the moment buffers, so
    per-device memory for params+grads+optimizer shrinks ~linearly with the
    axis size (proven by tests/test_parallel.py::TestFSDP).
    """
    n = mesh.shape[axis]

    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        if not shape or math.prod(shape) < min_size:
            return P()
        dims = sorted(range(len(shape)), key=lambda d: shape[d], reverse=True)
        for d in dims:
            if shape[d] % n == 0:
                return P(*(axis if i == d else None for i in range(len(shape))))
        return P()

    return jax.tree.map(spec_for, params)


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]), (DATA,))


def sharding(mesh: Mesh, *axis_per_dim: str | None | tuple) -> NamedSharding:
    """``sharding(mesh, 'data', None, 'tensor')`` -> NamedSharding for a rank-3
    array sharded over data on dim0 and tensor on dim2."""
    return NamedSharding(mesh, P(*axis_per_dim))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_pytree(tree, mesh: Mesh, spec_fn) -> object:
    """Device-put every leaf with the PartitionSpec returned by
    ``spec_fn(path_leafname, leaf)``; used by model loaders to place sharded
    weights without 2x host RAM."""
    import jax.tree_util as jtu

    def place(path, leaf):
        pspec = spec_fn(path, leaf)
        return jax.device_put(leaf, NamedSharding(mesh, pspec))

    return jtu.tree_map_with_path(place, tree)
