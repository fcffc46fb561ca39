"""Device mesh construction for table sharding.

One logical axis ("shards") carries data-parallel table partitioning — the
analog of the reference's executor count (reference nds/base.template
NUM_EXECUTORS x EXECUTOR_CORES; here chips on ICI).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_shards: Optional[int] = None,
              devices: Optional[Sequence] = None,
              axis_name: str = "shards") -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards is not None:
        if len(devs) < n_shards:
            raise ValueError(
                f"need {n_shards} devices, have {len(devs)} "
                "(for tests set XLA_FLAGS=--xla_force_host_platform_device_count)")
        devs = devs[:n_shards]
    import numpy as np
    return Mesh(np.asarray(devs), (axis_name,))


def shard_spec(mesh: Mesh) -> NamedSharding:
    """Row-sharded over the mesh's first axis."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
