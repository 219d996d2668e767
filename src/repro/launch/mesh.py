"""Production mesh construction.

Functions, never module-level constants: importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS *before* any jax init,
smoke tests want to keep seeing 1 device).

Every mesh has ``Auto`` axes: the drivers place their own shardings
(``shard_map`` specs, ``NamedSharding``) and leave the rest to the
compiler.  ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
plain indexing of a sharded corpus (``X[ids]``) is refused.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment meshes.

    single pod : (data=16, model=16)        = 256 chips (one v5e pod)
    multi-pod  : (pod=2, data=16, model=16) = 512 chips; the 'pod' axis
                 multiplies data parallelism and crosses DCI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh():
    """1x1 mesh with the production axis names (CPU tests/examples)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_mesh_for(devices: int, model_parallel: int = 16, pods: int = 1):
    """Elastic variant used by runtime re-meshing: distribute `devices`
    over (pod, data, model) with a fixed model size."""
    assert devices % (model_parallel * pods) == 0
    data = devices // (model_parallel * pods)
    if pods > 1:
        return _make_mesh((pods, data, model_parallel),
                          ("pod", "data", "model"))
    return _make_mesh((data, model_parallel), ("data", "model"))
