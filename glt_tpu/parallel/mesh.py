"""Device-mesh helpers.

The reference's process-group topology (worker groups over
TensorPipe/NCCL, distributed/dist_context.py) maps on TPU to a
jax.sharding.Mesh. The default single-axis 'data' mesh carries both data
parallelism (gradient psum = the DDP allreduce) and graph/feature shard
parallelism (all_to_all = the reference's cross-partition rpc fabric,
SURVEY.md §2.3).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(num_devices: Optional[int] = None,
              axis_names: Sequence[str] = ('data',)) -> Mesh:
  devs = jax.devices()
  n = num_devices or len(devs)
  assert n <= len(devs), f'requested {n} devices, have {len(devs)}'
  shape = (n,) if len(axis_names) == 1 else None
  assert shape is not None, 'multi-axis meshes: pass explicit device grid'
  return Mesh(np.array(devs[:n]).reshape(shape), axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, P())


def row_sharded(mesh: Mesh, axis: str = 'data') -> NamedSharding:
  return NamedSharding(mesh, P(axis))


def replicate(tree, mesh: Mesh):
  """Place every leaf of ``tree`` replicated over ``mesh``; a leaf that
  already is comes back as it is. The train steps pass params and
  optimizer state through this on entry: jax keys its trace cache on
  the mesh a value was placed with, so the state ``tx.init`` builds
  (its step count is a scalar made without a mesh) and the state a step
  returns would otherwise compile the same program twice."""
  return jax.device_put(tree, replicated(mesh))
