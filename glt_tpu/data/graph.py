"""Device-resident graph storage.

Reference: graphlearn_torch/python/data/graph.py:184-306 (py Graph binding a
native CSR container, include/graph.h:30-133). The reference's residency
modes CPU / DMA / ZERO_COPY map to:

  * ``GraphMode.HBM``  -- indptr/indices/(eids,weights) live as jax arrays in
    TPU HBM (the DMA analogue, graph.cu:69-80).
  * ``GraphMode.HOST`` -- arrays stay as numpy in host RAM; jitted code
    receives gathered slices via the loader's host stage (the ZERO_COPY/UVA
    analogue for beyond-HBM topologies).

There is no CUDA-IPC equivalent (data/graph.py:257-306): under SPMD a single
jax global array is already visible to every participating device, so the
share-via-handle machinery is unnecessary by design.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from ..typing import GraphMode
from .topology import Topology


class Graph:
  """Binds a host :class:`Topology` to device arrays, lazily.

  Lazy-init mirrors the reference (data/graph.py:219-252): the device copy
  happens on first access so partition loading can build many Graph objects
  cheaply.
  """

  def __init__(self, topo: Topology, mode: GraphMode = GraphMode.HBM,
               device: Optional[jax.Device] = None):
    if isinstance(mode, str):
      mode = GraphMode(mode.upper())
    self.topo = topo
    self.mode = mode
    self.device = device
    self._indptr = None
    self._indices = None
    self._edge_ids = None
    self._edge_weights = None
    self._initialized = False

  # producers currently ship a dataset_builder callable rather than
  # Graph objects, but mp channel payloads / checkpoints may pickle a
  # Graph directly. Device arrays are dropped: they are lazily rebuilt,
  # and a fresh process must re-place them on its own devices anyway.
  def __getstate__(self):
    state = self.__dict__.copy()
    if self.mode == GraphMode.HBM:
      state['_indptr'] = state['_indices'] = None
      state['_edge_ids'] = state['_edge_weights'] = None
      state['_initialized'] = False
    return state

  # -- lazy init ---------------------------------------------------------

  def lazy_init(self) -> None:
    if self._initialized:
      return
    if self.mode == GraphMode.HBM:
      put = lambda a: (jax.device_put(a, self.device)
                       if a is not None else None)
    else:  # HOST: keep numpy; jnp ops on host stage use them directly
      put = lambda a: a
    # indptr is int64 on host (billion-edge safe); narrow for device
    # placement when the edge count fits int32.
    indptr = self.topo.indptr
    if self.num_edges < np.iinfo(np.int32).max:
      indptr = indptr.astype(np.int32, copy=False)
    self._indptr = put(indptr)
    self._indices = put(self.topo.indices)
    self._edge_ids = put(self.topo.edge_ids)
    self._edge_weights = put(self.topo.edge_weights)
    self._initialized = True

  @property
  def indptr(self):
    self.lazy_init()
    return self._indptr

  @property
  def indices(self):
    self.lazy_init()
    return self._indices

  @property
  def edge_ids(self):
    self.lazy_init()
    return self._edge_ids

  @property
  def edge_weights(self):
    self.lazy_init()
    return self._edge_weights

  # -- probes (reference graph.cu:30-48 LookupDegreeKernel) ---------------

  @property
  def num_nodes(self) -> int:
    return self.topo.num_nodes

  @property
  def num_edges(self) -> int:
    return self.topo.num_edges

  @property
  def layout(self) -> str:
    return self.topo.layout

  def degree(self, ids) -> np.ndarray:
    ids = np.asarray(ids)
    return self.topo.indptr[ids + 1] - self.topo.indptr[ids]
