"""DistLoader / DistNeighborLoader — epoch iteration over the SPMD
distributed sampler.

Reference: graphlearn_torch/python/distributed/dist_loader.py (451) +
dist_neighbor_loader.py. The reference's three deployment modes map as:

  * collocated  -> this loader: sampling runs in the same program as
    training consumes (one SPMD dispatch per batch).
  * mp (producer subprocesses + shm channel) -> the host prefetch
    channel (glt_tpu.channel): epoch seed planning happens on host
    threads that keep the device queue fed; device work is identical.
  * remote (server-client) -> glt_tpu.distributed.server.

Each iteration yields a *stacked* per-device batch dict ([P, ...] arrays,
shard-major) plus per-device validity — the shape DistTrainStep and DDP
consumers expect.

Fault tolerance: this collective loader's data plane is XLA all2all
(no sockets to fail independently — a lost mesh process is a
whole-program fault handled by the launcher). The rpc-fed loaders are
where graceful degradation lives: RemoteNeighborLoader drops a dead
server from the epoch instead of stalling (channel_loader.py), and
DistFeature cold fetchers fail over / degrade via
``resilient_cold_fetcher`` — see docs/fault_tolerance.md.
"""
from __future__ import annotations

from typing import Iterator, Optional, Sequence

import numpy as np

from ..sampler.base import SamplingConfig
from ..utils import as_numpy
from .dist_feature import DistFeature
from .dist_graph import DistGraph
from .dist_neighbor_sampler import DistNeighborSampler


class DistNeighborLoader:
  """Args:
    dist_graph / dist_feature: sharded stores.
    num_neighbors: fanouts.
    input_nodes: per-device seed lists — [P, n_p] array or list of P
      arrays (each device iterates its own partition's training ids,
      exactly like the reference's per-rank seed splits).
    batch_size: per-device batch size.

  bucket_cap sizing (pass to the DistFeature builder): DistFeature's
  default stays uncapped (0: [P, B] buckets), and any cap is exact (the
  overflow drains in-program). The round counts of a cap of
  ``slack * ceil(B / P)`` on an 8-device virtual mesh are in
  benchmarks/results/bench_bucket_drain_cpu.json
  (benchmarks/bench_bucket_drain.py): near-uniform ids 1 round at
  slack 2, zipf-skewed and hot-spot ids 2 rounds at slack 4; that
  grid's times are a CPU's and no timing of a chip. On the chip the
  one-type store (``parallel.ShardedFeature``) now defaults to
  ``ceil(B / P)`` on more than one shard: v5e 2x2, B = 937,984, one
  round a step, the step 201.6 -> 135.9 ms against [P, B] buckets
  (PERF.md §6, PR 34). DistFeature has the twin line and waits for the
  merge of the two stores (ROADMAP D5): no cell runs its exchange over
  more than one partition.
  """

  def __init__(self, dist_graph: DistGraph,
               num_neighbors: Sequence[int],
               input_nodes,
               dist_feature: Optional[DistFeature] = None,
               labels: Optional[np.ndarray] = None,
               batch_size: int = 512,
               shuffle: bool = False,
               drop_last: bool = False,
               with_edge: bool = False,
               seed: Optional[int] = None,
               rng: Optional[np.random.Generator] = None,
               edge_feature: Optional[DistFeature] = None):
    self.sampler = DistNeighborSampler(
        dist_graph, num_neighbors,
        with_edge=with_edge or edge_feature is not None, seed=seed)
    self.feature = dist_feature
    self.edge_feature = edge_feature
    self.labels = as_numpy(labels)
    self.n_dev = dist_graph.mesh.shape[dist_graph.axis]
    if isinstance(input_nodes, (list, tuple)):
      self.seeds = [as_numpy(s).astype(np.int64) for s in input_nodes]
    else:
      arr = as_numpy(input_nodes)
      self.seeds = [arr[p] for p in range(arr.shape[0])]
    assert len(self.seeds) == self.n_dev
    self.batch_size = int(batch_size)
    self.shuffle = shuffle
    self.drop_last = drop_last
    self.rng = rng or np.random.default_rng(0)

  def __len__(self):
    n = min(s.shape[0] for s in self.seeds)
    if self.drop_last:
      return n // self.batch_size
    return (n + self.batch_size - 1) // self.batch_size

  def __iter__(self) -> Iterator[dict]:
    orders = [(self.rng.permutation(s.shape[0]) if self.shuffle
               else np.arange(s.shape[0])) for s in self.seeds]
    steps = len(self)
    for it in range(steps):
      lo = it * self.batch_size
      seeds = np.zeros((self.n_dev, self.batch_size), np.int64)
      n_valid = np.zeros(self.n_dev, np.int32)
      for p in range(self.n_dev):
        sel = orders[p][lo:lo + self.batch_size]
        n_valid[p] = sel.shape[0]
        if sel.shape[0]:
          chunk = self.seeds[p][sel]
          seeds[p, :sel.shape[0]] = chunk
          seeds[p, sel.shape[0]:] = chunk[-1] if chunk.size else 0
      out = self.sampler.sample_from_nodes(seeds, n_valid)
      if self.feature is not None:
        import jax.numpy as jnp
        node = out['node'].reshape(-1)
        valid = (jnp.arange(out['node'].shape[1])[None, :]
                 < out['node_count'][:, None]).reshape(-1)
        x = self.feature.lookup(jnp.maximum(node, 0), valid)
        out['x'] = x.reshape(out['node'].shape + (-1,))
      if self.edge_feature is not None and 'edge' in out:
        self.edge_feature.collate_edge_attr(out)
      if self.labels is not None:
        out['y'] = self.labels[np.maximum(np.asarray(out['batch']), 0)]
      out['n_valid'] = n_valid
      yield out


#: Reference-name compatibility (distributed/dist_loader.py:46): the
#: reference's generic DistLoader base carries the collocated/mp/remote
#: mode dispatch that here lives directly in DistNeighborLoader (and
#: the channel loaders); node-seeded loading IS the generic entry.
DistLoader = DistNeighborLoader
