"""DistNeighborSampler — multi-hop sampling over sharded topology.

Reference: graphlearn_torch/python/distributed/dist_neighbor_sampler.py
(96-807): an asyncio engine that splits each hop's frontier by partition
book, samples locally, RPCs remote partitions, and stitches
(_sample_one_hop, :616-687). The TPU-native design collapses all of that
into collectives (SURVEY.md §7 "One SPMD program instead of rpc actors"):

    owner = node_pb[frontier]            # the PB routing
    all_to_all(requests)                 # the rpc fan-out
    local XLA sample on each owner
    all_to_all(responses)                # the rpc returns
    positional unbucket                  # the stitch

and the hop loop + dedup run unchanged from ops.pipeline — the same
`multihop_sample` the single-device engine uses, with the one-hop
function swapped for the collective version. No event loop, no
concurrency semaphore: latency hiding is XLA's async collectives.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.pipeline import edge_hop_offsets, multihop_sample
from ..ops.sample import sample_neighbors
from ..parallel.collectives import all_to_all, bucket_by_owner, unbucket
from ..utils import as_numpy
from ..utils.rng import RandomSeedManager
from .dist_graph import DistGraph


def make_dist_one_hop(graph_shards: Dict[str, jax.Array], num_nodes: int,
                      n_parts: int, rows_max: int, axis: str,
                      with_weight: bool = False,
                      max_weighted_degree: int = 0):
  """Build the in-shard one-hop closure over sharded CSR blocks.

  graph_shards: dict with this device's 'indptr' [R+1], 'indices' [E],
  'edge_ids' [E], 'local_row' [N], replicated 'node_pb' [N] and (for the
  weighted path) 'edge_weights' [E].
  """
  indptr = graph_shards['indptr']
  indices = graph_shards['indices']
  eids = graph_shards['edge_ids']
  local_row = graph_shards['local_row']
  node_pb = graph_shards['node_pb']
  weights = graph_shards.get('edge_weights')

  def one_hop(ids, fanout, key, mask):
    f = ids.shape[0]
    width = abs(fanout)  # negative = full-neighborhood hop, window |k|
    if n_parts == 1:
      # one partition owns every row: each request is served in its own
      # slot, with nothing to bucket, exchange or stitch (the bucketing
      # is a running count an owner and a scatter, the stitch three
      # gathers a hop)
      flat = jnp.where(mask, ids.astype(jnp.int32), -1)
    else:
      owner = jnp.take(node_pb, jnp.clip(ids, 0, num_nodes - 1),
                       mode='clip')
      owner = jnp.where(mask, owner, n_parts)
      req, meta = bucket_by_owner(ids.astype(jnp.int32), owner, n_parts)
      req_in = all_to_all(req, axis)                     # [P, F]
      flat = req_in.reshape(-1)
    lrow = jnp.take(local_row, jnp.clip(flat, 0, num_nodes - 1),
                    mode='clip')
    ok = (flat >= 0) & (lrow >= 0)
    # every device serves with the same folded key stream: fold by the
    # serving device so remote requests get independent randomness
    serve_key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    if fanout < 0:
      from ..ops.sample import sample_full_neighbors
      out = sample_full_neighbors(
          indptr, indices, jnp.clip(lrow, 0, rows_max - 1), width,
          seed_mask=ok, edge_ids=eids)
    elif with_weight and weights is not None:
      from ..ops.sample import sample_neighbors_weighted
      out = sample_neighbors_weighted(
          indptr, indices, weights, jnp.clip(lrow, 0, rows_max - 1),
          fanout, serve_key,
          max_degree=max(max_weighted_degree, fanout),
          seed_mask=ok, edge_ids=eids)
    else:
      out = sample_neighbors(indptr, indices,
                             jnp.clip(lrow, 0, rows_max - 1), fanout,
                             serve_key, seed_mask=ok, edge_ids=eids)
    from ..ops.sample import NeighborOutput
    if n_parts == 1:
      return NeighborOutput(nbrs=out.nbrs, mask=out.mask & mask[:, None],
                            eids=out.eids, rows_read=out.rows_read)
    resp_nbrs = all_to_all(out.nbrs.reshape(n_parts, f, width), axis)
    resp_mask = all_to_all(out.mask.reshape(n_parts, f, width), axis)
    resp_eids = all_to_all(out.eids.reshape(n_parts, f, width), axis)
    nbrs = unbucket(resp_nbrs, meta, n_parts)
    nmask = unbucket(resp_mask, meta, n_parts, invalid_value=False)
    out_eids = unbucket(resp_eids, meta, n_parts, invalid_value=-1)
    return NeighborOutput(nbrs=nbrs, mask=nmask & mask[:, None],
                          eids=out_eids, rows_read=out.rows_read)

  return one_hop


class DistNeighborSampler:
  """Drives SPMD sampling over a DistGraph; one seed batch per device.

  The jitted program takes [P * B] shard-major seeds and returns stacked
  per-device SamplerOutput payloads [P, ...].
  """

  def __init__(self, dist_graph: DistGraph, num_neighbors: Sequence[int],
               with_edge: bool = False, with_weight: bool = False,
               max_weighted_degree: Optional[int] = None,
               seed: Optional[int] = None,
               full_neighbor_cap: Optional[int] = None):
    self.g = dist_graph
    self.num_neighbors = []
    for f in num_neighbors:
      f = int(f)
      if f == -1:  # full neighborhood: resolve to a static -window
        cap = full_neighbor_cap or getattr(dist_graph, 'max_degree', 0)
        assert cap > 0, ('fanout=-1 needs full_neighbor_cap or a '
                         'DistGraph with a known max_degree')
        f = -int(cap)
      else:
        assert f > 0, f'fanout must be positive or -1, got {f}'
      self.num_neighbors.append(f)
    self.with_edge = with_edge
    self.with_weight = with_weight and dist_graph.edge_weights is not None
    self.max_weighted_degree = (max_weighted_degree
                                or getattr(dist_graph, 'max_degree', 1))
    self.mesh = dist_graph.mesh
    self.axis = dist_graph.axis
    from ..utils.rng import make_key
    self._base_key = make_key(
        seed if seed is not None
        else RandomSeedManager.getInstance().getSeed())
    self._step = 0
    self._fn_cache = {}

  def _next_key(self):
    self._step += 1
    return jax.random.fold_in(self._base_key, self._step)

  def _build(self, batch_size: int):
    g = self.g
    n_parts = g.num_partitions
    axis = self.axis
    fanouts = self.num_neighbors
    with_edge = self.with_edge

    def device_fn(indptr, indices, eids, weights, local_row, node_pb,
                  seeds, n_valid, key):
      shards = dict(indptr=indptr[0], indices=indices[0],
                    edge_ids=eids[0], local_row=local_row[0],
                    node_pb=node_pb)
      if weights is not None:
        shards['edge_weights'] = weights[0]
      one_hop = make_dist_one_hop(
          shards, g.num_nodes, n_parts, g.max_rows, axis,
          with_weight=self.with_weight,
          max_weighted_degree=self.max_weighted_degree)
      my_key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))
      out = multihop_sample(one_hop, seeds, n_valid[0], fanouts, my_key,
                            with_edge=with_edge)
      return {k: v[None] for k, v in out.items()}

    sp = P(self.axis)
    w_spec = sp if g.edge_weights is not None else None
    fn = jax.shard_map(
        device_fn, mesh=self.mesh,
        in_specs=(sp, sp, sp, w_spec, sp, P(), sp, sp, sp),
        out_specs={k: sp for k in self._out_keys()},
        check_vma=False)

    # graph arrays enter as ARGUMENTS (closure capture would embed them
    # as jit constants, which cannot span processes in multi-host runs)
    step = jax.jit(fn)

    def run(seeds, n_valid, keys):
      return step(g.indptr, g.indices, g.edge_ids, g.edge_weights,
                  g.local_row, g.node_pb, seeds, n_valid, keys)

    return run

  def _out_keys(self):
    keys = ['node', 'node_count', 'row', 'col', 'edge_mask', 'batch',
            'seed_labels', 'seed_count', 'num_sampled_nodes',
            'num_sampled_edges', 'hop_rows_read']
    if self.with_edge:
      keys.append('edge')
    return keys

  def sample_from_nodes(self, seeds_per_device: np.ndarray,
                        n_valid_per_device=None, key=None):
    """seeds_per_device: [P, B] or [P*B] shard-major. Returns a dict of
    stacked arrays [P, ...] (one SamplerOutput per device)."""
    seeds = as_numpy(seeds_per_device)
    n_dev = self.mesh.shape[self.axis]
    if seeds.ndim == 2:
      seeds = seeds.reshape(-1)
    batch_size = seeds.shape[0] // n_dev
    if n_valid_per_device is None:
      n_valid_per_device = np.full(n_dev, batch_size, np.int32)
    if batch_size not in self._fn_cache:
      self._fn_cache[batch_size] = self._build(batch_size)
    if key is None:
      key = self._next_key()
    keys = jax.random.split(key, n_dev)
    shard = NamedSharding(self.mesh, P(self.axis))
    out = self._fn_cache[batch_size](
        jax.device_put(jnp.asarray(seeds, jnp.int32), shard),
        jax.device_put(jnp.asarray(n_valid_per_device, jnp.int32), shard),
        keys)
    out['edge_hop_offsets'] = edge_hop_offsets(batch_size, fanouts=
                                               self.num_neighbors)
    return out
