"""Double-Radius Node Labeling (DRNL) for SEAL link prediction.

Reference: examples/seal_link_pred.py:107-136 computes DRNL per enclosing
subgraph with scipy shortest_path on the host. TPU formulation: the
subgraphs are padded static [N]-node / [E]-edge-slot graphs, so DRNL is a
pair of *edge-parallel BFS relaxations* (segment_min over edge slots
inside ``lax.while_loop``) — fully jittable and vmappable over a batch of
enclosing subgraphs, no host round-trip.

z(v) = 1 + min(d_src, d_dst) + (d//2) * (d//2 + d%2 - 1), d = d_src+d_dst,
with d_src computed on the graph minus dst (and vice versa), z(src) =
z(dst) = 1, unreachable nodes -> 0. Identical to the reference formula.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_INF = np.int32(1 << 29)   # numpy: importing this module touches no device


def bfs_distances(row: jax.Array, col: jax.Array, edge_mask: jax.Array,
                  num_nodes: int, source: jax.Array) -> jax.Array:
  """Unweighted shortest-path distances from ``source`` over masked,
  relabeled edge slots (directed relaxation; pass both directions for an
  undirected graph). Runs relaxation rounds until a fixpoint, so the
  result is exact for any diameter. Unreachable nodes hold a large
  sentinel (>= 1<<29).
  """
  seg = jnp.where(edge_mask, col, num_nodes)  # invalid slots -> overflow
  safe_row = jnp.clip(row, 0, num_nodes - 1)
  dist0 = jnp.where(jnp.arange(num_nodes) == source, 0, _INF)

  def body(carry):
    dist, _ = carry
    cand = jnp.where(edge_mask, jnp.take(dist, safe_row) + 1, _INF)
    relaxed = jax.ops.segment_min(cand, seg, num_nodes + 1)[:num_nodes]
    new = jnp.minimum(dist, relaxed)
    return new, jnp.any(new < dist)

  dist, _ = jax.lax.while_loop(lambda c: c[1], body, (dist0, True))
  return dist


def drnl_node_labeling(row: jax.Array, col: jax.Array,
                       edge_mask: jax.Array, num_nodes: int,
                       src: jax.Array, dst: jax.Array,
                       max_z: int) -> jax.Array:
  """DRNL labels for one padded enclosing subgraph; vmap for a batch.

  Args:
    row/col/edge_mask: relabeled padded edge slots (target link already
      removed by the caller, as the reference does).
    src/dst: the candidate link's labels (scalars).
    max_z: static clip bound for the label vocabulary (one-hot width is
      ``max_z + 1``).
  """
  keep_wo_dst = edge_mask & (row != dst) & (col != dst)
  keep_wo_src = edge_mask & (row != src) & (col != src)
  d_src = bfs_distances(row, col, keep_wo_dst, num_nodes, src)
  d_dst = bfs_distances(row, col, keep_wo_src, num_nodes, dst)
  reachable = (d_src < _INF) & (d_dst < _INF)
  d = d_src + d_dst
  half, rem = d // 2, d % 2
  z = 1 + jnp.minimum(d_src, d_dst) + half * (half + rem - 1)
  z = jnp.where(reachable, z, 0)
  idx = jnp.arange(num_nodes)
  z = jnp.where((idx == src) | (idx == dst), 1, z)
  return jnp.clip(z, 0, max_z).astype(jnp.int32)


def drnl_dense(adj: jax.Array, node_mask: jax.Array, max_z: int):
  """DRNL for a batch of ``L`` padded subgraphs given as dense blocks,
  inside one program: ``adj [L, S, S]`` bool (symmetric, the target link
  already out), the link's source in slot 0 and its destination in slot
  1. Both searches of every link advance together, one frontier product
  on the matrix unit a round (0/1 operands, which no matmul precision
  rounds, float32 sums: exact), with
  the other endpoint masked out of the frontier and of the reached set,
  until no search of the batch reaches a new node: exact for any
  diameter.

  Returns ``(z [L, S] int32, rounds, unreachable)``: labels clipped to
  ``max_z - 1``, 0 for a node one of the endpoints cannot reach and for
  a masked slot; the rounds that reached something (the largest finite
  distance in the batch); the live nodes labelled 0.
  """
  num_links, s = node_mask.shape
  a = adj.astype(jnp.float32)
  slot = jnp.arange(s)
  # column 0 searches from the source without the destination, column 1
  # from the destination without the source
  origin = jnp.stack([slot == 0, slot == 1], axis=-1)        # [S, 2]
  barred = jnp.stack([slot == 1, slot == 0], axis=-1)
  allowed = node_mask[:, :, None] & ~barred[None]
  dist0 = jnp.where(origin[None] & allowed, 0, _INF)

  def body(carry):
    dist, frontier, rounds, _ = carry
    hit = jnp.einsum('lij,ljc->lic', a, frontier.astype(jnp.float32)) > 0
    new = hit & allowed & (dist >= _INF)
    dist = jnp.where(new, rounds + 1, dist)
    more = new.any()
    return dist, new, rounds + more.astype(jnp.int32), more

  dist, _, rounds, _ = jax.lax.while_loop(
      lambda c: c[3], body,
      (dist0, dist0 == 0, jnp.zeros((), jnp.int32), True))
  d_src, d_dst = dist[..., 0], dist[..., 1]
  reachable = (d_src < _INF) & (d_dst < _INF)
  d = d_src + d_dst
  half, rem = d // 2, d % 2
  z = 1 + jnp.minimum(d_src, d_dst) + half * (half + rem - 1)
  z = jnp.where(reachable, z, 0)
  z = jnp.where(slot[None, :] < 2, 1, z)
  z = jnp.where(node_mask, jnp.clip(z, 0, max_z - 1), 0).astype(jnp.int32)
  unreachable = (node_mask & (z == 0)).sum(dtype=jnp.int32)
  return z, rounds, unreachable
