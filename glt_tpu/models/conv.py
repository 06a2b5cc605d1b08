"""GNN convolution layers (flax linen) over padded edge lists.

The reference trains standard PyG convs (SAGEConv/GATConv/RGCN/HGT —
examples/, examples/igbh/rgnn.py). These are from-scratch flax
implementations of the same math, designed for the framework's padded
static-shape batches: invalid edge slots are routed to a sacrificial
segment so aggregation is one masked segment_sum — no dynamic shapes, and
the feature matmuls stay dense on the MXU.

Every convolution takes ``num_out``, a static int: it computes output rows
for the first ``num_out`` nodes only (``None``: all of ``x``). Children are
still read from every row of ``x``; an edge whose parent lies beyond
``num_out`` is masked. models/sage.py passes the hop prefix a later layer
reads (``Batch.node_hop_offsets``).

``SAGEConv`` and ``GATConv`` also take ``groups``, static ``(offset, S,
K)`` triples: the producer's promise (``Batch.hop_fanouts``, given by
ops/pipeline.py::hop_fanouts and handed on by models/sage.py; per
relation ``HeteroBatch.hop_fanouts_dict``, given by
ops/pipeline.py::hetero_hop_fanouts and handed on by models/rgnn.py)
that the edge slots from ``offset`` on are ``S`` groups of ``K`` adjacent
slots with one parent each, and that a parent heads one group with a
live slot. A parent's children are then summed by
:func:`grouped_aggregate`: a take, a reshape and a masked reduce over
the fanout axis, and one placing of the ``S`` group results at their
parents, where the segment path scatter-adds every slot (on the
benchmark's cells 936,960 slots into 169,984 rows at conv0; the slots,
not the rows, bound it); ``GATConv`` computes its softmax and its
weighted sum over the same axis, in the same layout. Without ``groups``
the segment paths compute what they always did, bit for bit; ``GCNConv``
knows nothing of groups.
"""
from __future__ import annotations


import flax.linen as nn
import jax
import jax.numpy as jnp


def segment_mean(msgs: jax.Array, targets: jax.Array, mask: jax.Array,
                 num_segments: int) -> jax.Array:
  """Masked mean aggregation: invalid slots go to segment num_segments."""
  seg = jnp.where(mask, targets, num_segments)
  total = jax.ops.segment_sum(
      jnp.where(mask[:, None], msgs, 0.0), seg, num_segments + 1)
  cnt = jax.ops.segment_sum(mask.astype(msgs.dtype), seg, num_segments + 1)
  return total[:num_segments] / jnp.maximum(cnt[:num_segments, None], 1.0)


def segment_sum_masked(msgs, targets, mask, num_segments):
  seg = jnp.where(mask, targets, num_segments)
  return jax.ops.segment_sum(
      jnp.where(mask[:, None], msgs, 0.0), seg, num_segments + 1
  )[:num_segments]


def segment_max_masked(msgs, targets, mask, num_segments):
  seg = jnp.where(mask, targets, num_segments)
  out = jax.ops.segment_max(
      jnp.where(mask[:, None], msgs, -jnp.inf), seg, num_segments + 1)
  out = out[:num_segments]
  return jnp.where(jnp.isfinite(out), out, 0.0)


_AGGRS = {
    'mean': segment_mean,
    'sum': segment_sum_masked,
    'max': segment_max_masked,
}


def _group_sum(msgs, live):
  return jnp.where(live[:, :, None], msgs, 0.0).sum(axis=0)


def _group_mean(msgs, live):
  cnt = live.sum(axis=0).astype(msgs.dtype)
  return _group_sum(msgs, live) / jnp.maximum(cnt[:, None], 1.0)


def _group_max(msgs, live):
  out = jnp.where(live[:, :, None], msgs, -jnp.inf).max(axis=0)
  return jnp.where(jnp.isfinite(out), out, 0.0)


_GROUP_AGGRS = {
    'mean': _group_mean,
    'sum': _group_sum,
    'max': _group_max,
}


def grouped_aggregate(aggr: str, x: jax.Array, row: jax.Array,
                      col: jax.Array, ok: jax.Array, groups,
                      num_segments: int) -> jax.Array:
  """``_AGGRS[aggr]`` over edge slots that are parent-major: ``groups``
  holds static ``(offset, S, K)`` triples, ``S`` groups of ``K`` adjacent
  slots from ``offset`` on, ``col`` one value over a group.

  The layout is what the v5e measured best (PERF.md, section 6, PR 30).
  A hop's child indices are transposed to ``[K, S]``, so that the reduce
  runs over the leading axis (``K`` additions of ``[S, D]`` slabs; with
  ``K`` of 5, 10 or 15 on the second-minor axis the messages were
  relaid, 2 ms at conv0), and taken as one flat vector a hop (XLA then
  joins the hops' transposes into one scatter-add; one take for all
  hops paid a copy of each hop's slice). A masked slot reads the row of
  its own position, under the mask: with every masked slot on row 0 the
  same take ran 30 % slower. A mean divides by its group's own count,
  since a parent heads one live group. What is left of the scatter
  places the group results at their parents, once for all hops: unique
  rows, because a group with no live slot (every slot masked, a parent
  of -1 or beyond ``num_segments``) is sent past the end, each to an
  index of its own, and dropped."""
  n = x.shape[0]
  reduce = _GROUP_AGGRS[aggr]
  vals, parents, kept = [], [], []
  for group in groups:
    _, s, k = group
    idx, live = _group_children(row, ok, group, n)
    msgs = jnp.take(x, idx, axis=0, mode='clip')
    vals.append(reduce(msgs.reshape(k, s, -1), live))
    parents.append(_group_parents(col, group))
    kept.append(live.any(axis=0))
  vals, parents, kept = (jnp.concatenate(v) for v in (vals, parents, kept))
  return _place_groups(vals, parents, kept, num_segments)


def _group_children(row, ok, group, n):
  """One hop block's child indices, flat in ``[K, S]`` order, and its
  ``[K, S]`` mask; a masked slot reads the row of its own position."""
  off, s, k = group
  end = off + s * k
  idx = row[off:end].reshape(s, k).T.reshape(-1)
  live = ok[off:end].reshape(s, k).T
  spread = jnp.arange(s * k, dtype=idx.dtype) % n
  return jnp.where(live.reshape(-1), idx, spread), live


def _group_parents(col, group):
  off, s, k = group
  # not col[off:end:k]: a strided slice of a 1-D array is 1.1 ms here
  return col[off:off + s * k].reshape(s, k)[:, 0]


def _beyond(parents, num_segments):
  return num_segments + jnp.arange(parents.shape[0], dtype=parents.dtype)


def _group_rows(parents, kept, num_segments):
  """The row of each group's result: its parent, or for a group with no
  live slot an index of its own past the end, so that the rows are
  unique and such a group is dropped."""
  return jnp.where(kept, parents, _beyond(parents, num_segments))


def _place_groups(vals, parents, kept, num_segments):
  # :func:`_group_rows`, written out around the zeros: the order of
  # these ops is the order of the SAGE cells' StableHLO since PR 30
  beyond = _beyond(parents, num_segments)
  return jnp.zeros((num_segments,) + vals.shape[1:], vals.dtype).at[
      jnp.where(kept, parents, beyond)].set(
          vals, mode='drop', unique_indices=True)


def grouped_attention(proj, logit_dst, att_src, row, col, ok, groups,
                      negative_slope):
  """``GATConv`` over edge slots that are parent-major (``groups`` as
  :func:`grouped_aggregate`'s): the softmax over a parent's children
  and their weighted sum as masked reduces over the fanout axis, a hop
  block at a time in that function's layout. ``proj``: the children's
  projected rows, ``[n, heads, out]``; ``logit_dst``: the parents'
  logits, ``[m, heads]``; returns ``[m, heads, out]``.

  A block's projected rows are taken once, and the children's logits
  come from the taken rows: the same products row by row, and no
  ``[E, heads]`` take whose transpose scatter-adds every slot a second
  time (11 ms a relation of 250,560 slots on the v5e: PERF.md, section
  6, PR 32). A group's result is placed at its parent once; the
  parents' logits are read, and their gradient placed, by the same
  unique rows."""
  (n, h, f), m = proj.shape, logit_dst.shape[0]
  blocks, parents, kept = [], [], []
  with jax.named_scope('aggregate'):
    # rows of the 2-D projection: taken from ``[n, heads, out]`` the
    # whole projection was relaid first (3 ms a relation and pass) and
    # the take's transposed scatter-add ran at half the speed
    flat = proj.reshape(n, h * f)
    for group in groups:
      _, s, k = group
      idx, live = _group_children(row, ok, group, n)
      src = jnp.take(flat, idx, axis=0, mode='clip')
      blocks.append((src.reshape(k, s, h, f), live[:, :, None]))
      parents.append(_group_parents(col, group))
      kept.append(live.any(axis=0))
    parents, kept = jnp.concatenate(parents), jnp.concatenate(kept)
  with jax.named_scope('attention'):
    parent_logit = logit_dst.at[_group_rows(parents, kept, m)].get(
        mode='fill', fill_value=0.0, unique_indices=True)  # [sum S, h]
    alphas, at = [], 0
    for src, live in blocks:
      s = src.shape[1]
      logit = nn.leaky_relu(
          (src * att_src).sum(-1) + parent_logit[at:at + s],
          negative_slope=negative_slope)                   # [K, S, h]
      at += s
      # numerically-stable masked softmax over the fanout axis
      top = jnp.where(live, logit, -jnp.inf).max(axis=0)
      top = jnp.where(jnp.isfinite(top), top, 0.0)
      z = jnp.where(live, jnp.exp(logit - top), 0.0)
      alphas.append(z / jnp.maximum(z.sum(axis=0), 1e-16))
  with jax.named_scope('aggregate'):
    vals = [(src * alpha[:, :, :, None]).sum(axis=0)
            for (src, _), alpha in zip(blocks, alphas)]
    return _place_groups(jnp.concatenate(vals), parents, kept, m)


class SAGEConv(nn.Module):
  """GraphSAGE convolution: W_root·x + W_nbr·aggr(x[children]).

  ``groups`` (static, ``None``: no promise): ``(offset, S, K)`` triples
  that cover the edge slots given, the producer's promise that they are
  parent-major (module docstring). With it the aggregation is
  :func:`grouped_aggregate`, without it the segment path; the same
  mathematics, and only the order of a parent's ``K`` additions
  differs."""
  out_features: int
  aggr: str = 'mean'
  use_bias: bool = True
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jax.Array, row: jax.Array, col: jax.Array,
               edge_mask: jax.Array, num_out=None,
               x_dst=None, groups=None) -> jax.Array:
    n = x.shape[0]
    x_dst = x if x_dst is None else x_dst   # parents of another node type
    m = x_dst.shape[0] if num_out is None else num_out
    ok = edge_mask & (row >= 0) & (col >= 0) & (col < m)
    if groups:
      agg = grouped_aggregate(self.aggr, x, row, col, ok, groups, m)
    else:
      msgs = jnp.take(x, jnp.clip(row, 0, n - 1), axis=0)
      agg = _AGGRS[self.aggr](msgs, jnp.clip(col, 0, m - 1), ok, m)
    lin_nbr = nn.Dense(self.out_features, use_bias=False,
                       param_dtype=self.param_dtype, name='lin_nbr')
    lin_root = nn.Dense(self.out_features, use_bias=self.use_bias,
                        param_dtype=self.param_dtype, name='lin_root')
    return lin_root(x_dst[:m]) + lin_nbr(agg)


class GATConv(nn.Module):
  """Graph attention (GATv1): per-edge attention logits softmax-normalized
  over each parent's incoming sampled edges, multi-head.

  ``x`` holds the rows that edges read as children; ``x_dst`` the parents'
  rows where they are another node type's (a typed relation; ``None``: the
  parents are rows of ``x``). Only children are projected to ``heads x
  out``: a parent's logit is ``x_dst @ (W . att_dst)``, an ``[m, heads]``
  product, which equals ``((x_dst @ W) * att_dst).sum(-1)``.

  ``groups`` (static, ``None``: no promise): as ``SAGEConv``'s. With it
  the per-parent maximum, the softmax's denominator and the weighted sum
  are reduces over the fanout axis (:func:`grouped_attention`), without it
  ``segment_max`` and ``segment_sum`` over every slot; the same
  mathematics, and only the order of a parent's ``K`` additions differs.
  """
  out_features: int
  heads: int = 1
  concat: bool = True
  negative_slope: float = 0.2
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x, row, col, edge_mask, num_out=None, x_dst=None,
               groups=None):
    n = x.shape[0]
    x_dst = x if x_dst is None else x_dst
    m = x_dst.shape[0] if num_out is None else num_out
    h, f = self.heads, self.out_features
    ok = edge_mask & (row >= 0) & (row < n) & (col >= 0) & (col < m)
    # children are read from the projection, so it covers every row
    dense = nn.Dense(h * f, use_bias=False, param_dtype=self.param_dtype,
                     name='proj')
    proj = dense(x).reshape(n, h, f)
    att_src = self.param('att_src', nn.initializers.glorot_uniform(),
                         (h, f), self.param_dtype)
    att_dst = self.param('att_dst', nn.initializers.glorot_uniform(),
                         (h, f), self.param_dtype)
    with jax.named_scope('attention'):
      kernel = dense.variables['params']['kernel'].reshape(-1, h, f)
      w_dst = (kernel * att_dst).sum(-1)                    # [in, h]
      logit_dst = x_dst[:m].astype(proj.dtype) @ w_dst        # [m, h]
    if groups:
      out = grouped_attention(proj, logit_dst, att_src, row, col, ok,
                              groups, self.negative_slope)
      return out.reshape(m, h * f) if self.concat else out.mean(axis=1)
    with jax.named_scope('attention'):
      logit_src = (proj * att_src).sum(-1)                  # [n, h]
      seg = jnp.where(ok, col, m)
      logit = nn.leaky_relu(
          jnp.take(logit_src, jnp.clip(row, 0, n - 1), axis=0)
          + jnp.take(logit_dst, jnp.clip(col, 0, m - 1), axis=0),
          negative_slope=self.negative_slope)               # [E, h]
      # numerically-stable masked segment softmax over each parent
      seg_max = jax.ops.segment_max(
          jnp.where(ok[:, None], logit, -jnp.inf), seg, m + 1)
      seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
      z = jnp.exp(logit - seg_max[jnp.clip(seg, 0, m)])
      z = jnp.where(ok[:, None], z, 0.0)
      denom = jax.ops.segment_sum(z, seg, m + 1)
      alpha = z / jnp.maximum(denom[jnp.clip(seg, 0, m)], 1e-16)  # [E, h]
    with jax.named_scope('aggregate'):
      src = jnp.take(proj, jnp.clip(row, 0, n - 1), axis=0)   # [E, h, f]
      out = jax.ops.segment_sum(
          src * alpha[:, :, None], seg, m + 1)[:m]          # [m, h, f]
    if self.concat:
      return out.reshape(m, h * f)
    return out.mean(axis=1)


class GCNConv(nn.Module):
  """GCN layer with symmetric degree normalization computed on the sampled
  subgraph (masked)."""
  out_features: int
  use_bias: bool = True
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x, row, col, edge_mask, num_out=None):
    n = x.shape[0]
    m = n if num_out is None else num_out
    ok = edge_mask & (row >= 0) & (col >= 0) & (col < m)
    # children are read from h, so it covers every row
    h = nn.Dense(self.out_features, use_bias=False,
                 param_dtype=self.param_dtype, name='lin')(x)
    ones = ok.astype(h.dtype)
    seg_in = jnp.where(ok, col, m)
    # PyG GCN semantics: both endpoints are normalized by the in-degree
    # of the self-loop-augmented graph (deg_in includes the +1 loop), and
    # the self-loop term below uses 1/deg_in — models ported from the
    # reference match numerically.
    deg_in = jax.ops.segment_sum(ones, seg_in, m + 1)[:m] + 1.0
    # a child beyond num_out is the parent of no edge: its self-loop's 1
    deg_row = jnp.where(row < m,
                        jnp.take(deg_in, jnp.clip(row, 0, m - 1)), 1.0)
    norm = (deg_row ** -0.5
            * jnp.take(deg_in, jnp.clip(col, 0, m - 1)) ** -0.5)
    msgs = jnp.take(h, jnp.clip(row, 0, n - 1), axis=0) * norm[:, None]
    agg = jax.ops.segment_sum(
        jnp.where(ok[:, None], msgs, 0.0), seg_in, m + 1)[:m]
    # self-loop term with its own normalization
    agg = agg + h[:m] / deg_in[:, None]
    if self.use_bias:
      agg = agg + self.param('bias', nn.initializers.zeros,
                             (self.out_features,), self.param_dtype)
    return agg


class DenseGCNConv(nn.Module):
  """:class:`GCNConv` over a batch of small graphs given as dense blocks:
  ``x [L, S, F]``, ``adj [L, S, S]`` float32 0/1, symmetric, no
  self-loops. PyG's ``GCNConv``: self-loops added, both ends normalised
  by the degree with its loop, ``out = D^-1/2 (A + I) D^-1/2 (x W) + b``.
  The parameters are ``GCNConv``'s (``lin/kernel``, ``bias``), so one
  tree serves both. The neighbours' sum is a product with the block on
  the matrix unit at ``highest`` precision: it stands where PyG
  scatter-adds float32 rows, so it rounds nothing (the linear map rounds
  as every linear map of the step does)."""
  out_features: int
  use_bias: bool = True
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x, adj):
    h = nn.Dense(self.out_features, use_bias=False,
                 param_dtype=self.param_dtype, name='lin')(x)
    deg = adj.sum(-1) + 1.0
    inv = jax.lax.rsqrt(deg)[..., None]
    out = jnp.einsum('lij,ljc->lic', adj, h * inv,
                     precision=jax.lax.Precision.HIGHEST) * inv
    out = out + h / deg[..., None]
    if self.use_bias:
      out = out + self.param('bias', nn.initializers.zeros,
                             (self.out_features,), self.param_dtype)
    return out
