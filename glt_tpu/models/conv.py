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


class SAGEConv(nn.Module):
  """GraphSAGE convolution: W_root·x + W_nbr·aggr(x[children])."""
  out_features: int
  aggr: str = 'mean'
  use_bias: bool = True
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x: jax.Array, row: jax.Array, col: jax.Array,
               edge_mask: jax.Array, num_out=None,
               x_dst=None) -> jax.Array:
    n = x.shape[0]
    x_dst = x if x_dst is None else x_dst   # parents of another node type
    m = x_dst.shape[0] if num_out is None else num_out
    safe_row = jnp.clip(row, 0, n - 1)
    msgs = jnp.take(x, safe_row, axis=0)
    agg = _AGGRS[self.aggr](
        msgs, jnp.clip(col, 0, m - 1),
        edge_mask & (row >= 0) & (col >= 0) & (col < m), m)
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
  """
  out_features: int
  heads: int = 1
  concat: bool = True
  negative_slope: float = 0.2
  param_dtype: jnp.dtype = jnp.float32

  @nn.compact
  def __call__(self, x, row, col, edge_mask, num_out=None, x_dst=None):
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
