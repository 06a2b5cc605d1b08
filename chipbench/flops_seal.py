"""What one SEAL step of the configuration's DGCNN requires of one chip,
from the configuration and the traffic alone (links a step, fanout,
widths): the same number whatever implements the step.

A link's enclosing subgraph holds its two ends and ``fanout`` neighbours
of each, no dedup assumed: ``2 + 2 * fanout`` nodes, the node slots. Every
GCN layer maps every node (``in x out`` at 2 FLOPs a multiply-add), the
first 1-D convolution maps the ``k`` pooled nodes, the second slides 5
taps over the ``k // 2`` pooled positions, the MLP maps the flattened map.
Backward counts twice forward. Sampling the negatives and the neighbours,
the extraction, DRNL, the gather, a layer's sum over neighbours (a sum
over the subgraph's edges, whose count the recipe does not fix) and the
sort count nought.
"""


def links(traffic):
  """Graphs a chip's step holds: a positive and its negative a seed."""
  return 2 * traffic['batch_per_chip']


def nodes_a_link(traffic):
  return 2 + 2 * traffic['fanout'][0]


def _gcn_dims(cfg):
  hidden = cfg['hidden_dim']
  return ([hidden + cfg['feature_dim']] + [hidden] * cfg['num_gcn_layers']
          + [cfg['sort_key_channels']])


def _head(cfg):
  """``(pooled width, positions after the pool, positions after the
  second convolution)``."""
  width = cfg['hidden_dim'] * cfg['num_gcn_layers'] + cfg['sort_key_channels']
  half = cfg['sortpool_k'] // 2
  return width, half, half - cfg['conv1d_kernels'][1] + 1


def link_flops(cfg, traffic):
  """Forward FLOPs of one link's graph."""
  dims = _gcn_dims(cfg)
  c1, c2 = cfg['conv1d_channels']
  width, _, last = _head(cfg)
  gcn = nodes_a_link(traffic) * sum(2 * a * b
                                    for a, b in zip(dims[:-1], dims[1:]))
  head = (cfg['sortpool_k'] * 2 * width * c1
          + last * 2 * cfg['conv1d_kernels'][1] * c1 * c2
          + 2 * last * c2 * cfg['mlp_hidden'] + 2 * cfg['mlp_hidden'])
  return gcn + head


def step_flops(cfg, traffic):
  """Forward and backward FLOPs one chip's batch requires."""
  return 3 * links(traffic) * link_flops(cfg, traffic)


def num_params(cfg):
  dims = _gcn_dims(cfg)
  c1, c2 = cfg['conv1d_channels']
  width, _, last = _head(cfg)
  return (cfg['max_z'] * cfg['hidden_dim']
          + sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
          + width * c1 + c1 + cfg['conv1d_kernels'][1] * c1 * c2 + c2
          + last * c2 * cfg['mlp_hidden'] + cfg['mlp_hidden']
          + cfg['mlp_hidden'] + 1)


def step_bytes(cfg, traffic):
  """The least bytes one chip's step moves: every node slot's feature
  row read from the table and written once, its label's embedding row
  read once and its gradient written once, each GCN layer's output row
  written once forward and read once backward, parameters and Adam's two
  moments read and written once. float32 throughout."""
  dims = _gcn_dims(cfg)
  slots = links(traffic) * nodes_a_link(traffic)
  gather = slots * cfg['feature_dim'] * 4 * 2
  labels = slots * cfg['hidden_dim'] * 4 * 2
  acts = slots * sum(dims[1:]) * 4 * 2
  return gather + labels + acts + num_params(cfg) * 4 * 3 * 2


def least_step_seconds(cfg, traffic, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, traffic) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, traffic) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
