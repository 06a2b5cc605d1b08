"""What one link-prediction step of the configuration's GraphSAGE
requires of one chip, from the configuration alone (positive pairs a
step, fanout, widths): ``chipbench/flops.py``'s count for the ``4B``
endpoint seeds of ``B`` positive pairs and their ``B`` negatives, with the
configuration's ``out_dim`` where a classifier has its classes, and the
loss's own. Sampling the negatives and the neighbours, gather and
aggregation count nought.
"""
from chipbench import flops


def _as_node_cfg(cfg):
  return dict(cfg, num_classes=cfg['out_dim'])


def endpoint_seeds(batch):
  """Seeds of the hop loop: both endpoints of ``batch`` positive and of
  ``batch`` negative pairs, no dedup assumed."""
  return 4 * batch


def loss_flops(cfg, batch):
  """Forward FLOPs of the ``2 * batch`` dot products of ``out_dim``."""
  return 2 * batch * 2 * cfg['out_dim']


def step_flops(cfg, batch, fanout):
  """Forward and backward FLOPs one chip's batch requires."""
  return (flops.step_flops(_as_node_cfg(cfg), endpoint_seeds(batch), fanout)
          + 3 * loss_flops(cfg, batch))


def step_bytes(cfg, batch, fanout):
  """The least bytes one chip's step moves: ``flops.step_bytes`` over the
  endpoint seeds, and for the loss every endpoint's embedding read once
  and its gradient written once. float32 throughout."""
  return (flops.step_bytes(_as_node_cfg(cfg), endpoint_seeds(batch), fanout)
          + endpoint_seeds(batch) * cfg['out_dim'] * 4 * 2)


def least_step_seconds(cfg, batch, fanout, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, batch, fanout) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, batch, fanout) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
