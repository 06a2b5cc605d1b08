"""What one training step of the configuration's GraphSAGE requires of
one chip, from the configuration alone (batch, fanout, widths): the same
number whatever implements the step.

Layer ``l`` of ``L`` (from 1) needs output rows for the nodes within
``L - l`` hops of the seeds: ``batch * sum_{h <= L-l} prod_{j <= h}
fanout_j`` rows, the reference's ``trim_to_layer`` count with no dedup
assumed. Each row costs two ``in x out`` matmul rows (root and neighbour
weights) at 2 FLOPs a multiply-add. Backward counts twice forward.
Sampling, gather and aggregation count nought.
"""


def _dims(cfg):
  return ([cfg['feature_dim']] + [cfg['hidden_dim']] * (cfg['num_layers'] - 1)
          + [cfg['num_classes']])


def hop_slots(batch, fanout):
  """[seeds, slots of hop 1, ..., slots of hop L]."""
  hop = [batch]
  for k in fanout:
    hop.append(hop[-1] * k)
  return hop


def rows_needed(batch, fanout):
  """[rows of layer 1, ..., rows of layer L]."""
  hop, n = hop_slots(batch, fanout), len(fanout)
  return [sum(hop[:n - l + 1]) for l in range(1, n + 1)]


def budget_rows(batch, fanout):
  """Rows of the padded node budget: seeds and every hop's slots."""
  return sum(hop_slots(batch, fanout))


def step_flops(cfg, batch, fanout):
  """Forward and backward FLOPs one chip's batch requires."""
  dims = _dims(cfg)
  fwd = sum(rows * 2 * a * b * 2 for rows, a, b in
            zip(rows_needed(batch, fanout), dims[:-1], dims[1:]))
  return 3 * fwd


def step_bytes(cfg, batch, fanout):
  """The least bytes one chip's step moves: every row of the padded node
  budget read from the table and written once, each required activation
  row written once forward and read once backward, parameters and
  Adam's two moments read and written once. float32 throughout."""
  dims = _dims(cfg)
  gather = budget_rows(batch, fanout) * dims[0] * 4 * 2
  acts = sum(rows * b * 4 * 2 for rows, b in
             zip(rows_needed(batch, fanout), dims[1:]))
  n_params = sum(2 * a * b + b for a, b in zip(dims[:-1], dims[1:]))
  return gather + acts + n_params * 4 * 3 * 2


def least_step_seconds(cfg, batch, fanout, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, batch, fanout) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, batch, fanout) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
