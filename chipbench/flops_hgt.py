"""What one training step of the configuration's HGT requires of one chip,
from the configuration and the traffic alone (relations, fanout, batch,
widths): the same number whatever implements the step.

No dedup is assumed, as in ``chipbench/flops_rgat.py`` (whose frontiers,
budgets, edge slots and needed rows these are): every sampled edge has a
child of its own. The input linear is one ``feature x hidden`` row per
node slot, forward, and once more backward (its kernel's gradient;
features take none). Layer ``l`` of ``L`` (from 1) reads the edges of
hops ``<= min(H, L - l + 1)`` and writes rows for the nodes within
``min(H, L - l)`` hops. Per layer it needs, forward: a key and a value
row per edge read (``2 x hidden x hidden`` each, the child's), their
``A_r`` and ``M_r`` products (``heads`` blocks of ``d x d`` each),
``6 x hidden`` per edge for logits, softmax and the weighted sum, and a
query row and an output row (``hidden x hidden`` each) per row written.
Backward counts twice forward. The head is ``batch x hidden x classes``.
Sampling and the gather count nought.
"""
from chipbench import flops_rgat


def step_flops(cfg, batch, fanout, seed_type):
  """Forward and backward FLOPs one chip's batch requires."""
  n, hops = cfg['num_layers'], len(fanout)
  hidden, d = cfg['hidden_dim'], cfg['hidden_dim'] // cfg['heads']
  slots = sum(flops_rgat.budget_rows(cfg, batch, fanout,
                                     seed_type).values())
  total = slots * 2 * cfg['feature_dim'] * hidden * 2
  rows = flops_rgat.rows_needed(cfg, batch, fanout, seed_type)
  for l in range(1, n + 1):
    edges = sum(flops_rgat.edge_slots(cfg, batch, fanout, seed_type,
                                      min(hops, n - l + 1)).values())
    per_edge = 2 * (2 * hidden * hidden + 2 * hidden * d) + 6 * hidden
    per_row = 2 * 2 * hidden * hidden
    total += 3 * (edges * per_edge + sum(rows[l - 1].values()) * per_row)
  return total + 3 * batch * 2 * hidden * cfg['num_classes']


def num_params(cfg):
  hidden, heads = cfg['hidden_dim'], cfg['heads']
  d, types = hidden // heads, len(cfg['num_nodes'])
  per_layer = (types * (4 * (hidden * hidden + hidden) + 1)
               + len(cfg['relations']) * (2 * heads * d * d + heads))
  return (types * (cfg['feature_dim'] * hidden + hidden)
          + cfg['num_layers'] * per_layer
          + hidden * cfg['num_classes'] + cfg['num_classes'])


def step_bytes(cfg, batch, fanout, seed_type):
  """The least bytes one chip's step moves: every row of the padded node
  budgets read from its table and written once (bfloat16), its input
  projection and each required activation row written once forward and
  read once backward (float32), parameters and Adam's two moments read
  and written once."""
  item = {'bfloat16': 2, 'float32': 4}[cfg['feature_dtype']]
  slots = sum(flops_rgat.budget_rows(cfg, batch, fanout,
                                     seed_type).values())
  gather = slots * cfg['feature_dim'] * item * 2
  acts = (slots + sum(sum(r.values()) for r in flops_rgat.rows_needed(
      cfg, batch, fanout, seed_type))) * cfg['hidden_dim'] * 4 * 2
  return gather + acts + num_params(cfg) * 4 * 3 * 2


def least_step_seconds(cfg, batch, fanout, seed_type, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, batch, fanout, seed_type) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, batch, fanout, seed_type) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
