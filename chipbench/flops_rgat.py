"""What one training step of the configuration's R-GAT requires of one
chip, from the configuration and the traffic alone (relations, fanout,
batch, widths): the same number whatever implements the step.

No dedup is assumed, as in ``chipbench/flops.py``: every sampled edge has
a child of its own. A relation ``r`` (traversed parent to child, messages
flowing back) has ``parents_h x fanout_h`` edge slots in hop ``h``, where
``parents_h`` is the frontier of its parents' type. Layer ``l`` of ``L``
(from 1) reads the edges of hops ``<= min(H, L - l + 1)`` and writes rows
for the nodes within ``min(H, L - l)`` hops. Per relation and layer it
needs, forward: one ``in x hidden`` projection row per edge read (the
child's), one ``in x heads`` product per parent row (its logit), and
``6 x hidden`` per edge for logits, softmax and the weighted sum.
Backward counts twice forward, but layer 1's projection once: features
take no gradient. The head is ``batch x hidden x classes``. Sampling and
the gather count nought.
"""


def frontiers(cfg, batch, fanout, seed_type):
  """[{type: slots}] per hop 0..H: the parents that hop ``h + 1``
  expands, no dedup assumed."""
  caps = [{t: (batch if t == seed_type else 0) for t in cfg['num_nodes']}]
  for k in fanout:
    nxt = {t: 0 for t in cfg['num_nodes']}
    for rel in cfg['relations']:
      nxt[rel['dst']] += caps[-1][rel['src']] * k
    caps.append(nxt)
  return caps


def budget_rows(cfg, batch, fanout, seed_type):
  """{type: rows of its padded node budget}."""
  caps = frontiers(cfg, batch, fanout, seed_type)
  return {t: sum(c[t] for c in caps) for t in cfg['num_nodes']}


def edge_slots(cfg, batch, fanout, seed_type, hops=None):
  """{relation name: edge slots within the first ``hops`` hops}."""
  caps = frontiers(cfg, batch, fanout, seed_type)
  hops = len(fanout) if hops is None else hops
  return {rel['name']: sum(caps[h][rel['src']] * fanout[h]
                           for h in range(hops))
          for rel in cfg['relations']}


def rows_needed(cfg, batch, fanout, seed_type):
  """[{type: output rows}] per layer 1..L."""
  caps = frontiers(cfg, batch, fanout, seed_type)
  n, hops = cfg['num_layers'], len(fanout)
  return [{t: sum(c[t] for c in caps[:min(hops, n - l) + 1])
           for t in cfg['num_nodes']} for l in range(1, n + 1)]


def step_flops(cfg, batch, fanout, seed_type):
  """Forward and backward FLOPs one chip's batch requires."""
  n, hops = cfg['num_layers'], len(fanout)
  hidden, heads = cfg['hidden_dim'], cfg['heads']
  rows = rows_needed(cfg, batch, fanout, seed_type)
  total = 0
  for l in range(1, n + 1):
    width = cfg['feature_dim'] if l == 1 else hidden
    edges = edge_slots(cfg, batch, fanout, seed_type,
                       min(hops, n - l + 1))
    for rel in cfg['relations']:
      e = edges[rel['name']]
      proj = e * 2 * width * hidden
      rest = rows[l - 1][rel['src']] * 2 * width * heads + e * 6 * hidden
      total += proj * (2 if l == 1 else 3) + rest * 3
  return total + 3 * batch * 2 * hidden * cfg['num_classes']


def num_params(cfg):
  hidden, heads = cfg['hidden_dim'], cfg['heads']
  per_layer = lambda width: len(cfg['relations']) * (
      width * hidden + 2 * hidden)
  return (per_layer(cfg['feature_dim'])
          + (cfg['num_layers'] - 1) * per_layer(hidden)
          + hidden * cfg['num_classes'] + cfg['num_classes'])


def step_bytes(cfg, batch, fanout, seed_type):
  """The least bytes one chip's step moves: every row of the padded node
  budgets read from its table and written once (bfloat16), each required
  activation row written once forward and read once backward (float32),
  parameters and Adam's two moments read and written once."""
  item = {'bfloat16': 2, 'float32': 4}[cfg['feature_dtype']]
  gather = sum(budget_rows(cfg, batch, fanout, seed_type).values()) * (
      cfg['feature_dim'] * item * 2)
  acts = sum(sum(r.values()) for r in rows_needed(
      cfg, batch, fanout, seed_type)) * cfg['hidden_dim'] * 4 * 2
  return gather + acts + num_params(cfg) * 4 * 3 * 2


def least_step_seconds(cfg, batch, fanout, seed_type, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, batch, fanout, seed_type) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, batch, fanout, seed_type) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
