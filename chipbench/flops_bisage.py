"""What one training step of the configuration's user-item link model
requires of one chip, from the configuration and the traffic alone
(positive edges a step, fanout, widths, the tables' rows): the same number
whatever implements the step.

No dedup is assumed: every seed slot and every sampled edge has a node of
its own. ``B`` positive edges a step give ``2B`` user seeds and ``2B`` item
seeds; the loader samples the three relations on both hops, the model
reads items into items on both and items into users on the first. FLOPs:
a ``SAGEConv`` is two ``in x out`` products a row written (root and
neighbours), a linear layer one, forward, and twice that backward. The
first layer's two convolutions (one an encoder) write the items within one
hop of a seed, the second layer's three the seeds, the decoder the ``2B``
pairs. Sampling, the embedding take, the means and the update count
nought.

Bytes, the least a step moves: dense Adam reads every row of both tables
with its two moments and its gradient and writes the row and the moments
back, seven passes over the tables whatever a batch touched; every node
slot's embedding row is read once and its gradient row written once
(users: the seed prefix alone); each activation row written once forward
and read once backward; the other parameters and their moments read and
written once. float32 throughout.
"""


def frontiers(batch, fanout):
  """Seed and frontier slots by hop and type, no dedup: ``[{'user': n,
  'item': n}]`` for hops 0, 1, 2. Users are reached from items, items from
  users and from items."""
  hops = [{'user': 2 * batch, 'item': 2 * batch}]
  for k in fanout:
    last = hops[-1]
    hops.append({'user': last['item'] * k,
                 'item': (last['user'] + last['item']) * k})
  return hops


def node_slots(batch, fanout):
  """{type: node slots of the padded budget}."""
  hops = frontiers(batch, fanout)
  return {t: sum(h[t] for h in hops) for t in ('user', 'item')}


def edge_slots(batch, fanout):
  """Edge slots of the three relations over all hops, as the loader
  samples them: each frontier slot times the hop's fanout, items' twice
  (into users' and into items' neighbours)."""
  hops = frontiers(batch, fanout)
  return sum((h['user'] + 2 * h['item']) * k for h, k in zip(hops, fanout))


def rows_written(batch, fanout):
  """Rows the model's layers write: the first layer's two convolutions
  the items within one hop of a seed each, the second layer's three and
  the two linear layers the seeds of their type."""
  hops = frontiers(batch, fanout)
  near = hops[0]['item'] + hops[1]['item']
  return {'first': 2 * near, 'second': 3 * 2 * batch, 'lin': 2 * 2 * batch}


def embedding_rows_read(batch, fanout):
  """Table rows a step reads: every item slot, the users' seed prefix."""
  return {'item': node_slots(batch, fanout)['item'], 'user': 2 * batch}


def step_flops(cfg, batch, fanout):
  """Forward and backward FLOPs one chip's batch requires."""
  d, hidden, out = cfg['embedding_dim'], cfg['hidden_dim'], cfg['out_dim']
  rows = rows_written(batch, fanout)
  near = rows['first'] // 2
  forward = (
      2 * near * 2 * (2 * d * hidden)            # both encoders' conv1
      + 2 * batch * 2 * (2 * hidden * hidden)    # items' conv2
      + 2 * batch * 2 * (2 * d * hidden)         # users' conv2
      + 2 * batch * 2 * (2 * hidden * hidden)    # users' conv3
      + rows['lin'] * 2 * hidden * out           # the two linear layers
      + 2 * batch * 2 * (2 * out * out + out))   # the decoder
  return 3 * forward


def num_params(cfg):
  """(table parameters, all other parameters)."""
  d, hidden, out = cfg['embedding_dim'], cfg['hidden_dim'], cfg['out_dim']
  conv = lambda a, b: 2 * a * b + b
  dense = lambda a, b: a * b + b
  rest = (2 * conv(d, hidden) + conv(hidden, hidden)     # conv1 x 2, conv2
          + conv(d, hidden) + conv(hidden, hidden)       # users' conv2, 3
          + 2 * dense(hidden, out) + dense(2 * out, out) + dense(out, 1))
  return sum(cfg['num_nodes'].values()) * d, rest


def table_bytes(cfg):
  """Dense Adam over the tables: parameter, two moments and gradient
  read, parameter and two moments written."""
  return num_params(cfg)[0] * 4 * 7


def step_bytes(cfg, batch, fanout):
  """The least bytes one chip's step moves (module docstring)."""
  read = embedding_rows_read(batch, fanout)
  rows = rows_written(batch, fanout)
  take = sum(read.values()) * cfg['embedding_dim'] * 4 * 2
  acts = (rows['first'] + rows['second']) * cfg['hidden_dim'] * 4 * 2 \
      + rows['lin'] * cfg['out_dim'] * 4 * 2
  return (table_bytes(cfg) + take + acts
          + num_params(cfg)[1] * 4 * 3 * 2)


def least_step_seconds(cfg, batch, fanout, peak):
  """(seconds, which bound is the larger)."""
  by_flops = step_flops(cfg, batch, fanout) / peak['flops_per_s']
  by_bytes = step_bytes(cfg, batch, fanout) / peak['bytes_per_s']
  return max(by_flops, by_bytes), ('flops' if by_flops > by_bytes
                                   else 'bytes')
