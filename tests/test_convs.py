"""Exact-math unit tests for the conv layers over padded edge lists."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glt_tpu.models import GCNConv, SAGEConv
from glt_tpu.models.conv import segment_mean


def test_segment_mean_masked():
  msgs = jnp.array([[1.], [3.], [100.], [5.]])
  targets = jnp.array([0, 0, 1, 1])
  mask = jnp.array([True, True, False, True])
  out = np.asarray(segment_mean(msgs, targets, mask, 3))
  np.testing.assert_allclose(out, [[2.], [5.], [0.]])


def test_sage_conv_exact():
  # 3 nodes; edges child->parent: (1->0), (2->0); node features scalar
  x = jnp.array([[1.], [2.], [4.]])
  row = jnp.array([1, 2, 0])
  col = jnp.array([0, 0, 2])
  mask = jnp.array([True, True, False])     # last edge padded out
  conv = SAGEConv(1, use_bias=False)
  params = conv.init(jax.random.key(0), x, row, col, mask)
  w_root = np.asarray(params['params']['lin_root']['kernel'])[0, 0]
  w_nbr = np.asarray(params['params']['lin_nbr']['kernel'])[0, 0]
  out = np.asarray(conv.apply(params, x, row, col, mask))
  # node0: root*1 + nbr*mean(2,4); node1: root*2; node2: root*4
  np.testing.assert_allclose(out[0, 0], w_root * 1 + w_nbr * 3, rtol=1e-5)
  np.testing.assert_allclose(out[1, 0], w_root * 2, rtol=1e-5)
  np.testing.assert_allclose(out[2, 0], w_root * 4, rtol=1e-5)


def test_gcn_conv_shapes_and_mask():
  x = jnp.ones((4, 8))
  row = jnp.array([0, 1, 2, 3])
  col = jnp.array([1, 2, 3, 0])
  mask = jnp.array([True, True, False, False])
  conv = GCNConv(16)
  params = conv.init(jax.random.key(0), x, row, col, mask)
  out = conv.apply(params, x, row, col, mask)
  assert out.shape == (4, 16)
  # masked edges contribute nothing: recompute with only the valid edges
  out2 = conv.apply(params, x, row[:2], col[:2],
                    jnp.array([True, True]))
  np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                             rtol=1e-5)


def test_host_mode_graph_sampling():
  """GraphMode.HOST keeps topology in host memory; the sampler still
  works (arrays embed as constants — the beyond-HBM path uses the mp
  producer instead, this guards the API)."""
  from glt_tpu.data import Dataset
  from glt_tpu.sampler import NeighborSampler
  import sys, os
  sys.path.insert(0, os.path.dirname(__file__))
  from fixtures import ring_edges
  rows, cols, _ = ring_edges(20)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([rows, cols]), num_nodes=20,
                graph_mode='HOST')
  g = ds.get_graph()
  assert isinstance(g.indptr, np.ndarray)   # stayed on host
  s = NeighborSampler(g, [2], seed=0)
  out = s.sample_from_nodes(np.array([0, 5]))
  nodes = np.asarray(out.node)[:int(out.node_count)]
  assert set(nodes.tolist()) == {0, 5, 1, 2, 6, 7}


def test_gat_conv_multihead():
  x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 8))
                  .astype(np.float32))
  row = jnp.array([1, 2, 3, 4, 5])
  col = jnp.array([0, 0, 0, 1, 1])
  mask = jnp.array([True, True, False, True, True])
  from glt_tpu.models import GATConv
  conv = GATConv(4, heads=3, concat=True)
  params = conv.init(jax.random.key(0), x, row, col, mask)
  out = conv.apply(params, x, row, col, mask)
  assert out.shape == (6, 12)                 # heads * features
  # attention weights per parent sum to 1 over valid incoming edges:
  # masked edge (3->0) contributes nothing — recompute without it
  keep = jnp.array([0, 1, 3, 4])
  out2 = conv.apply(params, x, row[keep], col[keep],
                    jnp.ones(4, bool))
  np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                             rtol=1e-5, atol=1e-6)
  conv_mean = GATConv(4, heads=3, concat=False)
  p2 = conv_mean.init(jax.random.key(1), x, row, col, mask)
  assert conv_mean.apply(p2, x, row, col, mask).shape == (6, 4)


def test_trim_does_not_change_seed_outputs():
  """Static hop-trimming drops only edges that cannot influence seed
  representations: trimmed and untrimmed GraphSAGE agree exactly on the
  seed rows."""
  import sys, os
  sys.path.insert(0, os.path.dirname(__file__))
  from fixtures import ring_dataset
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import GraphSAGE
  ds = ring_dataset(num_nodes=40, feat_dim=8)
  loader = NeighborLoader(ds, [2, 2, 2], input_nodes=np.arange(16),
                          batch_size=16, seed=0)
  b = next(iter(loader))
  trimmed = GraphSAGE(hidden_features=16, out_features=4, num_layers=3,
                      trim=True)
  full = GraphSAGE(hidden_features=16, out_features=4, num_layers=3,
                   trim=False)
  params = trimmed.init(jax.random.key(0), b)
  out_t = trimmed.apply(params, b)
  out_f = full.apply(params, b)
  np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_f),
                             rtol=1e-5, atol=1e-6)


def test_trim_equivalence_more_layers_than_hops():
  """num_layers > num_hops: layers must keep every hop they can still
  propagate (regression for the over-trim at layer i > 0)."""
  import sys, os
  sys.path.insert(0, os.path.dirname(__file__))
  from fixtures import ring_dataset
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import GraphSAGE
  ds = ring_dataset(num_nodes=40, feat_dim=8)
  loader = NeighborLoader(ds, [2, 2], input_nodes=np.arange(8),
                          batch_size=8, seed=0)
  b = next(iter(loader))
  kw = dict(hidden_features=16, out_features=4, num_layers=3)
  params = GraphSAGE(trim=True, **kw).init(jax.random.key(0), b)
  out_t = GraphSAGE(trim=True, **kw).apply(params, b)
  out_f = GraphSAGE(trim=False, **kw).apply(params, b)
  np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_f),
                             rtol=1e-5, atol=1e-6)


# -- the grouped aggregation (Batch.hop_fanouts) against the segment path --

def _parent_major_slots(fanouts, batch_size=6, seed=0):
  """Edge slots as the hop loops lay them out: hop h is ``S_h`` groups
  of ``K_h`` adjacent slots under one parent. Among the groups: wholly
  masked ones (their parents' labels are used again by a live group of
  the next hop, as the table engine does), parents of -1, a duplicate
  seed (two groups of one label, one of them dead), seed slots beyond
  ``n_valid`` and parents beyond any ``num_out`` under the node count."""
  rng = np.random.default_rng(seed)
  groups, row, col, mask = [], [], [], []
  off, s, lo = 0, batch_size, 0
  for h, k in enumerate(fanouts):
    parents = lo + np.arange(s)
    live = rng.random(s) < 0.7
    if h == 0:
      parents[2] = parents[1]          # a duplicate seed: its group is dead
      live[2] = False
      live[-1] = False                 # n_valid under the batch size
      parents[-1] = -1
    else:
      parents[rng.random(s) < 0.1] = -1
      live[0], live[1] = False, True
    live &= parents >= 0
    m = (rng.random((s, k)) < 0.6) & live[:, None]
    m[live, 0] = True                  # a live group has a live slot
    n_children = lo + s + s * k
    r = np.where(m, rng.integers(0, n_children, (s, k)), -1)
    groups.append((off, s, k))
    row.append(r.reshape(-1))
    col.append(np.repeat(parents, k))
    mask.append(m.reshape(-1))
    off, lo, s = off + s * k, lo + s, s * k
  n = lo + s
  as_i32 = lambda a: jnp.asarray(np.concatenate(a).astype(np.int32))
  return (tuple(groups), n, as_i32(row), as_i32(col),
          jnp.asarray(np.concatenate(mask)))


@pytest.mark.parametrize('trimmed', [False, True],
                         ids=['all_rows', 'num_out'])
@pytest.mark.parametrize('fanouts', [(5, 3), (10, 2), (15,), (8, 16)],
                         ids=['f5_3', 'f10_2', 'f15', 'f8_16'])
@pytest.mark.parametrize('aggr', ['mean', 'sum', 'max'])
def test_sage_conv_grouped_matches_segment(aggr, fanouts, trimmed):
  groups, n, row, col, mask = _parent_major_slots(fanouts)
  # half of the parents lie beyond num_out
  num_out = (int(col.max()) + 1) // 2 if trimmed else None
  dead = [not np.asarray(mask)[o:o + s * k].reshape(s, k)[i].any()
          for o, s, k in groups for i in range(s)]
  assert any(dead) and not all(dead) and (np.asarray(col) == -1).any()
  x = jnp.asarray(np.random.default_rng(1).normal(size=(n, 12))
                  .astype(np.float32))
  conv = SAGEConv(7, aggr=aggr)
  params = conv.init(jax.random.key(0), x, row, col, mask)
  w = jnp.asarray(np.random.default_rng(2).normal(
      size=(n if num_out is None else num_out, 7)).astype(np.float32))

  def out_and_grads(groups):
    def f(p, x):
      out = conv.apply(p, x, row, col, mask, num_out=num_out,
                       groups=groups)
      return (out * w).sum(), out
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1),
                                         has_aux=True)(params, x)
    return out, grads

  got, g_got = out_and_grads(groups)
  want, g_want = out_and_grads(None)
  assert got.shape == want.shape == w.shape
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)
  for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
    assert np.abs(np.asarray(b)).max() > 0
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize('aggr', ['mean', 'sum', 'max'])
def test_sage_conv_without_groups_is_the_segment_path(aggr):
  """No promise, no change: ``groups=None`` (and a call that never
  heard of groups) computes the segment aggregation bit for bit."""
  from glt_tpu.models.conv import _AGGRS
  _, n, row, col, mask = _parent_major_slots((5, 3))
  x = jnp.asarray(np.random.default_rng(1).normal(size=(n, 12))
                  .astype(np.float32))
  conv = SAGEConv(7, aggr=aggr)
  params = conv.init(jax.random.key(0), x, row, col, mask)
  p = params['params']
  ok = mask & (row >= 0) & (col >= 0) & (col < n)
  agg = _AGGRS[aggr](jnp.take(x, jnp.clip(row, 0, n - 1), axis=0),
                     jnp.clip(col, 0, n - 1), ok, n)
  want = (x @ p['lin_root']['kernel'] + p['lin_root']['bias']
          + agg @ p['lin_nbr']['kernel'])
  for kw in ({}, {'groups': None}, {'groups': ()}):
    np.testing.assert_array_equal(
        np.asarray(conv.apply(params, x, row, col, mask, **kw)),
        np.asarray(want))


# -- GATConv: the softmax and the weighted sum over the fanout axis --------

def _gat_case(fanouts, heads, concat, typed, trimmed):
  """A parent-major batch (wholly masked groups, parents of -1, two hop
  blocks of different ``K``), the convolution, its parameters and the
  parents' rows: another type's where ``typed``; ``trimmed`` puts half
  of the parents beyond ``num_out``."""
  from glt_tpu.models import GATConv
  groups, n, row, col, mask = _parent_major_slots(fanouts)
  num_out = (int(col.max()) + 1) // 2 if trimmed else None
  rng = np.random.default_rng(1)
  x = jnp.asarray(rng.normal(size=(n, 12)).astype(np.float32))
  x_dst = (jnp.asarray(rng.normal(size=(int(col.max()) + 4, 12))
                       .astype(np.float32)) if typed else None)
  conv = GATConv(7, heads=heads, concat=concat)
  params = conv.init(jax.random.key(0), x, row, col, mask, x_dst=x_dst)
  return conv, params, x, x_dst, row, col, mask, num_out, groups


@pytest.mark.parametrize('trimmed', [False, True],
                         ids=['all_rows', 'num_out'])
@pytest.mark.parametrize('typed', [False, True], ids=['x', 'x_dst'])
@pytest.mark.parametrize('concat', [True, False], ids=['concat', 'mean'])
@pytest.mark.parametrize('heads', [1, 4])
@pytest.mark.parametrize('fanouts', [(5, 3), (15,)], ids=['f5_3', 'f15'])
def test_gat_conv_grouped_matches_segment(fanouts, heads, concat, typed,
                                          trimmed):
  (conv, params, x, x_dst, row, col, mask, num_out,
   groups) = _gat_case(fanouts, heads, concat, typed, trimmed)
  m = (x_dst if typed else x).shape[0] if num_out is None else num_out
  w = jnp.asarray(np.random.default_rng(2).normal(
      size=(m, 7 * heads if concat else 7)).astype(np.float32))

  def out_and_grads(groups):
    def f(p, x, x_dst):
      out = conv.apply(p, x, row, col, mask, num_out=num_out, x_dst=x_dst,
                       groups=groups)
      return (out * w).sum(), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2) if typed else (0, 1), has_aux=True))(
            params, x, x_dst)
    leaves = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(grads)}
    return out, leaves

  got, g_got = out_and_grads(groups)
  want, g_want = out_and_grads(None)
  assert got.shape == want.shape == w.shape
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=1e-5, atol=1e-5)
  # att_dst first: its gradient cancels within a parent (PERF.md, 2)
  names = sorted(g_want, key=lambda k: 'att_dst' not in k)
  assert 'att_dst' in names[0] and len(names) == (5 if typed else 4)
  for k in names:
    assert np.abs(np.asarray(g_want[k])).max() > 0, k
    np.testing.assert_allclose(np.asarray(g_got[k]), np.asarray(g_want[k]),
                               rtol=1e-5, atol=1e-5, err_msg=k)


@pytest.mark.parametrize('typed', [False, True], ids=['x', 'x_dst'])
def test_gat_conv_without_groups_is_the_segment_path(typed):
  """No promise, no change: ``groups=None`` (and a call that never heard
  of groups) computes the segment softmax bit for bit, as written out
  here from the convolution's own parameters."""
  conv, params, x, x_dst, row, col, mask, _, _ = _gat_case(
      (5, 3), 4, True, typed, False)
  p = params['params']
  dst = x if x_dst is None else x_dst
  n, m, h, f = x.shape[0], dst.shape[0], 4, 7
  ok = mask & (row >= 0) & (row < n) & (col >= 0) & (col < m)
  proj = (x @ p['proj']['kernel']).reshape(n, h, f)
  w_dst = (p['proj']['kernel'].reshape(-1, h, f) * p['att_dst']).sum(-1)
  seg = jnp.where(ok, col, m)
  logit = jax.nn.leaky_relu(
      jnp.take((proj * p['att_src']).sum(-1), jnp.clip(row, 0, n - 1),
               axis=0)
      + jnp.take(dst @ w_dst, jnp.clip(col, 0, m - 1), axis=0), 0.2)
  top = jax.ops.segment_max(jnp.where(ok[:, None], logit, -jnp.inf), seg,
                            m + 1)
  top = jnp.where(jnp.isfinite(top), top, 0.0)
  z = jnp.where(ok[:, None], jnp.exp(logit - top[jnp.clip(seg, 0, m)]), 0.0)
  alpha = z / jnp.maximum(
      jax.ops.segment_sum(z, seg, m + 1)[jnp.clip(seg, 0, m)], 1e-16)
  want = jax.ops.segment_sum(
      jnp.take(proj, jnp.clip(row, 0, n - 1), axis=0) * alpha[:, :, None],
      seg, m + 1)[:m].reshape(m, h * f)
  for kw in ({}, {'groups': None}, {'groups': ()}):
    np.testing.assert_array_equal(
        np.asarray(conv.apply(params, x, row, col, mask, x_dst=x_dst,
                              **kw)), np.asarray(want))
