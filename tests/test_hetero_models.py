"""Hetero model tests: RGNN (rsage/rgat) and HGT learn on the hetero
ring fixture through the full loader path."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.data import Dataset
from glt_tpu.loader import NeighborLoader
from glt_tpu.models import HGT, RGNN

U2I = ('user', 'u2i', 'item')
I2I = ('item', 'i2i', 'item')
# message-passing keys produced by edge_dir='out' sampling
REV_U2I = ('item', 'rev_u2i', 'user')
REV_I2I = ('item', 'i2i', 'item')


def _hetero_onehot_dataset(num_users=12, num_items=24):
  u = np.arange(num_users, dtype=np.int64)
  u2i_rows = np.repeat(u, 2)
  u2i_cols = np.stack([2 * u, 2 * u + 1], 1).reshape(-1) % num_items
  i = np.arange(num_items, dtype=np.int64)
  i2i_rows = np.repeat(i, 2)
  i2i_cols = np.stack([(i + 1) % num_items, (i + 2) % num_items],
                      1).reshape(-1)
  ds = Dataset(edge_dir='out')
  ds.init_graph(
      edge_index={U2I: np.stack([u2i_rows, u2i_cols]),
                  I2I: np.stack([i2i_rows, i2i_cols])},
      num_nodes={'user': num_users, 'item': num_items})
  ds.init_node_features({
      'user': np.eye(num_users, dtype=np.float32),
      'item': np.eye(num_items, dtype=np.float32),
  })
  ds.init_node_labels({
      'user': (np.arange(num_users) % 3).astype(np.int32),
      'item': (np.arange(num_items) % 3).astype(np.int32),
  })
  return ds


def _pad_user_features(ds, dim):
  """user/item one-hots have different widths; RGNN aggregates them into
  one dst space per relation, so pad to a common width."""
  nu = ds.node_features['user'].num_rows
  ni = ds.node_features['item'].num_rows
  w = max(nu, ni)
  feats = {
      'user': np.pad(np.eye(nu, dtype=np.float32), ((0, 0), (0, w - nu))),
      'item': np.pad(np.eye(ni, dtype=np.float32), ((0, 0), (0, w - ni))),
  }
  ds.init_node_features(feats)
  return ds


def _train(model, loader, steps=80, lr=5e-3, seed=0):
  b0 = next(iter(loader))
  params = model.init(jax.random.key(seed), b0)
  tx = optax.adam(lr)
  opt = tx.init(params)

  @jax.jit
  def step(params, opt, batch):
    def loss_fn(p):
      logits = model.apply(p, batch)
      mask = jnp.arange(logits.shape[0]) < batch.metadata['n_valid']
      losses = optax.softmax_cross_entropy_with_integer_labels(
          logits, batch.y_dict[batch.input_type])
      return jnp.where(mask, losses, 0).sum() / jnp.maximum(mask.sum(), 1)
    loss, g = jax.value_and_grad(loss_fn)(params)
    up, opt = tx.update(g, opt)
    return optax.apply_updates(params, up), opt, loss

  losses = []
  it = 0
  while it < steps:
    for batch in loader:
      meta = dict(batch.metadata)
      meta['n_valid'] = jnp.asarray(meta['n_valid'])
      params, opt, loss = step(params, opt, batch.replace(metadata=meta))
      losses.append(float(loss))
      it += 1
      if it >= steps:
        break
  return losses


@pytest.mark.parametrize('conv', ['rsage', 'rgat'])
def test_rgnn_learns(conv):
  ds = _pad_user_features(_hetero_onehot_dataset(), 0)
  loader = NeighborLoader(ds, {U2I: [2, 2], I2I: [2, 2]},
                          input_nodes=('user', np.arange(12)),
                          batch_size=6, shuffle=True, seed=0,
                          rng=np.random.default_rng(1))
  model = RGNN(edge_types=[REV_U2I, REV_I2I], hidden_features=32,
               out_features=3, num_layers=2, conv=conv)
  steps = 150 if conv == 'rgat' else 60  # attention converges slower
  losses = _train(model, loader, steps=steps)
  assert losses[-1] < 0.35, f'{conv} did not learn: {losses[::12]}'


def test_hgt_learns():
  ds = _pad_user_features(_hetero_onehot_dataset(), 0)
  loader = NeighborLoader(ds, {U2I: [2, 2], I2I: [2, 2]},
                          input_nodes=('user', np.arange(12)),
                          batch_size=6, shuffle=True, seed=0,
                          rng=np.random.default_rng(2))
  model = HGT(node_types=['user', 'item'],
              edge_types=[REV_U2I, REV_I2I],
              hidden_features=32, out_features=3, num_layers=2, heads=2)
  losses = _train(model, loader, steps=60, lr=3e-3)
  assert losses[-1] < 0.5, f'HGT did not learn: {losses[::12]}'


def test_hetero_trim_equivalence():
  """RGNN hierarchical trimming must not change seed outputs: trimmed
  hops feed representations no later layer reads (reference
  trim_to_layer semantics)."""
  import jax
  import numpy as np
  from fixtures import hetero_ring_dataset
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type

  ds = hetero_ring_dataset(num_users=12, num_items=24)
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  loader = NeighborLoader(ds, [3, 2], ('user', np.arange(12)),
                          batch_size=4, shuffle=False, seed=0)
  batch = next(iter(loader))
  assert batch.edge_hop_offsets_dict
  kw = dict(edge_types=[reverse_edge_type(u2i), i2i],
            hidden_features=8, out_features=3, num_layers=2,
            conv='rsage')
  trimmed = RGNN(trim=True, **kw)
  full = RGNN(trim=False, **kw)
  params = trimmed.init(jax.random.key(0), batch)
  out_t = np.asarray(trimmed.apply(params, batch))
  out_f = np.asarray(full.apply(params, batch))
  np.testing.assert_allclose(out_t, out_f, rtol=1e-5, atol=1e-5)


def test_hetero_trim_equivalence_more_layers_than_hops():
  import jax
  import numpy as np
  from fixtures import hetero_ring_dataset
  from glt_tpu.loader import NeighborLoader
  from glt_tpu.models import RGNN
  from glt_tpu.typing import reverse_edge_type

  ds = hetero_ring_dataset(num_users=12, num_items=24)
  u2i = ('user', 'u2i', 'item')
  i2i = ('item', 'i2i', 'item')
  loader = NeighborLoader(ds, [2, 2], ('user', np.arange(8)),
                          batch_size=4, shuffle=False, seed=0)
  batch = next(iter(loader))
  kw = dict(edge_types=[reverse_edge_type(u2i), i2i],
            hidden_features=8, out_features=3, num_layers=3,
            conv='rsage')
  params = RGNN(trim=True, **kw).init(jax.random.key(0), batch)
  out_t = np.asarray(RGNN(trim=True, **kw).apply(params, batch))
  out_f = np.asarray(RGNN(trim=False, **kw).apply(params, batch))
  np.testing.assert_allclose(out_t, out_f, rtol=1e-5, atol=1e-5)


def _hgt_ring_batch(fanout, layers, batch_size=4):
  from fixtures import hetero_ring_dataset
  from glt_tpu.typing import reverse_edge_type
  ds = hetero_ring_dataset(num_users=12, num_items=24)
  loader = NeighborLoader(ds, fanout, ('user', np.arange(12)),
                          batch_size=batch_size, shuffle=False, seed=0)
  batch = next(iter(loader))
  kw = dict(node_types=['user', 'item'],
            edge_types=[reverse_edge_type(U2I), I2I], hidden_features=8,
            out_features=3, num_layers=layers, heads=2)
  return batch, kw


@pytest.mark.parametrize('fanout,layers', [([3, 2], 2), ([2, 2], 3)])
def test_hgt_trim_equivalence(fanout, layers):
  """HGT under the typed models' one plan: the edge trim of a loader's
  batch does not change the seeds' outputs (as RGNN's does not)."""
  batch, kw = _hgt_ring_batch(fanout, layers)
  assert batch.edge_hop_offsets_dict
  params = HGT(trim=True, **kw).init(jax.random.key(0), batch)
  out_t = np.asarray(HGT(trim=True, **kw).apply(params, batch))
  out_f = np.asarray(HGT(trim=False, **kw).apply(params, batch))
  np.testing.assert_allclose(out_t, out_f, rtol=1e-5, atol=1e-5)


def test_hgt_on_a_loaders_batch_runs_the_segment_form():
  """A loader's batch carries no promise of parent-major edge slots:
  every layer's groups read 0, each type is the parent of one relation,
  and the parameters are the tree the reference names."""
  batch, kw = _hgt_ring_batch([3, 2], 2)
  assert batch.hop_fanouts_dict is None
  model = HGT(**kw)
  assert all(n == 0 for g in model.layer_groups(batch) for n in g.values())
  joint = model.layer_joint_relations(batch)
  assert joint[0] == {'user': 1, 'item': 1}
  params = model.init(jax.random.key(0), batch)['params']
  assert set(params) == {'in_user', 'in_item', 'layer0', 'layer1', 'head'}
  assert {'k_user', 'q_item', 'v_item', 'a_user', 'skip_item',
          'watt_item__i2i__item', 'wmsg_item__rev_u2i__user',
          'prior_item__i2i__item'} <= set(params['layer0'])
  assert float(params['layer0']['skip_user']) == 1.0
  assert np.all(np.asarray(params['layer0']['prior_item__i2i__item']) == 1)
  out = model.apply({'params': params}, batch)
  assert out.shape == (4, 3) and np.isfinite(np.asarray(out)).all()
