"""The typed models share one plan since HGT came (models/plan.py): the
R-GAT step, with the promise of parent-major edge slots and with it
withheld, and the three SAGE steps still lower to the programs they
were."""
import hashlib
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARENT_STABLEHLO = {
    # sha256 of the tiny cells' step programs as StableHLO (no locations),
    # under GLT_DEDUP=sort GLT_FUSED_HOP=1, read on the parent of the PR
    # that brought HGT (bd94cd5): a PR that means to change one of these
    # programs reads the hash anew and says so. c4's was read anew by the
    # PR that gave the store's per-owner buckets ceil(b / P) slots and put
    # the drain loop and its three counters into the four-chip step (it
    # was a03c3aee...bad7d59); the other four are the parent's still,
    # which is that PR's proof that their cells run the parent's program
    'c1': '796cd17c7761caa22f05c36e824c0e4463ada262b71cbf0f6ad08ab11f391096',
    'c4': 'f4a4b6defb06c356082034e36271ab47a6f059b96890156774adb49e409b5347',
    'link': '4a6baa938beadfe9a51ca6f744bb73ba48aca6774a3d968395484e1a456f93fe',
    'typed': '196fde82f9b6340e00476b67d6d05c1ab120e0e8b4a78d2abdab2521e6904d19',
    'typed_withheld':
        '3c10d6a0010d98233f39aa085120f535d4d1e83dcb44d92ce01fb41d4b14d519',
}


def _sage_text(driver, cell, chips):
  from jax.sharding import NamedSharding, PartitionSpec as P
  _, _, cfg, traffic = cell
  s = driver.build(cfg, traffic, chips, 5)
  t = s.trainer
  rows = NamedSharding(t.mesh, P(t.axis))
  seeds, keys = driver.feed(s, 0)
  return t._step_fn.lower(
      s.params, s.opt, t.tables, t.scratches,
      jax.device_put(np.asarray(seeds, np.int32), rows),
      jax.device_put(s.n_valid, rows), keys, t.feature.array, t.labels,
      t._indptr, t._indices).as_text()


def _typed_text(withheld):
  from jax.sharding import NamedSharding, PartitionSpec as P
  from chipbench.drivers import hetero_fused
  import test_rgat_cell
  _, _, cfg, traffic = test_rgat_cell.tiny_cell()
  s = hetero_fused.build(cfg, traffic, 1, 5)
  t = s.trainer
  if withheld:
    t._batch_static['hop_fanouts_dict'] = None
    t._step_fn = t._build()
  run = t._step_fn
  cells = dict(zip(run.__code__.co_freevars,
                   (c.cell_contents for c in run.__closure__)))
  shards, feat_shards, efeat_shards = cells['payloads']()
  shard = NamedSharding(t.mesh, P(t.axis))
  seeds, key = hetero_fused.feed(s, 0)
  return run.jitted.lower(
      s.params, s.opt, shards, feat_shards, efeat_shards, t.labels,
      jax.device_put(np.asarray(seeds, np.int32).reshape(-1), shard),
      jax.device_put(np.asarray(s.n_valid, np.int32), shard),
      jax.random.split(key, 1), t.sampler.tables).as_text()


@pytest.mark.parametrize('name', sorted(PARENT_STABLEHLO))
def test_the_other_cells_tiny_steps_lower_to_the_parents(name, monkeypatch):
  """R-GAT's typed step, with the promise and with it withheld, and the
  three SAGE steps lower to the StableHLO they had before the typed
  models shared one plan."""
  monkeypatch.setenv('GLT_DEDUP', 'sort')
  monkeypatch.setenv('GLT_FUSED_HOP', '1')
  sys.path.insert(0, REPO)
  sys.path.insert(0, os.path.join(REPO, 'tests', 'chipbench'))
  if name.startswith('typed'):
    text = _typed_text(name == 'typed_withheld')
  else:
    from chipbench.drivers import fused, link_fused
    import test_chipbench
    import test_link_cell
    text = {'c1': lambda: _sage_text(fused, test_chipbench.tiny_cell(1), 1),
            'c4': lambda: _sage_text(fused, test_chipbench.tiny_cell(4), 4),
            'link': lambda: _sage_text(link_fused, test_link_cell.tiny_cell(),
                                       1)}[name]()
  assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STABLEHLO[name]
