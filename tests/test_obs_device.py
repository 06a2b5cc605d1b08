"""glt_tpu.obs.device: layer-named scopes in the step program, and the
reducer that reads them back from a device trace. On the CPU: what a path
maps to, what a hand-made trace sums to, and that the compiled step
carries a layer on (nearly) every instruction. Times come from the chip."""
import gc
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from glt_tpu.obs import device, get_tracer

MS = 1_000_000


@pytest.mark.parametrize('op_name, want', [
    ('jit(step)/sampler/dedup0/sort',
     ('sampler', 'sampler/dedup0', False)),
    ('jit(step)/sampler/sort', ('sampler', 'sampler', False)),
    ('jit(step)/model_step/jvp(forward)/GraphSAGE/conv1/conv1/lin_root/'
     'dot_general',
     ('model_step', 'model_step/forward/GraphSAGE/conv1/lin_root', False)),
    ('jit(step)/shard_map/model_step/transpose(jvp(forward))/GraphSAGE/'
     'conv0/conv0/jit(_take)/gather',
     ('model_step', 'model_step/forward/GraphSAGE/conv0', True)),
    ('jit(step)/transpose(jvp(model_step))/forward/mul',
     ('model_step', 'model_step/forward', True)),
    ('jit(step)/shard_map/feature_store/feature_store/exchange/all_to_all',
     ('feature_store', 'feature_store/exchange', False)),
    ('jit(step)/shard_map/feature_store/while/body/feature_store/bucket/'
     'jit(_take)/select_n',
     ('feature_store', 'feature_store/bucket', False)),
    ('jit(step)/sampler/sample_hop1/jit(_uniform)/while/body/closed_call/'
     'add', ('sampler', 'sampler/sample_hop1', False)),
    ('jit(step)/jvp(feature_store/bucket)/cos',
     ('feature_store', 'feature_store/bucket', False)),
    ('jit(step)/shard_map/collectives/grad_sync/psum',
     ('collectives', 'collectives/grad_sync', False)),
    ('jit(step)/shard_map/multiply.59', (None, None, False)),
    ('reduce_window_sum', (None, None, False)),
])
def test_layer_of(op_name, want):
  assert device.layer_of(op_name) == want


def test_scope_names_a_layer_first():
  with pytest.raises(AssertionError):
    device.scope('bucket')

  def f(x):
    with device.scope('feature_store', 'serve'):
      return jnp.sin(x) * 2

  text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
  names = re.findall(r'op_name="([^"]*)"', text)
  assert any(device.layer_of(n)[1] == 'feature_store/serve' for n in names)


def _hlo_proto(compiled):
  """The ``HloProto`` a profiler session embeds, made by hand: field 1,
  the serialized module."""
  module = compiled.runtime_executable().hlo_modules()[
      0].as_serialized_hlo_module_proto()
  size, head = len(module), bytearray([0x0a])
  while size >= 0x80:
    head.append(size & 0x7f | 0x80)
    size >>= 7
  head.append(size)
  return bytes(head) + module


def test_hlo_scopes_reads_a_compiled_programs_proto():
  def f(x):
    with device.scope('sampler'):
      y = jnp.maximum(jnp.sort(x, axis=0), 0.0)
    with device.scope('model_step'):
      return jnp.maximum(y @ y.T, 0.0).sum()

  compiled = jax.jit(f).lower(jnp.ones((64, 64))).compile()
  scopes = device.hlo_scopes(_hlo_proto(compiled))
  text_names = set(re.findall(r'^\s+(?:ROOT )?%?([\w.\-]+) = ',
                              compiled.as_text(), re.M))
  assert text_names and text_names <= set(scopes)
  layers = {device.layer_of(n)[0] for names in scopes.values()
            for n in names}
  assert {'sampler', 'model_step'} <= layers
  # a fusion lists the instructions it calls that do work
  assert any(len(names) > 1 for names in scopes.values())


def test_reduce_scopes_on_a_hand_made_event_list():
  sam, fea, mod = ('jit(step)/sampler/dedup0/sort',
                   'jit(step)/feature_store/serve/gather',
                   'jit(step)/model_step/transpose(jvp(forward))/conv1/mul')
  dev = {'modules': [('jit_step(1)', -3 * MS, 3 * MS),        # cut short
                     ('jit_step(1)', 0, 10 * MS),
                     ('jit_squeeze(2)', 10 * MS, 1),
                     ('jit_step(1)', 10 * MS, 10 * MS),
                     ('jit_step(1)', 20 * MS, 4 * MS)],       # cut short
         'ops': [('%fusion.1 = s32[8] fusion()', -2 * MS, 1 * MS, [sam]),
                 ('%fusion.1 = s32[8] fusion()', 0, 4 * MS, [sam]),
                 # a fusion that spans layers: its root's, and mixed
                 ('%fusion.2 = f32[8] fusion()', 4 * MS, 2 * MS, [fea, sam]),
                 ('%all_to_all.11 = f32[4,8]{1,0} all-to-all(f32[4,8] %x)',
                  6 * MS, 1 * MS,
                  ['jit(step)/feature_store/exchange/all_to_all']),
                 # 7..10 ms idle, under train.step/put
                 ('%fusion.3 = f32[8] fusion()', 10 * MS, 5 * MS, [mod]),
                 # a while holds its body's ops: counted once
                 ('%while.4 = () while()', 15 * MS, 4 * MS, []),
                 ('%copy.5 = f32[8] copy()', 16 * MS, 2 * MS, [mod]),
                 ('%fusion.9 = f32[8] fusion()', 21 * MS, 2 * MS, [fea])]}
  idle = {'modules': [('jit_step(1)', 0, MS)] * 3, 'ops': []}
  host = [('train.step', 6 * MS, 5 * MS),
          ('train.step/put', 7 * MS, 3 * MS),
          ('train.step/dispatch', 12 * MS, 1 * MS)]
  r = device.reduce_scopes({'/device:TPU:0': dev, '/device:TPU:1': idle},
                           host)
  assert r['device'] == '/device:TPU:0' and r['steps'] == 2
  assert r['busy_ms'] == pytest.approx(8.0)       # (4+2+1+5+4) / 2
  assert r['window_ms'] == pytest.approx(10.0)
  assert r['layers'] == pytest.approx(
      {'sampler': 2.0, 'feature_store': 1.0, 'collectives': 0.5,
       'model_step': 3.5})
  assert r['stages'] == pytest.approx(
      {'sampler/dedup0': 2.0, 'feature_store/serve': 1.0,
       'collectives/feature_store/exchange': 0.5,
       'model_step/forward/conv1/bwd': 3.5, 'unscoped': 1.0})
  assert r['mixed_ms'] == pytest.approx(1.0)
  assert r['unscoped_ms'] == pytest.approx(1.0)    # the while's own 2 ms
  assert sum(r['layers'].values()) + r['unscoped_ms'] == pytest.approx(
      r['busy_ms'])
  assert r['mixed_ops'] == [['fusion.2', 'feature_store/serve',
                             pytest.approx(1.0)]]
  assert r['top_ops'][0] == ['fusion.3', 'model_step/forward/conv1/bwd',
                             pytest.approx(2.5)]
  assert r['idle_gaps'][0] == ['train.step/put', pytest.approx(3.0)]
  with pytest.raises(ValueError):
    device.reduce_scopes({'/device:TPU:1': idle}, host, 'jit_other')


# -- the step program itself ----------------------------------------------

def _tiny_trainer(chips):
  """``tests/chipbench``'s tiny cell: the benchmark's trainer at a size
  the CPU holds."""
  from chipbench import graphgen
  from glt_tpu.data import Graph
  from glt_tpu.models import GraphSAGE
  from glt_tpu.parallel import (ShardedFeature, SPMDSageTrainStep,
                                make_mesh)
  n, seed = 20000, 7
  indptr, indices = graphgen.csr(n, 291000, seed)
  feats = graphgen.Features(n, 16, 7, seed)
  mesh = make_mesh(chips)
  tx = optax.adam(1e-3)
  trainer = SPMDSageTrainStep(
      mesh, GraphSAGE(hidden_features=32, out_features=7, num_layers=3),
      tx, Graph(graphgen.SortedCSR(indptr, indices, n)),
      ShardedFeature(feats.table(), mesh), feats.labels(),
      fanouts=[4, 3, 2], batch_size_per_device=16)
  params = graphgen.weights(seed, 16, 32, 7, 3)
  batch = (np.arange(16 * chips, dtype=np.int32),
           np.full((chips,), 16, np.int32),
           jax.random.split(jax.random.key(seed), chips))
  return trainer, params, tx.init(params), batch


@pytest.mark.parametrize('chips', [1, 4])
def test_every_instruction_of_the_step_maps_to_a_layer(chips):
  from jax.sharding import NamedSharding, PartitionSpec as P
  trainer, params, opt, (seeds, n_valid, keys) = _tiny_trainer(chips)
  params, opt, _ = trainer(params, opt, seeds, n_valid, keys)
  rows = NamedSharding(trainer.mesh, P(trainer.axis))
  text = trainer._step_fn.lower(
      params, opt, jax.device_put(seeds, rows),
      jax.device_put(n_valid, rows), keys, trainer.feature.array,
      trainer.labels, trainer._indptr, trainer._indices).compile().as_text()
  seen, lost = {}, []
  for line in text.split('\n'):
    instr = re.match(r'^\s+(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(', line)
    op_name = re.search(r'op_name="([^"]*)"', line)
    if not instr or not op_name or instr.group(2) in device._NO_WORK:
      continue
    layer, stage, _ = device.layer_of(op_name.group(1))
    seen[stage] = seen.get(stage, 0) + 1
    if layer is None:
      lost.append(f'{instr.group(1)}: {op_name.group(1)}')
  total = sum(seen.values())
  assert total > 1000 and len(lost) <= 0.05 * total, (
      f'{len(lost)} of {total} instructions have no layer:\n'
      + '\n'.join(lost))
  stages = set(seen)
  want = {'sampler/sample_hop0', 'sampler/dedup2', 'feature_store/serve',
          'model_step/forward/GraphSAGE/conv0/lin_root',
          'model_step/forward/GraphSAGE/conv2', 'model_step/update'}
  # one shard serves its requests in place: nothing is bucketed by owner,
  # exchanged or stitched back
  routed = {'feature_store/bucket', 'feature_store/exchange',
            'feature_store/unbucket'}
  if chips > 1:
    want |= routed | {'collectives/grad_sync'}
  else:
    assert not routed & stages, routed & stages
  assert want <= stages, want - stages


def test_live_step_programs_drops_a_freed_trainer():
  gc.collect()
  before = len(device.live_step_programs())
  trainer = _tiny_trainer(1)[0]
  assert trainer in device.live_step_programs()
  assert len(device.live_step_programs()) == before + 1
  del trainer
  gc.collect()
  assert len(device.live_step_programs()) == before


def test_the_step_records_spans_only_with_the_tracer_on():
  trainer, params, opt, batch = _tiny_trainer(1)
  tracer = get_tracer()
  assert not tracer.enabled
  tracer.clear()
  params, opt, _ = trainer(params, opt, *batch)
  assert tracer.spans() == []
  tracer.enable(sample=0.0)
  try:
    trainer(params, opt, *batch)
  finally:
    tracer.disable()
  spans = {s.name: s for s in tracer.spans()}
  assert set(spans) == {'train.step', 'train.step/put',
                        'train.step/dispatch'}
  parent = spans['train.step'].span_id
  assert spans['train.step/put'].parent_id == parent
  assert spans['train.step/dispatch'].parent_id == parent
  tracer.clear()


def test_scope_profile_takes_a_session_and_leaves_no_trace(monkeypatch,
                                                           tmp_path):
  """On the CPU a session holds no TPU plane, so the reduction is handed
  what ``load_profile`` found and answers by hand; everything around it
  is the real thing."""
  trainer, params, opt, batch = _tiny_trainer(1)
  params, opt, _ = trainer(params, opt, *batch)
  monkeypatch.setenv('TMPDIR', str(tmp_path))
  monkeypatch.setattr('tempfile.tempdir', None)
  found = {}

  def reduce_(events, host_spans, step_program):
    found.update(events=events, step_program=step_program,
                 host={name for name, _, _ in host_spans})
    return {'busy_ms': 1.0}

  monkeypatch.setattr(device, 'reduce_scopes', reduce_)
  tracer = get_tracer()
  traces = trainer.step_traces
  assert trainer.scope_profile(params, opt, [batch] * 3) == {'busy_ms': 1.0}
  assert found['events'] == {} and found['step_program'] == 'jit_step'
  # the program's spans are on the profiler's host plane, and only they
  assert found['host'] == {'train.step', 'train.step/put',
                           'train.step/dispatch', 'scope_profile.wait'}
  assert not tracer.enabled and trainer.step_traces == traces
  assert os.listdir(tmp_path) == []
  tracer.clear()
