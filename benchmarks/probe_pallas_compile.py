"""Escalating Pallas/Mosaic compile probe. Runs on a TPU only.

The first failing rung names the construct the installed Mosaic
refuses. Rungs 1-7 are toy kernels, from trivial to the manual-DMA
window gather:

  1 vmem_id        : identity through VMEM blocks
  2 smem_scalar    : scalar input in SMEM steering the body
  3 dma_fixed      : manual HBM->VMEM async_copy of a static slice
  4 dma_dynamic    : async_copy with pl.ds(dynamic scalar) source
  5 prefetch_grid  : PrefetchScalarGridSpec with index_map using the
                     prefetched scalars (the gather_rows pattern)
  6 gather_windows : the real window-gather kernel at toy size
  7 vmem_take2d    : in-kernel 2-D dynamic gather from a VMEM table

Rungs 8-10 are the three fused kernel FAMILIES at the widths a TPU
default would have to serve, each driven through ``NeighborSampler``
exactly as a loader would and compared bit for bit with the XLA
``sort+fused`` engine (same sampler seed, hence the same keys):

  8 per_hop_products : ``sample_hop_dedup`` once per hop, batch 1024,
                       fanout 15,10,5 on the products-shaped graph
  9 walk_products    : ``sample_walk_dedup``, the same walk as one kernel
 10 hetero_plane     : the hetero type plane at the shapes of
                       examples/hetero/train_rgnn.py

``ops/pipeline.py::hop_engine`` makes a Pallas family a TPU default
only while its rung here passes on the chip. On the v5e with jax 0.9.0
(PR 21) rungs 1, 2 and 5 pass; 3, 4, 6, 8 and 10 are refused by Mosaic
("Slice shape along dimension 0 must be aligned to tiling (1024)": a
1-D int32 HBM operand cannot be sliced at an arbitrary offset); 9 fails
in Pallas lowering (bool vector to scalar); 7 compiles and returns
wrong values. So no family is a default.

Prints one JSON line per rung and a summary line; full error texts go
to ``chiprun_out/probe_pallas_compile.json``. Exits nonzero when any
rung fails, and refuses to start when the backend is not a TPU.
"""
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, 'examples'))

import numpy as np

PRODUCTS = dict(num_nodes=2_450_000, avg_degree=25, batch=1024,
                fanout=[15, 10, 5])

#: sampler-output fields the engines' bit-identity contract covers
#: (tests/test_pallas_fused.py::EXACT_KEYS; neighbor values on masked
#: lanes are undefined per engine and not compared)
EXACT_FIELDS = ('node', 'node_count', 'row', 'col', 'edge_mask', 'batch',
                'num_sampled_nodes', 'num_sampled_edges')

_ENGINE_KNOBS = ('GLT_HOP_ENGINE', 'GLT_FUSED_WALK', 'GLT_DEDUP',
                 'GLT_FUSED_HOP')


def _sample_under(env, make_sampler, sample):
  """One batch from a FRESH sampler built under ``env`` (the engine
  knobs are read at trace time). Returns the compared fields as a flat
  dict of numpy arrays, and the engine the sampler resolved to."""
  saved = {k: os.environ.pop(k, None) for k in _ENGINE_KNOBS}
  os.environ.update(env)
  try:
    sampler = make_sampler()
    out = sample(sampler)
    return _flat(out), sampler._resolved_hop_engine()
  finally:
    for k in _ENGINE_KNOBS:
      os.environ.pop(k, None)
      if saved[k] is not None:
        os.environ[k] = saved[k]


def _flat(out):
  """The compared fields of a (Hetero)SamplerOutput, per-type dicts
  flattened, as numpy (the readback is the device fence)."""
  fields = {f: getattr(out, f) for f in EXACT_FIELDS}
  fields['seed_labels'] = out.metadata['seed_labels']
  flat = {}
  for name, v in fields.items():
    if isinstance(v, dict):
      flat.update({f'{name}[{k}]': np.asarray(a) for k, a in v.items()})
    else:
      flat[name] = np.asarray(v)
  return flat


def _assert_identical(ref, got):
  if ref.keys() != got.keys():
    raise AssertionError((sorted(ref), sorted(got)))
  for name in ref:
    np.testing.assert_array_equal(ref[name], got[name], err_msg=name)


def family_rung(walk_mode, make_sampler, sample):
  """Run the ``pallas_fused`` family under ``walk_mode`` and compare it
  with the XLA ``sort+fused`` engine (dedup 'sort' and fused hops are
  what ``auto`` resolves to on a TPU)."""
  ref, _ = _sample_under({'GLT_HOP_ENGINE': 'element'}, make_sampler,
                         sample)
  got, resolved = _sample_under(
      {'GLT_HOP_ENGINE': 'pallas_fused', 'GLT_FUSED_WALK': walk_mode},
      make_sampler, sample)
  if resolved != 'pallas_fused':
    raise AssertionError(f'family demoted to {resolved}')
  _assert_identical(ref, got)


def main():
  import jax
  if jax.default_backend() != 'tpu':
    sys.exit(f'probe_pallas_compile: backend is '
             f'{jax.default_backend()!r}, not tpu; the ladder only '
             'means something compiled by Mosaic on a chip')
  import jax.numpy as jnp
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu
  from glt_tpu.ops.pallas_kernels import interpret_default
  if interpret_default():
    sys.exit('probe_pallas_compile: GLT_PALLAS_INTERPRET is set; the '
             'ladder must compile its kernels')

  dev = jax.devices()[0]
  print(json.dumps({'device_kind': dev.device_kind,
                    'jax': jax.__version__}), flush=True)
  rng = np.random.default_rng(0)
  status, errors = {}, {}

  def rung(name, fn):
    t0 = time.time()
    try:
      out = fn()
      if out is not None:
        np.asarray(out)  # host readback: the kernel really ran
      status[name] = 'ok'
    except Exception as e:  # the ladder reports every rung, then fails
      errors[name] = f'{type(e).__name__}: {e}'
      status[name] = errors[name][:400]
    print(json.dumps({name: status[name],
                      'seconds': round(time.time() - t0, 1)}),
          flush=True)

  # 1 vmem_id
  x = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))

  def vmem_id():
    def k(i, o):
      o[:] = i[:]
    return pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

  rung('1_vmem_id', vmem_id)

  # 2 smem_scalar
  def smem_scalar():
    s = jnp.asarray([[3]], jnp.int32)

    def k(s_ref, i_ref, o_ref):
      o_ref[:] = i_ref[:] * s_ref[0, 0].astype(jnp.float32)

    return pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM))(s, x)

  rung('2_smem_scalar', smem_scalar)

  # 3 dma_fixed
  big = jnp.asarray(rng.integers(0, 99, 4096, dtype=np.int32))

  def dma_fixed():
    def k(h_ref, o_ref):
      def body(scr, sem):
        dma = pltpu.make_async_copy(h_ref.at[pl.ds(256, 128)], scr, sem)
        dma.start()
        dma.wait()
        o_ref[:] = scr[:]
      pl.run_scoped(body, scr=pltpu.VMEM((128,), jnp.int32),
                    sem=pltpu.SemaphoreType.DMA(()))

    out = pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct((128,), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM))(big)
    np.testing.assert_array_equal(out, big[256:384], 'dma_fixed')
    return out

  rung('3_dma_fixed', dma_fixed)

  # 4 dma_dynamic
  def dma_dynamic():
    st = jnp.asarray([[512]], jnp.int32)

    def k(s_ref, h_ref, o_ref):
      def body(scr, sem):
        dma = pltpu.make_async_copy(
            h_ref.at[pl.ds(s_ref[0, 0], 128)], scr, sem)
        dma.start()
        dma.wait()
        o_ref[:] = scr[:]
      pl.run_scoped(body, scr=pltpu.VMEM((128,), jnp.int32),
                    sem=pltpu.SemaphoreType.DMA(()))

    out = pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct((128,), jnp.int32),
        in_specs=[pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM))(st, big)
    np.testing.assert_array_equal(out, big[512:640], 'dma_dynamic')
    return out

  rung('4_dma_dynamic', dma_dynamic)

  # 5 prefetch_grid — gather_rows pattern on (n,1,d) singleton trick
  def prefetch_grid():
    tab = jnp.asarray(rng.normal(size=(64, 1, 128)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 64, 16, dtype=np.int32))

    def k(idx_ref, row_ref, o_ref):
      o_ref[:] = row_ref[:]

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(16,),
        in_specs=[pl.BlockSpec((1, 1, 128), lambda i, idx: (idx[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, 128), lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        k, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((16, 1, 128), jnp.float32))(
            rows, tab)
    ref = jnp.take(tab, rows, axis=0)
    np.testing.assert_allclose(out, ref, err_msg='prefetch_grid')
    return out

  rung('5_prefetch_grid', prefetch_grid)

  # 6 gather_windows toy
  def gw():
    from glt_tpu.ops.pallas_kernels import gather_windows
    arr = jnp.asarray(rng.integers(0, 99, 8192, dtype=np.int32))
    starts = jnp.asarray(
        np.sort(rng.integers(0, 8192 - 128, 64).astype(np.int32)))
    out = gather_windows(arr, starts, 128, block=8)
    ref = jnp.stack([jax.lax.dynamic_slice(arr, (int(s),), (128,))
                     for s in np.asarray(starts)])
    np.testing.assert_array_equal(out, ref, 'gather_windows')
    return out

  rung('6_gather_windows', gw)

  # 7 vmem_take2d
  def vt():
    TN, TD = 64, 128
    tab = jnp.asarray(rng.integers(0, 1 << 20, (TN, TD), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, TN * TD, (8, 3840), dtype=np.int32))

    def k(t_ref, i_ref, o_ref):
      ii = i_ref[:]
      o_ref[:] = t_ref[:][ii >> 7, ii & 127]

    out = pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct(idx.shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM))(tab, idx)
    ref = jnp.take(tab.reshape(-1), idx, mode='clip')
    np.testing.assert_array_equal(out, ref, 'vmem_take2d')
    return out

  rung('7_vmem_take2d', vt)

  # 8, 9: the homo families at products width
  from glt_tpu.data import Dataset
  from glt_tpu.sampler import NeighborSampler, NodeSamplerInput

  p = PRODUCTS
  e = p['num_nodes'] * p['avg_degree']
  grng = np.random.default_rng(0)
  src = grng.integers(0, p['num_nodes'], e, dtype=np.int64)
  dst = (grng.random(e) ** 2 * p['num_nodes']).astype(np.int64) \
      % p['num_nodes']
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]),
                num_nodes=p['num_nodes'])
  del src, dst
  seeds = grng.integers(0, p['num_nodes'], p['batch']).astype(np.int32)

  def homo_sampler():
    return NeighborSampler(ds.graph, p['fanout'], seed=0)

  def homo_sample(sampler):
    return sampler.sample_from_nodes(seeds)

  rung('8_per_hop_products',
       lambda: family_rung('per_hop', homo_sampler, homo_sample))
  rung('9_walk_products',
       lambda: family_rung('cross', homo_sampler, homo_sample))

  # 10: the hetero type plane, examples/hetero/train_rgnn.py shapes
  from common import synthetic_hetero_mag
  hds, _, cites, writes = synthetic_hetero_mag()
  hseeds = np.arange(128, dtype=np.int32)

  def hetero_sampler():
    return NeighborSampler(hds.graph, {cites: [5, 5], writes: [5, 5]},
                           edge_dir=hds.edge_dir, seed=0)

  def hetero_sample(sampler):
    return sampler.sample_from_nodes(NodeSamplerInput(hseeds, 'paper'))

  rung('10_hetero_plane',
       lambda: family_rung('per_hop', hetero_sampler, hetero_sample))

  print(json.dumps(status))
  out_dir = os.path.join(_ROOT, 'chiprun_out')
  os.makedirs(out_dir, exist_ok=True)
  with open(os.path.join(out_dir, 'probe_pallas_compile.json'), 'w') as f:
    json.dump({'device_kind': dev.device_kind, 'jax': jax.__version__,
               'status': status, 'errors': errors}, f, indent=1)
  failed = [name for name, s in status.items() if s != 'ok']
  if failed:
    sys.exit(f'probe_pallas_compile: {len(failed)} rung(s) failed: '
             + ', '.join(failed))


if __name__ == '__main__':
  main()
