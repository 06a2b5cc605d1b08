"""Feature store with a device hot-cache and host spill.

Reference: graphlearn_torch/python/data/feature.py:32-283 and the native
UnifiedTensor (csrc/cuda/unified_tensor.cu). The reference splits rows by
``split_ratio`` into a GPU part (replicated per NVLink DeviceGroup) and a
pinned-CPU zero-copy part read over UVA inside GatherTensorKernel
(unified_tensor.cu:35-81). TPU-native translation:

  * hot rows  -> one jax array in HBM, gathered in-jit (``jnp.take``; the
    XLA gather runs at HBM bandwidth which is exactly what the warp-per-row
    GatherTensorKernel achieves on GPU);
  * cold rows -> by default ALSO a pinned-host jax array gathered inside
    the jitted collate (``gather_mixed``: a compute_on('device_host') read
    staged by XLA — the true zero-copy/UVA analogue); with
    host_offload=False, numpy in host RAM gathered between device calls,
    overlapped by the loader's prefetch thread.

DeviceGroup/NVLink replication (feature.py:179-199) and CUDA-IPC sharing
(feature.py:209-261) have no TPU equivalent: under SPMD one sharded global
array is addressable from every chip, and the distributed feature store
(glt_tpu.distributed.dist_feature) shards rows over the mesh instead.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import as_numpy


@jax.jit
def _mixed_gather(hot: jax.Array, cold: jax.Array,
                  rows: jax.Array) -> jax.Array:
  """hot [H, D] device block; cold [C, D] pinned-host block; rows [B]
  absolute row indices (cold row r lives at cold[r - H]). Index
  arithmetic stays on device; the cold read runs host-side via raw
  indexing (bounds ops would materialize device-space constants inside
  the host region)."""
  from jax.experimental import compute_on
  h = hot.shape[0]
  cold_idx = jnp.clip(rows - h, 0, cold.shape[0] - 1)
  idx_h = jax.device_put(cold_idx, jax.memory.Space.Host)
  with compute_on.compute_on('device_host'):
    c = cold[idx_h]
  c = jax.device_put(c, jax.memory.Space.Device)
  if h == 0:  # static shape: the whole table is cold
    return c
  safe = jnp.where(rows < h, rows, 0)
  x = jnp.take(hot, safe, axis=0)
  return jnp.where((rows >= h)[:, None], c.astype(x.dtype), x)


@jax.jit
def _host_rows_gather(cold: jax.Array, idx: jax.Array) -> jax.Array:
  """Read rows of a pinned-host block (eager gathers cannot mix memory
  spaces, so even host-side convenience reads go through this jitted
  compute_on program)."""
  from jax.experimental import compute_on
  idx_h = jax.device_put(jnp.clip(idx, 0, cold.shape[0] - 1),
                         jax.memory.Space.Host)
  with compute_on.compute_on('device_host'):
    out = cold[idx_h]
  return jax.device_put(out, jax.memory.Space.Device)


class Feature:
  """2-D feature table split into [hot | cold] rows.

  Rows [0, hot_count) live on device, rows [hot_count, N) on host. Callers
  that reorder rows by hotness first (see :func:`glt_tpu.data.reorder.
  sort_by_in_degree`) get the reference's cache behavior: frequently
  sampled nodes resolve entirely in HBM.

  Args:
    feats: [N, D] array-like.
    split_ratio: fraction of rows resident on device (reference semantics,
      feature.py:101-140). 1.0 = fully device-resident (DMA mode), 0.0 =
      fully host (pure zero-copy mode).
    id2index: optional dense global-id -> row map applied before lookup
      (reference feature.py:142-155).
    dtype: optional cast (e.g. jnp.bfloat16 for fp16-style compression,
      examples/igbh compress path).
  """

  def __init__(self, feats, split_ratio: float = 1.0,
               id2index: Optional[np.ndarray] = None,
               device: Optional[jax.Device] = None,
               dtype=None, host_offload: Optional[bool] = None):
    feats = as_numpy(feats)
    if feats.ndim == 1:
      feats = feats[:, None]
    self._host_full = feats
    self.split_ratio = float(split_ratio)
    self.hot_count = int(round(feats.shape[0] * self.split_ratio))
    self.device = device
    self.dtype = dtype if dtype is not None else feats.dtype
    self._id2index = as_numpy(id2index)
    self._id2index_dev = None
    self._hot = None
    self._cold = None
    # host_offload: None = auto (on when spilled unless
    # GLT_HOST_OFFLOAD=0) — cold rows then ALSO live as a pinned-host
    # jax array served in-jit by gather_mixed (the UVA analog,
    # reference unified_tensor.cu:202-231); False keeps only the
    # numpy host phase (gather_cold_host)
    self._host_offload = host_offload
    self.cold_array = None
    self._initialized = False

  # -- lazy split/placement (reference lazy-init pattern, feature.py:29) --

  def lazy_init(self) -> None:
    if self._initialized:
      return
    n_hot = self.hot_count
    hot_np = self._host_full[:n_hot]
    self._hot = jax.device_put(
        jnp.asarray(hot_np, dtype=self.dtype), self.device)
    self._cold = self._host_full[n_hot:]
    if self._id2index is not None:
      self._id2index_dev = jax.device_put(
          jnp.asarray(self._id2index), self.device)
    from ..utils.offload import maybe_pin_host, offload_requested
    self._cold_count = int(self._cold.shape[0])
    if offload_requested(self._host_offload, self._cold_count > 0) \
        and self._cold_count:
      # cast in numpy and device_put the numpy array STRAIGHT into host
      # memory: jnp.asarray would first materialize the whole cold block
      # on the default device, which is exactly the HBM allocation a
      # beyond-HBM cold block cannot afford (the sharded builders in
      # parallel/dist_feature.py already follow this rule)
      cold_np = self._cold.astype(
          np.dtype(jnp.dtype(self.dtype)), copy=False)
      self.cold_array = maybe_pin_host(
          lambda: jax.device_put(cold_np, jax.memory.Space.Host),
          self._host_offload)
      if self.cold_array is not None:
        # the pinned block IS the cold copy; keeping the numpy view
        # would pin _host_full and double the cold footprint
        self._cold = None
    self._host_full = None  # single-copy invariant, as in the reference
    self._initialized = True

  # -- geometry ----------------------------------------------------------

  @property
  def shape(self):
    if self._initialized:
      return (self._hot.shape[0] + self._cold_count,
              self._hot.shape[1])
    return self._host_full.shape

  @property
  def num_rows(self) -> int:
    return self.shape[0]

  @property
  def feature_dim(self) -> int:
    return self.shape[1]

  @property
  def id_space(self) -> int:
    """Size of the id domain lookups accept: the id2index table length
    when an id map is configured (partitioned stores take GLOBAL ids),
    else the row count."""
    return (self._id2index.shape[0] if self._id2index is not None
            else self.num_rows)

  @property
  def fully_device_resident(self) -> bool:
    return self.hot_count >= self.num_rows

  @property
  def device_part(self) -> jax.Array:
    self.lazy_init()
    return self._hot

  @property
  def id2index(self):
    self.lazy_init()
    return self._id2index_dev

  # -- lookup ------------------------------------------------------------

  def map_ids(self, ids):
    if self._id2index is None:
      return ids
    if isinstance(ids, np.ndarray):
      return self._id2index[ids]
    self.lazy_init()
    return jnp.take(self._id2index_dev, ids, mode='clip')

  def device_gather(self, rows: jax.Array) -> jax.Array:
    """Jit-safe gather; only valid when fully device resident (hot==all).
    ``rows`` are post-id2index row indices."""
    self.lazy_init()
    return jnp.take(self._hot, rows, axis=0, mode='clip')

  def gather_mixed(self, rows: jax.Array) -> jax.Array:
    """Jit-served gather over BOTH residency classes: hot rows from the
    device block, cold rows from the pinned-host block via a
    compute_on('device_host') gather — one compiled program, no host
    phase between batches. Requires the offloaded cold block
    (``cold_array``); loaders fall back to gather_cold_host otherwise."""
    self.lazy_init()
    assert self.cold_array is not None, 'host offload inactive'
    return _mixed_gather(self._hot, self.cold_array, rows)

  def cold_block_numpy(self) -> np.ndarray:
    """The whole cold block as numpy, whichever residency holds it
    (store builders reassemble [hot | cold] through this)."""
    self.lazy_init()
    if self._cold is not None:
      return self._cold
    if self.cold_array is not None:
      return np.asarray(self.cold_array)
    return np.zeros((0, self.feature_dim), self.dtype)

  def gather_cold_host(self, rows: np.ndarray) -> np.ndarray:
    """Host gather of cold rows (rows are absolute; caller pre-filters
    rows >= hot_count). The UVA-read analogue; offloaded stores serve
    the same rows from the pinned block."""
    self.lazy_init()
    if self._cold is not None:
      return np.asarray(
          self._cold[rows - self.hot_count], dtype=self.dtype)
    return np.asarray(
        _host_rows_gather(self.cold_array,
                          jnp.asarray(rows - self.hot_count)),
        dtype=self.dtype)

  def stage_cold_rows(self, nodes: np.ndarray,
                      counts: np.ndarray) -> np.ndarray:
    """Host-gather the cold rows for pre-sampled node stacks — the
    single-store counterpart of ``ShardedFeature.stage_cold_rows``
    (which is what the SPMD streaming trainer in parallel/train.py
    uses). This one is the staging primitive for loader-driven
    single-store pipelines that pre-sample and then overlap the host
    cold gather with device compute.

    Args:
      nodes: [..., B] POST-id2index row indices (apply ``map_ids``
        first when an id map is configured).
      counts: [...] valid-slot counts per node stack.

    Returns [..., B, D] numpy: cold-row values on cold valid lanes,
    zeros elsewhere (hot lanes resolve on device; merging is one
    elementwise add/where).
    """
    self.lazy_init()
    nodes = as_numpy(nodes).astype(np.int64)
    counts = as_numpy(counts)
    valid = np.arange(nodes.shape[-1]) < counts[..., None]
    cold = valid & (nodes >= self.hot_count) & (nodes < self.num_rows)
    np_dtype = np.dtype(jnp.dtype(self.dtype))
    out = np.zeros(nodes.shape + (self.feature_dim,), np_dtype)
    lanes = np.nonzero(cold)
    if lanes[0].size:
      out[lanes] = self.gather_cold_host(nodes[lanes]).astype(np_dtype)
    return out

  def with_updated_rows(self, ids, values) -> 'Feature':
    """Functional row update: a NEW Feature sharing every buffer with
    this one except the updated rows — the snapshot-isolation primitive
    of the stream subsystem (readers of the old Feature keep seeing the
    old values; jitted gathers against either are shape-identical, so
    swapping costs no recompile).

    Hot rows ride jax's functional ``.at[].set`` (copy-on-write of the
    device block); cold rows copy the host block once per call, so
    confine streams with heavy cold-row churn to split_ratio=1.0
    stores. Offloaded (pinned-host) cold blocks reject cold-row updates
    — re-pinning per update would thrash the very placement the offload
    exists for.
    """
    self.lazy_init()
    ids = as_numpy(ids).astype(np.int64).reshape(-1)
    values = as_numpy(values)
    if values.ndim == 1:
      values = values[:, None]
    assert values.shape == (ids.shape[0], self.feature_dim), (
        f'expected {(ids.shape[0], self.feature_dim)} update block, '
        f'got {values.shape}')
    rows = self.map_ids(ids)
    if isinstance(rows, jax.Array):
      rows = as_numpy(rows)
    rows = rows.astype(np.int64)
    if rows.size and (rows.min() < 0 or rows.max() >= self.num_rows):
      raise ValueError(
          f'feature row out of range [0, {self.num_rows})')
    out = Feature.__new__(Feature)
    out.__dict__.update(self.__dict__)
    hot_sel = rows < self.hot_count
    if hot_sel.any():
      np_dtype = np.dtype(jnp.dtype(self.dtype))
      out._hot = self._hot.at[jnp.asarray(rows[hot_sel])].set(
          jnp.asarray(values[hot_sel].astype(np_dtype)))
    if (~hot_sel).any():
      assert self.cold_array is None, (
          'cold-row updates are unsupported on host-offloaded stores; '
          'use host_offload=False or keep updated rows in the hot '
          'split')
      cold = self._cold.copy()
      cold[rows[~hot_sel] - self.hot_count] = values[~hot_sel]
      out._cold = cold
    return out

  def __getitem__(self, ids) -> np.ndarray:
    """Host-side convenience lookup returning numpy (reference cpu_get,
    feature.py:157-164)."""
    self.lazy_init()
    ids = as_numpy(ids).astype(np.int64)
    rows = self.map_ids(ids)
    out = np.empty((rows.shape[0], self.feature_dim), dtype=self.dtype)
    hot_mask = rows < self.hot_count
    if hot_mask.any():
      out[hot_mask] = np.asarray(
          jnp.take(self._hot, jnp.asarray(rows[hot_mask]), axis=0))
    if (~hot_mask).any():
      out[~hot_mask] = self.gather_cold_host(rows[~hot_mask])
    return out


def gather_features(feat: Optional[Feature], node) -> Optional[jax.Array]:
  """Batch gather over a Feature across BOTH residency classes — the
  single collate-time gather path shared by the training loaders
  (loader.node_loader) and the online serving engine (serving.engine).
  Hot rows stay on device; cold rows ride the pinned-host block
  (gather_mixed) when offloaded, else the host phase."""
  if feat is None:
    return None
  from ..obs import get_tracer
  tracer = get_tracer()
  if tracer.enabled:
    _out = {}
    with tracer.span('gather.features', sync=lambda: _out.get('x')):
      _out['x'] = x = _gather_features(feat, node)
    return x
  return _gather_features(feat, node)


def _gather_features(feat: Feature, node):
  rows = feat.map_ids(node)
  if feat.fully_device_resident:
    return feat.device_gather(rows)
  feat.lazy_init()  # offload is decided at placement time
  if feat.cold_array is not None:
    # host-offloaded cold block: one jitted program serves both
    # residency classes (compute_on host gather inside) — no host
    # phase between batches at all (jnp.asarray is a no-op for rows
    # already on device)
    return feat.gather_mixed(jnp.asarray(rows))
  # legacy mixed residency (host_offload=False): hot rows stay on
  # device end-to-end; only the cold slice crosses host->device (the
  # UVA-read analogue). The previous design pulled the hot gather D2H
  # and re-uploaded the whole batch — hot rows crossed PCIe twice,
  # defeating the split.
  rows_np = as_numpy(rows).astype(np.int64)
  if feat.hot_count == 0:
    # no device block at all (split_ratio=0.0): the whole batch is
    # cold; an empty jnp.take would raise, so serve host-side only
    return jnp.asarray(feat.gather_cold_host(rows_np)
                       .astype(feat.dtype))
  rows_dev = jnp.asarray(rows_np)
  hot = jnp.where(rows_dev < feat.hot_count, rows_dev, 0)
  x = feat.device_gather(hot)  # cold lanes junk
  cold_idx = np.nonzero(rows_np >= feat.hot_count)[0]
  if cold_idx.size:
    cold_vals = feat.gather_cold_host(rows_np[cold_idx]) \
        .astype(feat.dtype)
    # pad to the next power of two (duplicating the first cold lane)
    # so the eager scatter compiles O(log B) shapes, not one per batch
    cap = 1 << (int(cold_idx.size - 1)).bit_length()
    pad = cap - cold_idx.size
    if pad:
      cold_idx = np.concatenate(
          [cold_idx, np.full(pad, cold_idx[0], cold_idx.dtype)])
      cold_vals = np.concatenate(
          [cold_vals, np.broadcast_to(cold_vals[0], (pad,) +
                                      cold_vals.shape[1:])])
    x = x.at[jnp.asarray(cold_idx)].set(jax.device_put(cold_vals))
  return x
