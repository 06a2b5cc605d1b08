"""Bucketed online inference engine: k-hop sample -> feature gather ->
model forward under pre-compiled padded shapes.

XLA compiles one program per input shape, so naive request-sized
execution recompiles the whole sample+forward pipeline on every new
request size — seconds of latency per distinct size. The engine instead
serves every request through a small set of **shape buckets**: a request
for ``n`` embeddings runs in the smallest bucket ``B >= n``, padded, and
``warmup()`` compiles every bucket up front so steady-state serving
never traces again. The multi-hop sampler already compiles one program
per seed shape (sampler/neighbor_sampler.py); buckets are exactly its
cache keys, and the forward is jitted per bucket here with a trace
counter that tests (and ``compile_stats``) can assert against.

Results flow through the LRU :class:`~glt_tpu.serving.embedding_cache.
EmbeddingCache` keyed ``(node_id, model_version)``: a request whose ids
are all cached skips sampling and the forward entirely, and partial
hits shrink the computed batch to the missing unique ids.

The engine is intentionally NOT thread-safe per call (``infer`` takes an
internal lock): the sampler's key counter and program cache and the
embedding cache are shared, unguarded state. Put the
:class:`MicroBatcher` in front of it — that is also where cross-request
batching happens.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from ..data import Dataset
from ..data.feature import gather_features
from ..loader.transform import to_batch, to_hetero_batch
from ..obs import get_tracer
from ..sampler import NeighborSampler
from ..sampler.base import NodeSamplerInput
from ..utils import as_numpy
from .embedding_cache import EmbeddingCache


class InferenceEngine:
  """Online embedding/logit server over a trained GNN.

  Args:
    data: Dataset (graph + node features; labels unused).
    model: flax module whose ``apply(params, batch)`` returns a
      ``[batch_size, D]`` array for the seed rows (GraphSAGE/RGNN
      style). ``apply_fn`` overrides this contract if needed.
    params: trained parameters (e.g. restored via
      utils.checkpoint.restore_checkpoint).
    num_neighbors: serving fanout per hop, e.g. ``[15, 10, 5]``.
    buckets: padded seed-batch sizes to pre-compile, ascending. A
      request larger than the biggest bucket is served in chunks of it.
    cache: an EmbeddingCache, or None to build one of
      ``cache_capacity`` entries (0 disables caching).
    model_version: version tag for cache keys; ``set_params`` bumps it.
    seed: sampler RNG seed (serving samples fresh neighborhoods per
      request, matching the reference's inference-time sampling).
    sampler: inject a pre-built sampler instead of the default
      NeighborSampler over ``data.graph`` — how live-update serving
      plugs in a :class:`~glt_tpu.stream.StreamSampler` (whose jitted
      programs survive snapshot swaps; see ``update_snapshot``).
    input_type: REQUIRED for a hetero ``data.graph`` (dict): the seed
      node type requests address. Buckets pad the seed-type batch; the
      pipeline samples every edge type and the forward consumes a
      ``HeteroBatch`` — RGAT-style serving with the same
      zero-steady-state-recompile contract as homo.
  """

  def __init__(self, data: Dataset, model, params,
               num_neighbors: Sequence[int],
               buckets: Sequence[int] = (8, 64, 256),
               cache: Optional[EmbeddingCache] = None,
               cache_capacity: int = 100_000,
               model_version: int = 0,
               seed: Optional[int] = 0,
               apply_fn: Optional[Callable] = None,
               with_edge: bool = False,
               sampler=None,
               input_type=None):
    self._hetero = isinstance(data.graph, dict)
    if self._hetero:
      # hetero serving: requests are seed-type node ids; the bucketed
      # pipeline samples the multi-edge-type neighborhood (one program
      # per bucket) and the forward consumes a HeteroBatch. Bucket grid
      # stays 1-D: requests seed ONE type.
      assert input_type is not None, (
          'hetero serving needs input_type (the seed node type '
          'requests address)')
    self.input_type = input_type
    self.data = data
    self.model = model
    self.params = params
    self.buckets = tuple(sorted({int(b) for b in buckets}))
    assert self.buckets and self.buckets[0] > 0
    self.model_version = int(model_version)
    self.cache = cache if cache is not None \
        else EmbeddingCache(cache_capacity)
    self.sampler = sampler if sampler is not None else NeighborSampler(
        data.graph,
        dict(num_neighbors) if isinstance(num_neighbors, dict)
        else list(num_neighbors),
        edge_dir=data.edge_dir, with_edge=with_edge, seed=seed)
    self._apply_fn = apply_fn or (
        lambda params, batch: self.model.apply(params, batch))
    self._fwd = {}            # bucket -> jitted forward
    self._trace_counts = {}   # bucket -> times the forward was traced
    self.forward_calls = 0    # executed bucket runs (not traces)
    self._out_dim: Optional[int] = None
    self._warmed = False
    self._snapshot_version = 0
    self._lock = threading.Lock()

  # -- compilation -------------------------------------------------------

  def _make_forward(self, bucket: int):
    def fwd(params, batch):
      # trace-time side effect: executions never touch this counter, so
      # steady-state assertions can demand it stays flat
      self._trace_counts[bucket] = self._trace_counts.get(bucket, 0) + 1
      from ..obs.perf import count_compile
      count_compile('serve.forward')  # process-wide compiles_total{fn}
      return self._apply_fn(params, batch)
    return jax.jit(fwd)

  def _forward(self, bucket: int):
    if bucket not in self._fwd:
      self._fwd[bucket] = self._make_forward(bucket)
    return self._fwd[bucket]

  def warmup(self, publish_costs: Optional[bool] = None) -> dict:
    """Compile every bucket's sample+gather+forward pipeline once with
    dummy seeds. Serving before warmup works but pays compilation on
    first use of each bucket.

    ``publish_costs`` (default: the ``GLT_OBS_XLA_COST`` knob, off)
    additionally AOT-lowers each bucket's forward and publishes its
    XLA cost analysis as ``xla_flops{fn="serve.forward[b<bucket>]"}``
    etc. — NOTE this is one extra trace per bucket (the
    ``forward_traces`` counters each read 2 after warmup instead of
    1), which is why it is opt-in rather than ambient."""
    if publish_costs is None:
      from ..obs.perf import xla_cost_enabled
      publish_costs = xla_cost_enabled()
    with self._lock:
      for b in self.buckets:
        self._run_bucket(np.zeros(b, np.int64), b, b)
      if publish_costs:
        from ..obs.perf import instrument_compiled
        for b in self.buckets:
          batch = self.make_batch(np.zeros(b, np.int64), b, b)
          instrument_compiled(f'serve.forward[b{b}]', self._forward(b),
                              self.params, batch)
      self._warmed = True
      # warmup never inserts into the cache (only infer does), so only
      # the stats need resetting — a caller-supplied pre-populated
      # cache must survive warmup intact
      self.cache.reset_stats()
      self.forward_calls = 0
    return self.compile_stats()

  def compile_stats(self) -> dict:
    """Compilation/exec counters for the zero-recompile guarantee.

    Deliberately LOCK-FREE: infer() holds the engine lock across the
    device forward, so a wedged device would turn every stats scrape
    into a hang at exactly the moment operators need it (the stall
    path the watchdog exists for). The counters are GIL-atomic Python
    ints; a read racing an increment is off by at most one."""
    return {
        'forward_traces': dict(self._trace_counts),
        'sampler_compiled_fns': self.sampler.num_compiled_fns,
        'forward_calls': self.forward_calls,  # gltlint: disable=GLT002
    }

  @property
  def output_dim(self) -> Optional[int]:
    return self._out_dim

  @property
  def num_nodes(self) -> int:
    """Id-space bound for request validation: the seed TYPE's node
    count on a hetero graph (requests address one type)."""
    if self._hetero:
      return self.sampler._node_counts[self.input_type]
    return self.data.graph.num_nodes

  def validate_ids(self, ids: np.ndarray) -> None:
    """Reject out-of-range node ids: past the request boundary they
    would be silently clamped by the gather paths — a wrong-but-valid-
    looking embedding, cached under the bogus id forever."""
    if ids.size and (ids.min() < 0 or ids.max() >= self.num_nodes):
      bad = ids[(ids < 0) | (ids >= self.num_nodes)][:8]
      raise ValueError(
          f'node ids out of range [0, {self.num_nodes}): {bad.tolist()}')

  # -- serving -----------------------------------------------------------

  def bucket_for(self, n: int) -> int:
    for b in self.buckets:
      if n <= b:
        return b
    return self.buckets[-1]

  def make_batch(self, seeds: np.ndarray, n_valid: int, bucket: int):
    """Sample + gather a bucket-shaped Batch exactly as serving runs
    it (public so param init / benchmarks build batches through the
    same pipeline instead of re-rolling it). Hetero graphs produce a
    :class:`~glt_tpu.loader.transform.HeteroBatch` (per-type feature
    gather over the sampled node dict)."""
    if self._hetero:
      out = self.sampler.sample_from_nodes(
          NodeSamplerInput(seeds, self.input_type), n_valid=n_valid)
      # featureless node types are legal (node_loader tolerates partial
      # feature dicts the same way): gather only the types with a store
      feats = (self.data.node_features
               if isinstance(self.data.node_features, dict) else {})
      x_dict = {
          t: gather_features(feats[t], n)
          for t, n in out.node.items() if feats.get(t) is not None}
      return to_hetero_batch(out, x_dict=x_dict,
                             batch_size=bucket).replace(metadata=None)
    out = self.sampler.sample_from_nodes(seeds, n_valid=n_valid)
    x = gather_features(self.data.get_node_feature(), out.node)
    # metadata carries per-call arrays (seed labels) — stripping it
    # keeps the forward's pytree signature identical across calls
    return to_batch(out, x=x, batch_size=bucket).replace(metadata=None)

  def init_params(self, rng_key):
    """Initialize (and install) model params against a bucket-shaped
    batch — for serving fresh/benchmark weights without a training
    loop."""
    b = self.buckets[0]
    batch = self.make_batch(np.zeros(b, np.int64), b, b)
    params = self.model.init(rng_key, batch)
    with self._lock:
      self.params = params
    return params

  def _run_bucket(self, seeds: np.ndarray, n_valid: int,
                  bucket: int) -> np.ndarray:
    """One padded pipeline pass; returns rows [:n_valid]."""
    padded = seeds
    if padded.shape[0] < bucket:
      padded = np.concatenate(
          [padded, np.full(bucket - padded.shape[0], padded[0] if
                           padded.size else 0, padded.dtype)])
    tracer = get_tracer()
    # sample.multihop / gather.features spans open inside make_batch;
    # the bucket span parents them and (np.asarray below is a full
    # device sync) carries the true end-to-end stage time
    with tracer.span('serve.bucket', bucket=bucket,
                     n_valid=int(n_valid)):
      batch = self.make_batch(padded, n_valid, bucket)
      with tracer.span('serve.forward', bucket=bucket):
        emb = self._forward(bucket)(self.params, batch)
        self.forward_calls += 1
        rows = np.asarray(emb)[:n_valid]
    if self._out_dim is None:
      self._out_dim = int(rows.shape[1])
    return rows

  def infer(self, ids) -> np.ndarray:
    """Embeddings/logits for ``ids`` (duplicates allowed), aligned with
    the input order: cache hits served directly, the missing unique ids
    computed through the smallest fitting bucket (chunked by the
    largest bucket when needed) and inserted back into the cache."""
    ids_np = as_numpy(ids).astype(np.int64).reshape(-1)
    if ids_np.size == 0:
      return np.zeros((0, self._out_dim or 0), np.float32)
    with self._lock:
      version = self.model_version
      local = self.cache.lookup(ids_np, version)
      missing = np.unique(ids_np[~np.isin(
          ids_np, np.fromiter(local, np.int64, len(local)))]) \
          if local else np.unique(ids_np)
      lo = 0
      while lo < missing.size:
        chunk = missing[lo:lo + self.buckets[-1]]
        lo += chunk.size
        bucket = self.bucket_for(chunk.size)
        rows = self._run_bucket(chunk, chunk.size, bucket)
        self.cache.insert(chunk, rows, version)
        for i, row in zip(chunk, rows):
          local[int(i)] = row
      return np.stack([local[int(i)] for i in ids_np])

  def stale_serve(self, ids):
    """Degradation tier: answer from the versioned EmbeddingCache ONLY
    (any live version, newest first), zero-filling true misses —
    never touches the sampler or the forward, and deliberately does
    NOT take the engine lock (the lock is exactly what a wedged infer
    is sitting on). Returns ``(rows [n, D], cached_mask [n])`` so the
    caller can count stale serves vs zero-fills.

    Raises RuntimeError when the output width is unknown (the engine
    never completed a forward) — there is nothing to degrade TO."""
    ids_np = as_numpy(ids).astype(np.int64).reshape(-1)
    found = self.cache.lookup_stale(ids_np)
    dim = self._out_dim
    if dim is None and found:
      dim = int(next(iter(found.values())).shape[0])
    if dim is None:
      raise RuntimeError(
          'stale_serve before any completed forward: output dim '
          'unknown and the cache is empty')
    out = np.zeros((ids_np.size, dim), np.float32)
    mask = np.zeros(ids_np.size, bool)
    for k, i in enumerate(ids_np.tolist()):
      row = found.get(int(i))
      if row is not None:
        out[k] = row
        mask[k] = True
    return out, mask

  # -- invalidation hooks ------------------------------------------------

  def set_params(self, params, bump_version: bool = True) -> int:
    """Hot-swap model parameters. With ``bump_version`` (default) the
    cache version advances so stale embeddings stop hitting instantly;
    the jitted programs are shape-stable and need no recompile."""
    with self._lock:
      self.params = params
      if bump_version:
        self.model_version += 1
      # return the version from THIS swap's lock hold: reading it
      # after release could observe a concurrent swap's bump (GLT002)
      return self.model_version

  def invalidate(self, ids=None, version=None) -> int:
    """Cache invalidation serialized against in-flight infer (the
    engine lock): without it, invalidating ids an infer is currently
    computing would drop nothing and the stale rows would be inserted
    right after."""
    with self._lock:
      if ids is not None:
        ids = as_numpy(ids).reshape(-1).tolist()
      return self.cache.invalidate(ids, version)

  def invalidate_nodes(self, ids) -> int:
    """Feature/graph update hook: drop cached embeddings of ``ids``
    across all versions."""
    return self.invalidate(ids=ids)

  @property
  def snapshot_version(self) -> int:
    """The stream-snapshot version this engine last swapped onto (0 =
    the construction-time graph). Read under the engine lock so a
    caller never observes the version of a swap whose invalidation has
    not landed yet — the consistency token the fleet router threads
    through `apply_delta` propagation."""
    with self._lock:
      return self._snapshot_version

  def update_snapshot(self, snapshot, touched_ids=None,
                      expand_in_neighbors: bool = False,
                      version: Optional[int] = None) -> int:
    """Swap serving onto a new stream snapshot (glt_tpu.stream).

    Under the engine lock (serialized against in-flight infer): install
    the snapshot's Feature as the gather source, then fan the touched
    node ids into :meth:`EmbeddingCache.invalidate` so no embedding
    computed against the old graph/features is ever served again. An
    in-flight request that sampled the old snapshot finishes on it
    (RCU) and any stale rows it caches are swept here, because the
    invalidation runs strictly after the swap.

    Args:
      snapshot: a :class:`glt_tpu.stream.Snapshot`; its ``feature``
        (when not None) replaces ``data.node_features``.
      touched_ids: node ids whose neighborhoods/features changed; None
        invalidates the whole cache (conservative fallback).
      expand_in_neighbors: additionally invalidate the reverse-layout
        1-hop neighborhood of the touched ids (``Snapshot.
        expand_affected`` via the CSC view for a CSR base) — the nodes
        whose cached embeddings *aggregate over* a touched node.
      version: the snapshot's version token (``Snapshot.version`` /
        the ingestor flush info ``'version'``); None auto-increments.
        Stamped in the SAME lock hold as the swap+invalidation, so
        :attr:`snapshot_version` == v implies version-v features are
        installed AND every pre-v cached row of a touched id is gone.

    Returns the number of cache entries dropped.
    """
    if self._hetero:
      # the stream/snapshot machinery is homogeneous (StreamSampler,
      # Snapshot.feature are single-type); silently installing a homo
      # Feature over the per-type dict would serve featureless hetero
      # batches from then on — refuse loudly instead
      raise NotImplementedError(
          'update_snapshot is homogeneous-only: hetero serving has no '
          'stream snapshot lineage yet (invalidate_nodes/invalidate '
          'remain available)')
    with self._lock:
      if snapshot.feature is not None:
        self.data.node_features = snapshot.feature
      self._snapshot_version = int(version) if version is not None \
          else self._snapshot_version + 1
      if touched_ids is None:
        return self.cache.invalidate()
      ids = as_numpy(touched_ids).astype(np.int64).reshape(-1)
      if expand_in_neighbors and ids.size:
        ids = snapshot.expand_affected(ids)
      ids = ids[(ids >= 0) & (ids < self.num_nodes)]
      if ids.size == 0:
        return 0
      return self.cache.invalidate(ids=ids.tolist())
