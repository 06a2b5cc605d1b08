"""Shared helpers for examples: synthetic datasets standing in for OGB
downloads (this environment has no network egress). Generators are
scale-parameterized so the same scripts run as smoke tests or at
products-scale."""
from __future__ import annotations

import os

from glt_tpu.utils.backend import configure_compile_cache, force_backend

# honor GLT_PLATFORM/GLT_BENCH_PLATFORM (must run before backend init)
force_backend()
configure_compile_cache()

import numpy as np

from glt_tpu.data import Dataset, sort_by_in_degree


def synthetic_products(num_nodes=24_000, avg_degree=25, feat_dim=100,
                       num_classes=47, seed=0, split_ratio=1.0,
                       sort_features=False):
  """ogbn-products-shaped synthetic graph (2.45M nodes / 62M edges at
  full scale; defaults are a 1000x smaller smoke config)."""
  rng = np.random.default_rng(seed)
  e = num_nodes * avg_degree
  src = rng.integers(0, num_nodes, e, dtype=np.int64)
  # mild power-law: square a uniform to concentrate on low ids
  dst = (rng.random(e) ** 2 * num_nodes).astype(np.int64) % num_nodes
  feats = rng.normal(size=(num_nodes, feat_dim)).astype(np.float32)
  # learnable labels: block structure + feature signal
  w = rng.normal(size=(feat_dim, num_classes)).astype(np.float32)
  labels = np.argmax(feats @ w, axis=1).astype(np.int32)
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index=np.stack([src, dst]), num_nodes=num_nodes)
  ds.init_node_features(
      feats, sort_func=sort_by_in_degree if sort_features else None,
      split_ratio=split_ratio)
  ds.init_node_labels(labels)
  ds.random_node_split(num_val=0.1, num_test=0.1)
  return ds, num_classes


def synthetic_hetero_mag(num_papers=2_000, num_authors=1_000,
                         feat_dim=64, num_classes=8, seed=0):
  """ogbn-mag-shaped hetero graph: paper-cites-paper, author-writes-paper."""
  rng = np.random.default_rng(seed)
  cites = ('paper', 'cites', 'paper')
  writes = ('author', 'writes', 'paper')
  pp = np.stack([rng.integers(0, num_papers, num_papers * 8),
                 rng.integers(0, num_papers, num_papers * 8)])
  ap = np.stack([rng.integers(0, num_authors, num_papers * 3),
                 rng.integers(0, num_papers, num_papers * 3)])
  ds = Dataset(edge_dir='out')
  ds.init_graph(edge_index={cites: pp, writes: ap},
                num_nodes={'paper': num_papers, 'author': num_authors})
  pf = rng.normal(size=(num_papers, feat_dim)).astype(np.float32)
  af = rng.normal(size=(num_authors, feat_dim)).astype(np.float32)
  w = rng.normal(size=(feat_dim, num_classes)).astype(np.float32)
  labels = np.argmax(pf @ w, 1).astype(np.int32)
  ds.init_node_features({'paper': pf, 'author': af})
  ds.init_node_labels({'paper': labels})
  return ds, num_classes, cites, writes
