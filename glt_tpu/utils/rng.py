"""Seed management.

TPU-native analogue of the reference's native ``RandomSeedManager`` singleton
(reference include/common.h:36-61, used by neighbor_sampler.py:67-68): a
process-wide base seed from which functional jax PRNG keys are derived.
Every consumer folds in a fresh counter so independent samplers never share
a key stream, while the whole run stays reproducible from one seed.
"""
from __future__ import annotations

import threading

import jax


def make_key(seed: int) -> jax.Array:
  """Typed threefry PRNG key (jax's default): counter-based and
  bit-reproducible across backends. Every ``jax.random.split`` /
  ``fold_in`` downstream inherits it."""
  return jax.random.key(int(seed))


class RandomSeedManager:
  _instance = None
  _lock = threading.Lock()

  def __init__(self):
    self._seed = 42
    self._counter = 0
    self._local = threading.Lock()

  @classmethod
  def getInstance(cls) -> 'RandomSeedManager':
    with cls._lock:
      if cls._instance is None:
        cls._instance = cls()
      return cls._instance

  def setSeed(self, seed: int) -> None:
    with self._local:
      self._seed = int(seed)
      self._counter = 0

  def getSeed(self) -> int:
    with self._local:
      return self._seed

  def nextKey(self) -> jax.Array:
    # seed and counter must come from ONE lock hold: a setSeed racing
    # between the counter draw and the seed read would pair the new
    # seed with the old stream position (gltlint GLT002)
    with self._local:
      c = self._counter
      self._counter += 1
      seed = self._seed
    return jax.random.fold_in(make_key(seed), c)


def new_key() -> jax.Array:
  return RandomSeedManager.getInstance().nextKey()
