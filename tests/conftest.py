"""Test harness config: run every test on an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing the real distributed stack on a
single host (SURVEY.md §4): instead of torch.multiprocessing.spawn over
localhost rpc, we ask XLA for 8 host devices so sharding/collective code
paths execute exactly as they would on a TPU slice.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the suite's offload assertions assume the documented default (auto-on
# when spilled); an ambient GLT_HOST_OFFLOAD=0 opt-out must not leak in
os.environ.pop('GLT_HOST_OFFLOAD', None)

# Must run before jax initializes its backend: the platform and the
# virtual device count are fixed there. The shared guard owns that
# rule: glt_tpu/utils/backend.py
from glt_tpu.utils.backend import force_backend

force_backend('cpu', host_devices=8)

import jax

import numpy as np
import pytest


def pytest_configure(config):
  assert jax.devices()[0].platform == 'cpu', (
      'tests must run on the virtual CPU mesh, not the real TPU')
  assert jax.device_count() == 8


@pytest.fixture
def rng():
  return np.random.default_rng(0)
