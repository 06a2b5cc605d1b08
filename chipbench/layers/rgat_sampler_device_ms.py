"""Layer ``sampler``: device ms a step of the operations under the typed
step's ``sampler`` scope (every relation's hop samples, every type's
dedup), from ``chipbench/hetero_scope_window.py``."""
from chipbench import hetero_scope_window


def read(run):
  return hetero_scope_window.layer_ms(run, 'sampler')
