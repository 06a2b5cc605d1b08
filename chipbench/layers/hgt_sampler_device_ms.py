"""Layer ``sampler``: device ms a step of the operations under the typed
step's ``sampler`` scope (every relation's hop samples, every type's
dedup) in the HGT cell, from ``chipbench/hgt_scope_window.py``."""
from chipbench import hgt_scope_window


def read(run):
  return hgt_scope_window.layer_ms(run, 'sampler')
