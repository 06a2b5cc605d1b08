"""Layer ``sampler``: device ms a step of the operations under the
edge-seeded typed step's ``sampler`` scope (the negatives, every
relation's hop samples, every type's dedup) in the user-item cell, from
``chipbench/bisage_scope_window.py``."""
from chipbench import bisage_scope_window


def read(run):
  return bisage_scope_window.layer_ms(run, 'sampler')
