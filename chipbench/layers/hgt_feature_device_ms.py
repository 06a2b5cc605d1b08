"""Layer ``feature_store``: device ms a step of the operations under the
typed step's ``feature_store`` scope (every type's row gather) in the HGT
cell, from ``chipbench/hgt_scope_window.py``."""
from chipbench import hgt_scope_window


def read(run):
  return hgt_scope_window.layer_ms(run, 'feature_store')
