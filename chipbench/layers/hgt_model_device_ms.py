"""Layer ``model_step``: device ms a step of the operations under the
typed step's ``model_step`` scope (HGT forward, backward and the
optimizer's update), from ``chipbench/hgt_scope_window.py``."""
from chipbench import hgt_scope_window


def read(run):
  return hgt_scope_window.layer_ms(run, 'model_step')
