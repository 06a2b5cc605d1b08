"""Layer ``model_step``: device ms a step of the operations under the
edge-seeded typed step's ``model_step`` scope (the embedding take, both
encoders, the decoder, the link loss, their backward pass, the update of
the tables and of the rest), from ``chipbench/bisage_scope_window.py``."""
from chipbench import bisage_scope_window


def read(run):
  return bisage_scope_window.layer_ms(run, 'model_step')
