"""Layer ``model_step``: device ms a step of DGCNN forward and backward,
the loss and the update."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.layer_ms(run, 'model_step')
