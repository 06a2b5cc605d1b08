"""Layer ``model_step``: device ms a step of the sort pooling readout
(``model_step/forward/DGCNN/sort_pool``, forward and backward): one
``top_k`` a graph and the 0/1 product that takes the rows."""
from chipbench import seal_scope_window


def read(run):
  return seal_scope_window.stage_ms(
      run, 'model_step/forward/DGCNN/sort_pool')
