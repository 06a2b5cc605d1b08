"""Layer ``model_step``: device ms a step of the operations under the
``attention`` and ``aggregate`` scopes of every relation's convolution
(logits, segment softmax, weighted sum; forward and backward), from
``chipbench/hetero_scope_window.py``. The rest of ``rgat_model_device_ms``
is the projections, the head and the update."""
from chipbench import hetero_scope_window


def read(run):
  return hetero_scope_window.stage_ms(run, 'model_step', 'attention',
                                      'aggregate')
