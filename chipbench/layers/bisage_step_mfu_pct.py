"""Layer ``model_step``: the FLOPs that forward and backward of the
configuration's user-item link model require for one step
(``chipbench/flops_bisage.py``, no dedup assumed), times steps per second
of the traced stretch, over the chip's peak. Small by design: the model's
matmuls are 64 wide and the step is the tables' bandwidth and the
sampler."""
from chipbench import flops_bisage, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  need = flops_bisage.step_flops(run['cfg'], tf['batch_per_chip'],
                                 tf['fanout'])
  rate = tr['steps'] / tr['top_window_s']
  return 100.0 * need * rate / peaks.peaks(run['device_kind'])['flops_per_s']
