"""Layer ``model_step``: the FLOPs that forward and backward of the
configuration's link-prediction step require (``chipbench/flops_link.py``),
times steps per second of the traced stretch, over the chip's peak. Per
chip: every chip of a cell steps a batch of its own."""
from chipbench import flops_link, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  need = flops_link.step_flops(run['cfg'], tf['batch_per_chip'],
                               tf['fanout'])
  rate = tr['steps'] / tr['top_window_s']
  return 100.0 * need * rate / peaks.peaks(run['device_kind'])['flops_per_s']
