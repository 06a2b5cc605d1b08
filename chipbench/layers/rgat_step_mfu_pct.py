"""Layer ``model_step``: the FLOPs that forward and backward of the
configuration's R-GAT require for one step (``chipbench/flops_rgat.py``),
times steps per second of the traced stretch, over the chip's peak."""
from chipbench import flops_rgat, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  need = flops_rgat.step_flops(run['cfg'], tf['batch_per_chip'],
                               tf['fanout'], tf['seed_type'])
  rate = tr['steps'] / tr['top_window_s']
  return 100.0 * need * rate / peaks.peaks(run['device_kind'])['flops_per_s']
