"""Layer ``model_step``: the FLOPs that forward and backward of the
configuration's HGT require for one step (``chipbench/flops_hgt.py``),
times steps per second of the traced stretch, over the chip's peak."""
from chipbench import flops_hgt, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  need = flops_hgt.step_flops(run['cfg'], tf['batch_per_chip'],
                              tf['fanout'], tf['seed_type'])
  rate = tr['steps'] / tr['top_window_s']
  return 100.0 * need * rate / peaks.peaks(run['device_kind'])['flops_per_s']
