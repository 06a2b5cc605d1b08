"""Layer ``model_step``: the FLOPs that forward and backward of the
configuration's SEAL step require (``chipbench/flops_seal.py``), times
steps per second of the traced stretch, over the chip's peak: the share
of the whole step."""
from chipbench import flops_seal, peaks


def read(run):
  tr = run['trace']
  need = flops_seal.step_flops(run['cfg'], run['traffic'])
  rate = tr['steps'] / tr['top_window_s']
  return 100.0 * need * rate / peaks.peaks(run['device_kind'])['flops_per_s']
