"""Layer ``kernels``: the least time the chip could take for one SEAL
step (``chipbench/flops_seal.py``: the larger of FLOPs over peak FLOP/s
and least bytes over peak bytes/s) over the measured device-busy time per
step. The step's XLA program is the kernel."""
from chipbench import flops_seal, peaks


def read(run):
  tr = run['trace']
  least, _ = flops_seal.least_step_seconds(
      run['cfg'], run['traffic'], peaks.peaks(run['device_kind']))
  return 100.0 * least / (tr['top_busy_s'] / tr['steps'])
