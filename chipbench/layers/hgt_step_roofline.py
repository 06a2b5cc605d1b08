"""Layer ``kernels``: the least time the chip could take for one step
under HGT (``chipbench/flops_hgt.py``: the larger of FLOPs over peak
FLOP/s and least bytes over peak bytes/s) over the measured device-busy
time per step. The step's XLA program is the kernel."""
from chipbench import flops_hgt, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  least, _ = flops_hgt.least_step_seconds(
      run['cfg'], tf['batch_per_chip'], tf['fanout'], tf['seed_type'],
      peaks.peaks(run['device_kind']))
  return 100.0 * least / (tr['top_busy_s'] / tr['steps'])
