"""Layer ``kernels``: the least time the chip could take for one link
step (``chipbench/flops_link.py``: the larger of FLOPs over peak FLOP/s
and least bytes over peak bytes/s) over the measured device-busy time per
step, on the busiest device. The step's XLA program is the kernel."""
from chipbench import flops_link, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  least, _ = flops_link.least_step_seconds(
      run['cfg'], tf['batch_per_chip'], tf['fanout'],
      peaks.peaks(run['device_kind']))
  return 100.0 * least / (tr['top_busy_s'] / tr['steps'])
