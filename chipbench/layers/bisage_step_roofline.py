"""Layer ``kernels``: the least time the chip could take for one step of
the user-item link model (``chipbench/flops_bisage.py``: the larger of
FLOPs over peak FLOP/s and least bytes over peak bytes/s; the bytes bind:
dense Adam's seven passes over the tables) over the measured device-busy
time per step. The step's XLA program is the kernel."""
from chipbench import flops_bisage, peaks


def read(run):
  tr, tf = run['trace'], run['traffic']
  least, _ = flops_bisage.least_step_seconds(
      run['cfg'], tf['batch_per_chip'], tf['fanout'],
      peaks.peaks(run['device_kind']))
  return 100.0 * least / (tr['top_busy_s'] / tr['steps'])
