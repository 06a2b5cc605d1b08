"""Layer ``loader``: wall ms per step that the driving loop spends not
blocked on a device result (staging seeds and keys, the dispatch call),
mean over the window. From the benchmark's own host clock."""


def read(run):
  win = run['window']
  return win['host_s'] * 1e3 / win['steps']
