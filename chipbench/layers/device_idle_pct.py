"""Layer ``device``: 1 - (union of the intervals in which any operation
runs on the device) / stretch, on the busiest device of the trace."""


def read(run):
  tr = run['trace']
  return 100.0 * (1.0 - tr['top_busy_s'] / tr['top_window_s'])
