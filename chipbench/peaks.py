"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not in the table is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
    # 819 GB/s of HBM bandwidth, 16 GB of HBM
    'TPU v5 lite': {'flops_per_s': 197e12, 'bytes_per_s': 819e9,
                    'memory_bytes': 16e9},
}
PEAKS['TPU v5e'] = PEAKS['TPU v5 lite']


def peaks(device_kind):
  if device_kind not in PEAKS:
    raise KeyError(f'no published peaks for device kind {device_kind!r}; '
                   'add it to chipbench/peaks.py with its source')
  return PEAKS[device_kind]
