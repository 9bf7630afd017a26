"""Published peaks of one chip, keyed by ``device_kind`` prefix.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per chip
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s.  A device that is not in the
table is an error, never a default: a share of a peak needs a known chip.  A
PR that brings the benchmark to another chip adds its row, with its source.
"""

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_of(device_kind):
    """The peak table's row for ``device_kind``; unknown device = error."""
    for prefix, row in PEAKS.items():
        if device_kind.startswith(prefix):
            return row
    raise RuntimeError(
        f"no peaks on record for device_kind {device_kind!r}: a share of a "
        f"peak or a roofline needs a known chip; known: {sorted(PEAKS)}")
