"""Kernels: the prefill-chunk program's chunked scan against the chip.
Numerator: max(operations / peak, bytes / bandwidth) of the SSD scan over
the real prompt tokens prefilled in the traced window (``lib/
falcon_h1_flops.py``: causal halves inside a chunk, the slot's state read
and written once a run); denominator: device self time under
``ssm.scan``."""
from benchmark.lib import falcon_h1_flops as F
from benchmark.lib import falcon_h1_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    t, runs = R.scope_seconds(ctx, R.PREFILL, ("ssm.scan",))
    w = R.work(ctx)
    if t is None or not w or not w["prefill_tokens"]:
        return None
    peaks = peaks_of(ctx["device_kind"])
    m, n = ctx["model"], w["prefill_tokens"]
    bound = max(F.scan_flops(m, n) / peaks["bf16_flops"],
                F.scan_bytes(m, n, runs) / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
