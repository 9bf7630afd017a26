"""Kernels: the decode program's hyper-connections against the chip's HBM.
Numerator: 13 C values a live row and sub-layer in the model's dtype (the
streams read once for coefficients and branch input, the streams and the
branch's output read and the streams written) plus every sub-layer's
float32 ``phi`` once a run, from shapes; denominator: device self time under
``hc.*`` in the decode program.  Single digits say latency-bound: tens of
small kernels a sub-layer over 64 rows."""
from benchmark.lib import xing4_reduce as R


def read(ctx):
    w = R.work(ctx)
    return R.hc_roofline_pct(ctx, R.DECODE, w and w["decode_tokens"])
