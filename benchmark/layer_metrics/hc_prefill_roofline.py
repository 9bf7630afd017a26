"""Kernels: the prefill-chunk program's hyper-connections against the chip's
HBM.  Numerator: 13 C values a REAL prompt row and sub-layer in the model's
dtype plus every sub-layer's float32 ``phi`` once a run, from shapes;
denominator: device self time under ``hc.*`` in the chunk program."""
from benchmark.lib import xing4_reduce as R


def read(ctx):
    w = R.work(ctx)
    return R.hc_roofline_pct(ctx, R.PREFILL, w and w["prefill_tokens"])
