"""Model step: device self time under the hyper-connection scopes (``hc.*``:
the coefficients with their Sinkhorn iteration, the read of the branch's
input, the write of its output into the streams) per run of the decode
program."""
from benchmark.lib import xing4_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.DECODE, R.HC_NAMES)
