"""Model step: device self time under the state-space scopes (``ssm.*``:
input projection, convolution, state update, gated norm, output
projection) per run of the decode program."""
from benchmark.lib import falcon_h1_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.DECODE, R.SSM_NAMES)
