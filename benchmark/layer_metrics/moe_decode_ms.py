"""Model step: device self time under the expert scopes (``moe.*``: router,
dispatch, grouped products, combine, shared expert) per run of the decode
program."""
from benchmark.lib import glm4_moe_lite_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.DECODE, R.MOE_NAMES)
