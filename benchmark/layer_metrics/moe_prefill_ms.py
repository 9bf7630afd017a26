"""Model step: device self time under the expert scopes (``moe.*``) per run
of the prefill-chunk program (a chunk rides in the scheduler step of a
decode dispatch, so its time is in every live slot's inter-token
interval)."""
from benchmark.lib import glm4_moe_lite_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.PREFILL, R.MOE_NAMES)
