"""Train step: device self time under ``optimizer`` (the AdamW update
inside the fused step) per step."""
from benchmark.lib import span_reduce

MODULE = "_step_fn"
SCOPES = ("optimizer",)


def read(ctx):
    return span_reduce.ms_per_run(ctx, MODULE, SCOPES)
