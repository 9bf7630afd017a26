"""Train step: device self time of the forward operations run AGAIN inside
the backward (``rematted_computation`` in the operation's name path) per
step."""
from benchmark.lib import span_reduce

MODULE = "_step_fn"


def read(ctx):
    return span_reduce.recompute_ms_per_run(ctx, MODULE)
