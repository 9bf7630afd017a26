"""Train step: device self time under ``lm_head`` and ``loss`` (the chunked
vocabulary matmul and cross-entropy), forward, backward and recompute, per
step."""
from benchmark.lib import span_reduce

MODULE = "_step_fn"
SCOPES = ("lm_head", "loss")


def read(ctx):
    return span_reduce.ms_per_run(ctx, MODULE, SCOPES)
