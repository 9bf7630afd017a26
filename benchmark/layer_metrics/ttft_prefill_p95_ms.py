"""Scheduler: first ``prefilling`` mark of ``Request.timeline()`` (admission)
-> the mark of the final chunk's dispatch, 95th percentile over the
window's finished requests: the middle leg of TTFT."""
from benchmark.lib import span_reduce


def read(ctx):
    return span_reduce.leg_p95_ms(ctx, "prefill")
