"""Scheduler: dispatch of a request's final prefill chunk -> its first
token handed to it (``t_first``), 95th percentile: the last leg of TTFT.
With pipelining the first token rides the next decode dispatch's record and
is emitted at that record's drain."""
from benchmark.lib import span_reduce


def read(ctx):
    return span_reduce.leg_p95_ms(ctx, "lag")
