"""Scheduler: share of the traced window the engine thread spends inside
``serving.step`` or ``serving.submit`` and NOT inside ``serving.drain.wait``
(the one blocking fetch): the host's own work.  Near 100 the host, not the
device, sets the pace."""
from benchmark.lib import span_reduce


def read(ctx):
    return span_reduce.host_own_pct(ctx)
