"""Device: share of the traced window's device self time that falls under
a name of the program's vocabulary — the guard on the per-scope metrics
(what it does not cover, they cannot see)."""
from benchmark.lib import span_reduce


def read(ctx):
    return span_reduce.coverage_pct(ctx)
