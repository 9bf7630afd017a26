"""Service: median time per output token after the first."""
from benchmark.lib.harness import percentile


def read(ctx):
    v = ctx["stats"]["tpot"]
    return 1e3 * percentile(v, 50) if v else None
