"""Service: median of due -> first token over every finished request."""
from benchmark.lib.harness import percentile


def read(ctx):
    v = ctx["stats"]["ttft"]
    return 1e3 * percentile(v, 50) if v else None
