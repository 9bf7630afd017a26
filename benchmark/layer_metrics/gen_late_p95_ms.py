"""Load generator: how late, against its due time, a request was submitted
(95th percentile over the window).  A starved generator is not a fast
server."""
from benchmark.lib.harness import percentile


def read(ctx):
    lates = ctx["record"]["lates"]
    return 1e3 * percentile(lates, 95) if lates else None
