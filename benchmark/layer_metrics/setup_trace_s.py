"""Model step: seconds of set-up spent tracing functions to jaxprs (JAX's
``jaxpr_trace_duration`` events in the program's start-up record, inner
jits folded into the trace that holds them; ``benchmark/lib/
setup_reduce.py``) — Python that runs whether the persistent cache is warm
or cold."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "trace")
