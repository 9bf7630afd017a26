"""Model step: seconds of set-up spent lowering jaxprs to modules (JAX's
``jaxpr_to_mlir_module_duration`` events in the program's start-up record;
``benchmark/lib/setup_reduce.py``) — paid warm and cold alike, before the
persistent cache's key can be computed."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "lower")
