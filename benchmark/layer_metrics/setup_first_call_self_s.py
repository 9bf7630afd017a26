"""Model step: SELF seconds of the monitored programs' first calls during
set-up — each miss's whole dispatch less the trace, lowering and load
inside it (``benchmark/lib/setup_reduce.py``): Python outside JAX's stages
(flattening the operands, hashing the key) and the dispatch itself."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "first_call")
