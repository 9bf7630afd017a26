"""Model step: seconds of set-up inside JAX's backend-compile events of the
program's start-up record (``benchmark/lib/setup_reduce.py``): on a warm
persistent cache the key, the retrieval, deserialising and loading the
executable; on a cold one the compilation itself."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "load")
