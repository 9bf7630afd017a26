"""Service: seconds of set-up inside the package's ``import`` phase — the
program's start-up record (``benchmark/lib/setup_reduce.py``).  The
benchmark imports JAX first, so this is the package's own modules."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "import")
