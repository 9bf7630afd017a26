"""Service: seconds of set-up inside generation-2 collections — the
``host.gc`` phases of the program's start-up record
(``benchmark/lib/setup_reduce.py``)."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "gc")
