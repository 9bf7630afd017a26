"""Service: SELF seconds of construction before the window — the phases
``serving.init`` (with ``.params`` and ``.cache``) or ``train.build`` of the
program's start-up record, less the compile stages nested in them
(``benchmark/lib/setup_reduce.py``): the weights pytree, the cache leaves,
the optimizer's state, as Python and device allocation."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.seconds(ctx, "construct")
