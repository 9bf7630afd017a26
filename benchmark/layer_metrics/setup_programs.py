"""Model step: executables loaded or compiled before the window — the
``load`` entries of the program's start-up record
(``benchmark/lib/setup_reduce.py``), programs and helpers alike; equals
the ``backend_compiles`` the driver's ``setup`` line prints."""
from benchmark.lib import setup_reduce


def read(ctx):
    return setup_reduce.programs(ctx)
