"""Train step: mean device time of the step's XLA module in the traced
window (the program jitted from ``TrainStep._step_fn``)."""
from benchmark.lib.trace_reduce import module_times

MODULE = "_step_fn"


def read(ctx):
    t = module_times(ctx["trace"], MODULE)
    return 1e3 * sum(t) / len(t) if t else None
