"""Model step: mean device time of the decode program in the traced window
(the XLA module jitted from ``_serving_decode_steps_impl``)."""
from benchmark.lib.trace_reduce import module_times

MODULE = "serving_decode_steps"


def read(ctx):
    t = module_times(ctx["trace"], MODULE)
    return 1e3 * sum(t) / len(t) if t else None
