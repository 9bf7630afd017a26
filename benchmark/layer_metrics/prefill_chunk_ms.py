"""Model step: mean device time of the prefill-chunk program in the traced
window (the XLA module jitted from ``_serving_prefill_chunk_impl``)."""
from benchmark.lib.trace_reduce import module_times

MODULE = "serving_prefill_chunk"


def read(ctx):
    t = module_times(ctx["trace"], MODULE)
    return 1e3 * sum(t) / len(t) if t else None
