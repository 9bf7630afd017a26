"""Model step: forward flops of the prompt tokens prefilled in the traced
window (real tokens only: a padded tail chunk counts what it holds) over
the prefill-chunk program's device time at the chip's bf16 peak."""
from benchmark.lib.peaks import peaks_of
from benchmark.lib.trace_reduce import module_seconds

MODULE = "serving_prefill_chunk"


def read(ctx):
    t = module_seconds(ctx["trace"], MODULE)
    work = ctx["work"]["prefill_flops"]
    if not t or not work:
        return None
    return 100.0 * work / (t * peaks_of(ctx["device_kind"])["bf16_flops"])
