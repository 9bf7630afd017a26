"""Model step: forward flops of the tokens decoded in the traced window
(every matmul parameter once per token plus attention over its live
context) over the decode program's device time at the chip's bf16 peak."""
from benchmark.lib.peaks import peaks_of
from benchmark.lib.trace_reduce import module_seconds

MODULE = "serving_decode_steps"


def read(ctx):
    t = module_seconds(ctx["trace"], MODULE)
    work = ctx["work"]["decode_flops"]
    if not t or not work:
        return None
    return 100.0 * work / (t * peaks_of(ctx["device_kind"])["bf16_flops"])
