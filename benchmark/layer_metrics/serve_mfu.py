"""Model step, whole: model flops of every token prefilled or decoded in
the traced window over the window at the chip's bf16 peak.  It bounds the
kernels' rooflines: a path taken off the device leaves them silent, not
this."""
from benchmark.lib.peaks import peaks_of


def read(ctx):
    w, window = ctx["work"], ctx["trace"]["window_s"]
    work = w["decode_flops"] + w["prefill_flops"]
    if not window or not work:
        return None
    return 100.0 * work / (window * ctx["chips"]
                           * peaks_of(ctx["device_kind"])["bf16_flops"])
