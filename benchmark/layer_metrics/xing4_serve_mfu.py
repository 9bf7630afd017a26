"""Model step, whole: model flops of every token prefilled or decoded in
the traced window, by this architecture's own count (``lib/xing4_flops.py``:
the ACTIVE parameters a token — attention, router, the chosen and the shared
experts, the hyper-connections' coefficient products, the head — and the
absorbed attention over the live context), over the window at the chip's
bf16 peak.  The whole step's share: it bounds the ``moe_*`` / ``mla_*`` /
``hc_*`` rooflines."""
from benchmark.lib import xing4_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    w, window = R.work(ctx), ctx["trace"]["window_s"]
    if not w or not window:
        return None
    flops = w["decode_flops"] + w["prefill_flops"]
    if not flops:
        return None
    return 100.0 * flops / (window * ctx["chips"]
                            * peaks_of(ctx["device_kind"])["bf16_flops"])
