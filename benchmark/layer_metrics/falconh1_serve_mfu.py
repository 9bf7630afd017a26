"""Model step, whole: model flops of every token prefilled or decoded in
the traced window, by Falcon-H1's own count (``lib/falcon_h1_flops.py``:
both branches' projections, the convolution, the scan or the state update,
attention, the head), over the window at the chip's bf16 peak.  The whole
step's share: it bounds ``ssm_state_roofline`` and ``ssm_scan_roofline``."""
from benchmark.lib import falcon_h1_reduce as R
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
