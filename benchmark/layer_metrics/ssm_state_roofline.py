"""Kernels: the decode program's one-token state update against the
chip's HBM.  Numerator: the recurrent state and conv tail of every live
slot read and written once a step and layer, from shapes (a parked slot
needs none); denominator: the device self time under ``ssm.state_update``
in the traced window.  Bandwidth-bound: 5 operations a 4-byte element."""
from benchmark.lib import falcon_h1_flops as F
from benchmark.lib import falcon_h1_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    t, _ = R.scope_seconds(ctx, R.DECODE, ("ssm.state_update",))
    w = R.work(ctx)
    if t is None or not w or not w["decode_tokens"]:
        return None
    peaks = peaks_of(ctx["device_kind"])
    m, n = ctx["model"], w["decode_tokens"]
    bound = max(F.state_update_bytes(m, n) / peaks["hbm_bytes_per_s"],
                F.state_update_flops_per_token(m) * n / peaks["bf16_flops"])
    return 100.0 * bound / t
