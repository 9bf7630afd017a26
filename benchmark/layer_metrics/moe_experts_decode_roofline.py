"""Kernels: the decode program's grouped expert products against the chip's
HBM.  Numerator: the weights of the experts TOUCHED, once a layer and run
(the window's mean of ``serving_moe_experts_touched_total`` a decode run x
the traced window's runs), plus the pairs' activations, from shapes;
denominator: device self time under ``moe.experts`` in the decode program.
Bandwidth-bound: two pairs an expert."""
from benchmark.lib import glm4_moe_lite_flops as F
from benchmark.lib import glm4_moe_lite_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    t, runs = R.scope_seconds(ctx, R.DECODE, ("moe.experts",))
    touched, w = R.touched_per_run(ctx, "decode"), R.work(ctx)
    if t is None or touched is None or not w or not w["decode_tokens"]:
        return None
    m = ctx["model"]
    pairs = w["decode_tokens"] * m["num_experts_per_tok"] * F.moe_layers(m)
    peaks = peaks_of(ctx["device_kind"])
    bound = max(F.experts_bytes(m, touched * runs, pairs)
                / peaks["hbm_bytes_per_s"],
                F.experts_flops(m, pairs) / peaks["bf16_flops"])
    return 100.0 * bound / t
