"""Kernels: the prefill-chunk program's grouped expert products against the
chip.  Numerator: max(operations / peak, bytes / bandwidth) of the three
grouped products over the REAL pairs of the chunks run in the traced window
(the touched experts' weights once a layer and run, the pairs'
activations); denominator: device self time under ``moe.experts`` in the
chunk program."""
from benchmark.lib import glm4_moe_lite_flops as F
from benchmark.lib import glm4_moe_lite_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    t, runs = R.scope_seconds(ctx, R.PREFILL, ("moe.experts",))
    touched, w = R.touched_per_run(ctx, "prefill"), R.work(ctx)
    if t is None or touched is None or not w or not w["prefill_tokens"]:
        return None
    m = ctx["model"]
    pairs = w["prefill_tokens"] * m["num_experts_per_tok"] * F.moe_layers(m)
    peaks = peaks_of(ctx["device_kind"])
    bound = max(F.experts_bytes(m, touched * runs, pairs)
                / peaks["hbm_bytes_per_s"],
                F.experts_flops(m, pairs) / peaks["bf16_flops"])
    return 100.0 * bound / t
