"""Kernels (the decode program's fusions; no Pallas kernel runs at the
engine's defaults): the least time the chip's HBM needs for the decode
steps of the traced window — every matmul weight once a step, the live
slots' K/V read, one new row per live slot written — over the decode
program's device time.  Bandwidth-bound: at these batch sizes the flop
bound is far below the byte bound."""
from benchmark.lib import flops
from benchmark.lib.peaks import peaks_of
from benchmark.lib.trace_reduce import module_runs, module_seconds

MODULE = "serving_decode_steps"


def read(ctx):
    t = module_seconds(ctx["trace"], MODULE)
    w, m = ctx["work"], ctx["model"]
    if not t or not w["decode_tokens"]:
        return None
    peaks = peaks_of(ctx["device_kind"])
    weights = flops.matmul_params(m) * 2
    kv = flops.kv_bytes_per_token(m)
    nbytes = (module_runs(ctx["trace"], MODULE) * weights
              + kv * w["decode_context_rows"] + kv * w["decode_tokens"])
    bound = max(nbytes / peaks["hbm_bytes_per_s"],
                w["decode_flops"] / peaks["bf16_flops"])
    return 100.0 * bound / t
