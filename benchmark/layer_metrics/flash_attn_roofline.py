"""Kernels (``ops/flash_attention.py``): the least time the chip needs for
the causal flash-attention forward and backward of the steps in the traced
window — the larger of flops over the bf16 peak and bytes over the HBM
peak, the causal triangle counted as half of the square — over the summed
device time of the kernel's events."""
from benchmark.lib import flops
from benchmark.lib.peaks import peaks_of
from benchmark.lib.trace_reduce import module_runs, op_seconds

MODULE = "_step_fn"
KERNEL = "flash"


def read(ctx):
    steps = module_runs(ctx["trace"], MODULE)
    t = op_seconds(ctx["trace"], KERNEL)
    if not steps or not t:
        return None
    tr, peaks = ctx["config"]["train"], peaks_of(ctx["device_kind"])
    work, nbytes = flops.flash_attention_cost(ctx["model"], tr["batch"],
                                              tr["seq"])
    # the recomputed layers run the forward kernel a second time; the model
    # needs it once, so the share counts it once
    bound = max(work / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * steps * bound / t
