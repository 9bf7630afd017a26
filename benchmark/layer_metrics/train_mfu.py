"""Train step, whole: model flops (forward + backward, ``6 N`` over the
matmul parameters plus causal attention; recompute not counted) of the
steps that ran in the traced window, over the window at the chip's bf16
peak.  It bounds the kernels' rooflines."""
from benchmark.lib import flops
from benchmark.lib.peaks import peaks_of
from benchmark.lib.trace_reduce import module_runs

MODULE = "_step_fn"


def read(ctx):
    steps = module_runs(ctx["trace"], MODULE)
    window = ctx["trace"]["window_s"]
    if not steps or not window:
        return None
    tr = ctx["config"]["train"]
    work = (steps * tr["batch"] * tr["seq"]
            * flops.train_flops_per_token(ctx["model"], tr["seq"]))
    return 100.0 * work / (window * ctx["chips"]
                           * peaks_of(ctx["device_kind"])["bf16_flops"])
