"""Scheduler: how uneven the routing was — the most loaded expert's live
(token, expert) pairs over the mean over all experts, from
``serving_moe_expert_tokens_total`` over the window (all expert layers
together: a ratio).  1 = even; the grouped products' cost follows the
pairs, so only the touched experts' weight reads, not this ratio, move the
step."""
from benchmark.lib import glm4_moe_lite_reduce as R


def read(ctx):
    moe = R.counters(ctx)
    n = ctx["model"].get("n_routed_experts")
    if not moe or not n or not sum(moe["pairs"].values()):
        return None
    return max(moe["pairs"].values()) * n / sum(moe["pairs"].values())
