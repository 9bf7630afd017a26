"""Device: share of the traced window's device self time under ANY name of
the vocabulary widened by the expert and latent scopes — the guard on the
``moe_*`` / ``mla_*`` readers (what it does not cover, they cannot see)."""
from benchmark.lib import glm4_moe_lite_reduce as R


def read(ctx):
    return R.coverage_pct(ctx)
