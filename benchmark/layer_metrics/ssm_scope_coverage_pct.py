"""Device: share of the traced window's device self time under ANY name of
the vocabulary widened by the state-space scopes — the guard on the
``ssm_*`` readers (what it does not cover, they cannot see)."""
from benchmark.lib import falcon_h1_reduce as R


def read(ctx):
    return R.coverage_pct(ctx)
