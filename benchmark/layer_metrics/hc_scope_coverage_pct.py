"""Device: share of the traced window's device self time under ANY name of
the vocabulary widened by the expert, latent and hyper-connection scopes
(the step loop's name alone does not count) — the guard on the ``hc_*``
readers (what it does not cover, they cannot see)."""
from benchmark.lib import xing4_reduce as R


def read(ctx):
    return R.coverage_pct(ctx)
