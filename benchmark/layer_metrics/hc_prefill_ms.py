"""Model step: device self time under the hyper-connection scopes (``hc.*``)
per run of the prefill-chunk program (a chunk rides in the scheduler step of
a decode dispatch, so its time is in every live slot's inter-token
interval)."""
from benchmark.lib import xing4_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.PREFILL, R.HC_NAMES)
