"""Model step: device self time under the state-space scopes (``ssm.*``,
the chunked scan among them) per run of the prefill-chunk program.  A chunk
rides in the scheduler step of a decode dispatch, so its time is part of
every live slot's inter-token interval: it moves ``tpot_p95_ms``."""
from benchmark.lib import falcon_h1_reduce as R


def read(ctx):
    return R.ms_per_run(ctx, R.PREFILL, R.SSM_NAMES)
