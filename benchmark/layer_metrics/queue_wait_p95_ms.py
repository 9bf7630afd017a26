"""Scheduler: due -> first ``prefilling`` mark of ``Request.timeline()``
(the wait for a slot and for the prefill budget), 95th percentile."""
from benchmark.lib.harness import percentile


def read(ctx):
    v = ctx["stats"]["queue"]
    return 1e3 * percentile(v, 95) if v else None
