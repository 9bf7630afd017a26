"""Scheduler: chunks of prompt spent per run of the prefill program —
``serving_prefill_chunks_total / serving_prefill_runs_total`` from the
program's registry over the whole process (the warm-up's four requests
included): how many 256-row chunks one read of the weights served.  1 = a
run a chunk; a program without the runs counter, or no run, gives nothing.
"""


def _value(name):
    from paddle_tpu.observability.metrics import get_registry

    fam = get_registry().get(name)
    return fam.labels(policy="continuous").value if fam is not None else None


def read(ctx):
    chunks, runs = (_value("serving_prefill_chunks_total"),
                    _value("serving_prefill_runs_total"))
    return chunks / runs if chunks is not None and runs else None
