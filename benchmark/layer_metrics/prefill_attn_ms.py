"""Model step: device self time of the prefill-chunk program's attention
(``attn.core`` with ``attn.core.chunks``, and ``attn.kv_write``) per run
of the program."""
from benchmark.lib import span_reduce

MODULE = "serving_prefill_chunk"
SCOPES = ("attn.core", "attn.core.chunks", "attn.kv_write")


def read(ctx):
    return span_reduce.ms_per_run(ctx, MODULE, SCOPES)
