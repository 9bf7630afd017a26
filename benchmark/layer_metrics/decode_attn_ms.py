"""Model step: device self time of the decode program's attention — the
cache read (``attn.core`` with its chunk loop ``attn.core.chunks``) and the
K/V append (``attn.kv_write``) — per run of the program, from the names the
program gives its operations."""
from benchmark.lib import span_reduce

MODULE = "serving_decode_steps"
SCOPES = ("attn.core", "attn.core.chunks", "attn.kv_write")


def read(ctx):
    return span_reduce.ms_per_run(ctx, MODULE, SCOPES)
