"""Model step: device self time of the decode program's weight matmuls
(``attn.qkv``, ``attn.out``, ``mlp``, ``lm_head``) per run of the program.
Floor: the weights read once at the HBM peak (7.25 GB / 819 GB/s = 8.9 ms
for ``mistral7b_serve``)."""
from benchmark.lib import span_reduce

MODULE = "serving_decode_steps"
SCOPES = ("attn.qkv", "attn.out", "mlp", "lm_head")


def read(ctx):
    return span_reduce.ms_per_run(ctx, MODULE, SCOPES)
