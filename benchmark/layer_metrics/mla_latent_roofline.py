"""Kernels: the decode program's latent cache read against the chip's HBM.
Numerator: the latent rows some live slot attends to (one row = latent +
rope key, read ONCE: it is both key and value) plus the rows written, every
layer, from shapes; denominator: device self time under ``attn.core`` and
its chunk loop ``attn.core.chunks`` in the decode program."""
from benchmark.lib import glm4_moe_lite_flops as F
from benchmark.lib import glm4_moe_lite_reduce as R
from benchmark.lib.peaks import peaks_of


def read(ctx):
    t, _ = R.scope_seconds(ctx, R.DECODE, ("attn.core", "attn.core.chunks"))
    w = R.work(ctx)
    if t is None or not w or not w["decode_tokens"]:
        return None
    m = ctx["model"]
    rows = w["decode_context_rows"] + w["decode_tokens"]
    return 100.0 * (m["num_hidden_layers"] * F.latent_bytes(m, rows)
                    / peaks_of(ctx["device_kind"])["hbm_bytes_per_s"]) / t
