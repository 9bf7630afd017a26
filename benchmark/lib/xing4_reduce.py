"""What the ``xing4_*`` cell's own per-layer readers share: ONE extra
reduction of the run's profiler trace with the expert, latent AND
hyper-connection scopes told apart, and the model work of the traced window
by this architecture's count.

``lib/glm4_moe_lite_reduce.NAMES`` (which the accepted ``moe_*`` / ``mla_*``
readers reduce with, in this cell too) knows no ``hc.*``: there an operation
under ``decode.steps/.../hc.write`` counts under the step loop's name alone.
The readers of ``benchmark/layer_metrics/hc_*.py`` and ``xing4_serve_mfu.py``
reduce the same xplane once more with the widened list (cached per run).  A
program that carries no such scope gives tables without them, and every
reader built on them returns ``None``.
"""
import os

from benchmark.lib import glm4_moe_lite_reduce as G
from benchmark.lib import span_reduce
from benchmark.lib import xing4_flops as F

# the benchmark's own copy of ``observability.trace.RESIDUAL_SCOPES``
HC_NAMES = ("hc.coeff", "hc.read", "hc.write")
NAMES = G.NAMES + HC_NAMES
DECODE, PREFILL, STEP_LOOP = G.DECODE, G.PREFILL, G.STEP_LOOP

_cache = {}


def for_run(ctx):
    """The widened reduction of the run in progress, or ``None``."""
    path = span_reduce.newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if _cache.get("key") != key:
        _cache.clear()
        _cache.update(key=key, reduced=span_reduce.reduce(
            span_reduce.load_scoped(path), NAMES, ctx.get("chips", 1)))
    return _cache["reduced"]


def scope_seconds(ctx, pattern, scopes):
    """(device self seconds under ``scopes`` in the modules matching
    ``pattern`` over the traced window, their runs) or ``(None, 0)``."""
    table, runs = span_reduce.module_table(for_run(ctx), pattern)
    if table is None:
        return None, 0.0
    t = sum(table.get(s, 0.0) for s in scopes)
    return (t, runs) if t else (None, 0.0)


def ms_per_run(ctx, pattern, scopes):
    t, runs = scope_seconds(ctx, pattern, scopes)
    return None if t is None else 1e3 * t / runs


def coverage_pct(ctx):
    """Share of the window's device self time under a name of ``NAMES``
    other than ``STEP_LOOP`` alone, and ``None`` unless a hyper-connection
    scope is among them."""
    red = for_run(ctx)
    if red is None:
        return None
    total = sum(v for t in red["self_s"].values() for v in t.values())
    named = sum(v for t in red["self_s"].values() for k, v in t.items()
                if k not in (span_reduce.UNSCOPED, STEP_LOOP))
    hc = sum(v for t in red["self_s"].values() for k, v in t.items()
             if k in HC_NAMES)
    return 100.0 * named / total if total and hc else None


def work(ctx):
    """``lib/glm4_moe_lite_reduce.work``'s attribution of the traced
    window's tokens (decode tokens, their context rows, prefill tokens)
    with the flops by THIS architecture's count: GLM's at these sizes plus
    the hyper-connections' coefficient products, a token."""
    w = G.work(ctx)
    if not w:
        return w
    hc = F.hc_flops_per_token(ctx["model"])
    return dict(w, decode_flops=w["decode_flops"] + hc * w["decode_tokens"],
                prefill_flops=w["prefill_flops"] + hc * w["prefill_tokens"])


def hc_roofline_pct(ctx, pattern, rows):
    """The hyper-connections of one program against the chip's HBM: what
    ``rows`` live rows in the traced window's runs have to move
    (``xing4_flops.hc_bytes``) over the device self time under ``hc.*``."""
    from benchmark.lib.peaks import peaks_of

    t, runs = scope_seconds(ctx, pattern, HC_NAMES)
    if t is None or not rows:
        return None
    return 100.0 * (F.hc_bytes(ctx["model"], rows, runs)
                    / peaks_of(ctx["device_kind"])["hbm_bytes_per_s"]) / t
