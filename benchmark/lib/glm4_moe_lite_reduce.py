"""What the ``glm47flash_*`` cell's per-layer readers share: ONE extra
reduction of the run's profiler trace with the expert and latent scopes told
apart, the model work of the traced window by this architecture's own count,
and the window's expert counters.

``span_reduce.NAMES`` is the accepted benchmark's closed list and knows no
``moe.*`` / ``mla.*``; it is not edited.  The readers reduce the same xplane
once more with the widened list (cached per run, as
``lib/falcon_h1_reduce.py`` does).  A program that carries no such scope or
counter gives tables without them, and every reader built on them returns
``None``.
"""
import os

from benchmark.lib import glm4_moe_lite_flops as F
from benchmark.lib import span_reduce

# the benchmark's own copy of ``observability.trace.EXPERT_SCOPES``
MOE_NAMES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine",
             "moe.shared")
MLA_NAMES = ("mla.absorb",)
NAMES = span_reduce.NAMES + MOE_NAMES + MLA_NAMES
DECODE, PREFILL = "serving_decode_steps", "serving_prefill_chunk"
# the scan that holds a whole decode step: an operation that carries this
# name and no finer one is as unseen by every reader as one with no name
STEP_LOOP = "decode.steps"

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
    other than ``STEP_LOOP`` alone, and ``None`` unless an expert scope is
    among them."""
    red = for_run(ctx)
    if red is None:
        return None
    total = sum(v for t in red["self_s"].values() for v in t.values())
    named = sum(v for t in red["self_s"].values() for k, v in t.items()
                if k not in (span_reduce.UNSCOPED, STEP_LOOP))
    moe = sum(v for t in red["self_s"].values() for k, v in t.items()
              if k in MOE_NAMES)
    return 100.0 * named / total if total and moe else None


def counters(ctx):
    """The WINDOW's expert counters (``drivers/serve_routed.py`` reads them
    before and after the window): ``pairs`` by expert, ``touched`` and
    ``dispatches`` by program; ``None`` without them."""
    moe = ctx["record"].get("moe")
    return moe if moe and sum(moe["dispatches"].values()) else None


def touched_per_run(ctx, program):
    """Experts touched (summed over the expert layers) a run of
    ``program`` (``decode`` / ``prefill``), the window's mean."""
    moe = counters(ctx)
    if not moe or not moe["dispatches"].get(program):
        return None
    return moe["touched"][program] / moe["dispatches"][program]


def work(ctx):
    """Model work inside the traced window by THIS architecture's count:
    decode tokens 2..n of a request evenly spaced between ``t_first`` and
    ``t_done``, a prompt attributed by the share of its chunks' marks
    inside."""
    if "glm_work" in ctx:
        return ctx["glm_work"]
    rec, m = ctx["record"], ctx["model"]
    if rec.get("traced") is None:
        return None
    a, b = rec["traced"]
    w = {"decode_tokens": 0, "decode_flops": 0.0, "decode_context_rows": 0,
         "prefill_tokens": 0.0, "prefill_flops": 0.0}
    for r in rec["requests"]:
        p, n = len(r.prompt_ids), len(r.output_ids)
        marks = [x["t"] for x in r.timeline() if x["phase"] == "prefilling"]
        inside = sum(a <= t < b for t in marks)
        if inside:
            share = inside / len(marks)
            w["prefill_tokens"] += share * p
            w["prefill_flops"] += share * F.prefill_flops(m, p,
                                                          with_head=True)
        if r.t_first is None or n < 2:
            continue
        t_end = r.t_done if r.t_done is not None else b
        gap = (t_end - r.t_first) / (n - 1)
        for j in range(1, n):
            if a <= r.t_first + j * gap < b:
                w["decode_tokens"] += 1
                w["decode_context_rows"] += p + j
                w["decode_flops"] += F.decode_token_flops(m, p + j)
    ctx["glm_work"] = w
    return w
