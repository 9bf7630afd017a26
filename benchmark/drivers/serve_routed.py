"""Driver of the ``serve_routed`` kind: the ``serve`` driver for a model
with routed experts whose engine records, beside each token, the experts
that served it (``Request.routes``).

Everything that measures is ``drivers/serve.py``'s, unchanged and imported
(``build_engine``, ``warm_up``, ``measure``, ``request_stats``,
``sample_finished``, ``padded_shape``); ``run`` is that file's ``run`` with
this file's ``check`` in its place (a copy, because that file may not be
edited) and the expert counters snapshotted around the window.

Why ``correct`` is decided differently here.  The program routes from bf16
hidden states, the float32 reference from its own: a few (position, layer)
pairs in a hundred choose another last expert, which moves logits by as much
as a lower precision does, so the widest logit gap cannot tell a sound run
from the fp8 control.  The reference therefore FOLLOWS a recorded choice
wherever that choice is legitimate by its own scores (every recorded
expert's biased score within ``check.route_margin`` of its own last-ranked
one) and refuses it where it is not.  Compared: ``routes_refused`` (limit 0:
a choice outside the margin is a wrong route, not a rounding),
``routes_followed_share`` (the share of recorded (position, layer) pairs
where the reference departed from its own choice: far above what rounding
explains, the router's arithmetic is off although each choice lies inside
the margin) and ``logit_gap_max`` as in ``serve``.

The control (the reference in the precision below the configuration's) is
judged in the program's place, by this same ``check`` (``control=``): its
own chosen sets are followed like recorded ones, its first-ranked tokens
stand for the served ones — ``check.readings`` in the configuration file
holds what it reads against each of the three limits.
"""
import gc
import time

import numpy as np

from benchmark.drivers import serve
from benchmark.lib import harness

MOE_COUNTERS = ("serving_moe_expert_tokens_total",
                "serving_moe_experts_touched_total",
                "serving_moe_dispatches_total")


def padded(shape, served):
    """The sampled requests as the reference's operands: ``(tokens [R, L],
    rows [R, omax], valid [R, omax], picked [R, omax], routes [R, L, L_moe,
    k])``.  ``served``: ``[(prompt_ids, output_ids, routes [p + n - 1,
    L_moe, k]), ...]``."""
    (L, omax), R = shape, len(served)
    tokens = np.zeros((R, L), np.int32)
    rows = np.zeros((R, omax), np.int32)
    valid = np.zeros((R, omax), bool)
    picked = np.zeros((R, omax), np.int64)
    routes = np.full((R, L) + served[0][2].shape[1:], -1, np.int32)
    for j, (prompt, outs, rt) in enumerate(served):
        p, n = len(prompt), len(outs)
        if len(rt) != p + n - 1:
            raise ValueError(f"request {j}: {len(rt)} recorded rows for "
                             f"{p} prompt + {n} served tokens")
        tokens[j, :p] = prompt
        tokens[j, p:p + n - 1] = outs[:-1]
        rows[j, :n] = p - 1 + np.arange(n)
        valid[j, :n] = True
        picked[j, :n] = outs
        routes[j, :p + n - 1] = rt
    return tokens, rows, valid, picked, routes


def gap_of(ref, tok):
    """How far the reference's logit of ``tok`` lies under its best."""
    return ref.max(-1) - np.take_along_axis(ref, tok[..., None], -1)[..., 0]


def routed_gaps(arch, config, shape, seed, served, quants=(None,),
                follow=True):
    """``serve.reference_gaps`` for requests with recorded routes.  Returns
    (per entry of ``quants`` the gaps at the served positions, the
    reference's route statistics)."""
    tokens, rows, valid, picked, routes = padded(shape, served)
    controls = tuple(q for q in quants if q is not None)
    stats = {}
    logits = dict(zip((None,) + controls, arch.serve_logits(
        config, seed, tokens, rows, quants=(None,) + controls,
        routes=routes if follow else None, stats=stats)))
    ref = logits[None]
    return [gap_of(ref, picked if q is None else logits[q].argmax(-1))[valid]
            for q in quants], stats


def control_in_place(arch, config, shape, seed, served, quant):
    """The control in the PROGRAM's place: the same prompts and tokens
    computed in ``quant`` with its own choice of experts.  What such a
    program would have handed back — the token it puts first at each served
    position, its chosen sets at every position the program recorded — is
    judged as the program's is: the float32 reference follows those sets
    within ``check.route_margin``.  Returns ``(gaps, stats)`` as one entry
    of ``routed_gaps`` does."""
    tokens, rows, valid, _, routes = padded(shape, served)
    sets = []
    (ctrl,) = arch.serve_logits(config, seed, tokens, rows, quants=(quant,),
                                chosen=sets)
    stats = {}
    (ref,) = arch.serve_logits(
        config, seed, tokens, rows, stats=stats,
        routes=np.where(routes >= 0, sets[0], -1))
    return gap_of(ref, ctrl.argmax(-1))[valid], stats


def served_of(sample):
    return [(r.prompt_ids, np.asarray(r.output_ids),
             np.concatenate(r.routes, axis=0)) for r in sample]


def check(compared, arch, config, shape, seed, rec, stats,
          compiles_in_window, control=None):
    """Fill ``compared`` from the window's record (engine already freed);
    returns the reference's route statistics.  ``control`` (the control
    tests and ``tools/route_probe.py`` only): a precision that takes the
    program's place over the same sample."""
    limits = config["check"]
    compared.add("requests_failed", stats["failed"], 0)
    compared.add("compiles_in_window", compiles_in_window, 0)
    sample = serve.sample_finished(rec, seed, limits["sample_requests"])
    if not sample:
        compared.add("served_tokens_compared", 0, 1, worse="below")
        return {}
    t0 = time.perf_counter()
    if control is None:
        (gaps,), st = routed_gaps(arch, config, shape, seed,
                                  served_of(sample))
    else:
        gaps, st = control_in_place(arch, config, shape, seed,
                                    served_of(sample), control)
    harness.say("reference", requests=len(sample), tokens=gaps.size,
                seconds=round(time.perf_counter() - t0, 2),
                short_max=float(st["short"].max()) if len(
                    st.get("short", ())) else 0.0,
                **{k: v for k, v in st.items() if k != "short"})
    compared.add("served_tokens_compared", gaps.size,
                 limits["min_tokens_compared"], worse="below")
    compared.add("routes_recorded", st.get("recorded", 0),
                 limits["min_tokens_compared"], worse="below")
    compared.add("routes_refused", st.get("refused", 0), 0)
    compared.add("routes_followed_share",
                 st.get("followed", 0) / max(1, st.get("recorded", 0)),
                 limits["routes_followed_share_max"])
    compared.add("logit_gap_max", float(gaps.max()), limits["logit_gap_max"])
    return st


def moe_counters():
    """The process's expert counters as plain numbers: ``{"pairs":
    {expert: n}, "touched": {program: n}, "dispatches": {program: n}}``;
    ``None`` where the program has no such counters."""
    from paddle_tpu.observability.metrics import get_registry

    snap = get_registry().snapshot()
    if any(name not in snap for name in MOE_COUNTERS):
        return None
    by = lambda name, label: {s["labels"][label]: s["value"]
                              for s in snap[name]["series"]}
    return {"pairs": by(MOE_COUNTERS[0], "expert"),
            "touched": by(MOE_COUNTERS[1], "program"),
            "dispatches": by(MOE_COUNTERS[2], "program")}


def _delta(a, b):
    """What the counters gained between two readings."""
    if a is None or b is None:
        return None
    return {group: {k: v - a[group].get(k, 0.0)
                    for k, v in b[group].items()} for group in b}


def run(files, cell, config, traffic, seed, seconds, trace, events, t_start,
        before_window=None, control=None):
    gen = files.named("generators", traffic["generator"])
    arch = files.named("models", config["model"])
    shape = serve.padded_shape(config, gen, traffic)
    split = {}
    t = time.perf_counter()
    model, engine = serve.build_engine(arch, config, seed)
    split["build_s"] = time.perf_counter() - t
    split["peak_gb_built"] = harness.memory_peak_bytes(cell["chips"]) / 1e9
    t = time.perf_counter()
    serve.warm_up(engine, gen, traffic, config["vocab_size"])
    split["warm_up_s"] = time.perf_counter() - t
    split["peak_gb_warm"] = harness.memory_peak_bytes(cell["chips"]) / 1e9
    sched = gen.schedule(traffic, seed, seconds, config["vocab_size"])
    if before_window is not None:       # the fault tests only, as control
        before_window(engine)
    trace_dir = harness.fresh_trace_dir(cell) if trace else None
    harness.say("setup", **{k: round(v, 2) for k, v in split.items()},
                **events.snapshot())
    compiles0 = events.compiles
    moe0 = moe_counters()
    setup_s = time.perf_counter() - t_start
    rec = serve.measure(engine, sched, seconds, trace_dir,
                        trace_s=min(float(traffic.get("trace_seconds", 4.0)),
                                    seconds / 2))
    compiles_in_window = events.compiles - compiles0
    rec["moe"] = _delta(moe0, moe_counters())
    stats = serve.request_stats(rec)
    peak = harness.memory_peak_bytes(cell["chips"])
    tokens, steps, chunks = rec["counters"]
    harness.say("window", window_s=round(rec["window_s"], 3),
                drain_s=round(rec["drain_s"], 3), requests=len(sched),
                failed=stats["failed"], tokens=tokens, steps=steps,
                late_p95_ms=round(1e3 * (harness.percentile(
                    rec["lates"], 95) or 0), 3),
                compiles_in_window=compiles_in_window)
    # free the program's state before the reference runs
    engine.close()
    del engine, model
    gc.collect()

    compared = harness.Compared()
    check(compared, arch, config, shape, seed, rec, stats,
          compiles_in_window, control=control)
    ms = lambda xs, q: (None if not xs else 1e3 * harness.percentile(xs, q))
    out = {
        "compared": compared, "attempted": len(sched),
        "failed": stats["failed"], "memory_peak_bytes": peak,
        "values": {"ttft_p95_ms": ms(stats["ttft"], 95),
                   "tpot_p95_ms": ms(stats["tpot"], 95),
                   "serve_tokens_per_s": tokens / rec["window_s"],
                   "setup_s": setup_s},
        "layer_values": {},
    }
    if trace and rec["traced"] is not None:
        harness.read_layers(files, cell, trace_dir, {
            "kind": "serve", "config": config, "model": arch.sizes(config),
            "traffic": traffic, "record": rec, "stats": stats}, out)
    return out
