"""Driver of the ``serve`` kind: one ``ServingEngine`` on one chip under
open-loop traffic.

The system under test is entered only through ``ServingEngine(model,
batch_size=, max_len=)``, ``submit`` and ``step`` — every other option stays
at the engine's default.  Each request is timed from when it was DUE;
requests due in the window are followed to completion under a bounded
drain, and one not ``done`` by then counts as failed.  ``correct`` compares
what the window served with the plain reference (the configuration's
``models/<model>.py``)
after the window has closed and the engine's state is freed.
"""
import gc
import time

import numpy as np

from benchmark.lib import harness, serve_work

DRAIN_S = 60.0


def build_engine(arch, config, seed):
    """(model, engine): the engine with ``batch_size`` and ``max_len`` and
    nothing else."""
    from paddle_tpu.serving import ServingEngine

    model = arch.build(config, seed, config["engine"]["max_len"])
    model.eval()
    harness.say("setup", peak_gb_model=round(
        harness.memory_peak_bytes(1) / 1e9, 2))
    engine = ServingEngine(model, **config["engine"])
    return model, engine


def warm_up(engine, gen, traffic, vocab_size):
    """Drive every program and helper the window will use: the longest and
    the shortest prompt of the mix, admitted while others decode."""
    from paddle_tpu.serving import Request

    (pmin, pmax), _ = gen.extremes(traffic)
    rng = np.random.default_rng(0)
    waves = [(pmax, 6), (pmin, 4)], [((pmin + pmax) // 2, 5), (pmax, 3)]
    reqs = []
    for wave in waves:
        for p, o in wave:
            reqs.append(engine.submit(Request(
                rng.integers(1, vocab_size, p).astype(np.int32), o)))
        for _ in range(3):
            engine.step()
    while engine.has_work:
        engine.step()
    bad = [r.status for r in reqs if r.status != "done"]
    if bad:
        raise SystemExit(f"warm-up requests ended as {bad}")


def _counter(name):
    from paddle_tpu.observability.metrics import get_registry

    fam = get_registry().get(name)
    return fam.labels(policy="continuous") if fam is not None else None


def measure(engine, sched, seconds, trace_dir=None, trace_s=0.0,
            drain_s=DRAIN_S):
    """Offer ``sched`` to the engine for ``seconds`` and follow every
    request to its end.  Single-threaded: arrivals that are due are
    submitted between scheduler steps.  Returns the window's record."""
    import jax

    from paddle_tpu.serving import Request

    emitted, steps = (_counter("serving_tokens_emitted_total"),
                      _counter("serving_steps_total"))
    chunks = _counter("serving_prefill_chunks_total")
    c0 = [c.value for c in (emitted, steps, chunks)]
    reqs, dues, lates, refused = [], [], [], 0
    # ``marking``: inside the traced sub-window (the last ``trace_s`` of the
    # window), where the loop's calls carry the benchmark's annotations; the
    # reduction takes its window from them.  The profiler itself is stopped
    # only after the drain, so that stopping it stalls no request.
    profiling = marking = False
    traced = None
    trace_at = seconds - trace_s if trace_dir and trace_s > 0 else None
    closed = None
    i, n = 0, len(sched)

    def marked(name, fn, *args):
        if not marking:
            return fn(*args)
        with jax.profiler.TraceAnnotation(name):
            return fn(*args)

    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if trace_at is not None and not profiling and now >= trace_at:
            harness.start_trace(trace_dir)
            profiling = marking = True
            t_mark0 = time.perf_counter()
        if closed is None and now >= seconds:
            closed = {"window_s": now,
                      "counters": [c.value - b for c, b in zip(
                          (emitted, steps, chunks), c0)]}
            if marking:
                marking, traced = False, (t_mark0, time.perf_counter())
        while i < n and sched[i][0] <= now:
            due, ids, n_out = sched[i]
            i += 1
            r = Request(ids, n_out)
            try:
                marked("bench.submit", engine.submit, r)
            except (RuntimeError, ValueError) as e:   # shed or refused
                harness.say("serve", refused=type(e).__name__, why=e)
                refused += 1
                continue
            reqs.append(r)
            dues.append(t0 + due)
            lates.append(r.t_submit - (t0 + due))
        if engine.has_work:
            marked("bench.step", engine.step)
        elif i < n or closed is None:
            marked("bench.wait_arrival", time.sleep, 2e-4)
        else:
            break
        if now > seconds + drain_s:
            break
    drained_s = time.perf_counter() - t0 - closed["window_s"]
    if profiling:
        jax.profiler.stop_trace()       # some 40 s for a 4 s device trace
    closed.update(t0=t0, requests=reqs, dues=dues, lates=lates,
                  refused=refused, traced=traced, drain_s=drained_s)
    return closed


def request_stats(rec):
    """Per-request host-clock numbers of the window, in seconds."""
    ttft, tpot, queue, failed = [], [], [], rec["refused"]
    for r, due in zip(rec["requests"], rec["dues"]):
        if r.status != "done" or len(r.output_ids) != r.max_new_tokens:
            failed += 1
            continue
        ttft.append(r.t_first - due)
        if len(r.output_ids) > 1:
            tpot.append((r.t_done - r.t_first) / (len(r.output_ids) - 1))
        marks = r.timeline()
        first_pf = next((x["t"] for x in marks if x["phase"] == "prefilling"),
                        None)
        if first_pf is not None:
            queue.append(first_pf - due)
    return {"ttft": ttft, "tpot": tpot, "queue": queue, "failed": failed}


def sample_finished(rec, seed, k):
    """``k`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in rec["requests"] if r.status == "done"
            and len(r.output_ids) == r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt_ids) + len(r.output_ids))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[int(j)] for j in picks]


def padded_shape(config, gen, traffic):
    """(L, K) of the reference's one compiled shape for a mix: the longest
    prompt plus output the mix can draw, rounded up to a multiple of 256
    (never past the engine's ``max_len``), and the longest output."""
    (_, pmax), (_, omax) = gen.extremes(traffic)
    return min(config["engine"]["max_len"], -(-(pmax + omax) // 256) * 256), omax


def reference_gaps(arch, config, shape, seed, served, quants=(None,)):
    """The reference's verdict on served requests.  ``served``:
    ``[(prompt_ids, output_ids), ...]``.  Runs the plain reference ONCE over
    each prompt with its served tokens and returns, per entry of ``quants``,
    the gaps at the served positions: for the reference (None) the gap by which each served
    token's logit lies below the reference's best; for a control precision
    the gap of the token THAT precision puts first.  Shapes are fixed by the
    configuration and the mix (one compiled reference per cell)."""
    (L, omax), R = shape, len(served)
    tokens = np.zeros((R, L), np.int32)
    rows = np.zeros((R, omax), np.int32)
    valid = np.zeros((R, omax), bool)
    picked = np.zeros((R, omax), np.int64)
    for j, (prompt, outs) in enumerate(served):
        p, n = len(prompt), len(outs)
        tokens[j, :p] = prompt
        tokens[j, p:p + n - 1] = outs[:-1]
        rows[j, :n] = p - 1 + np.arange(n)
        valid[j, :n] = True
        picked[j, :n] = outs
    controls = tuple(q for q in quants if q is not None)
    logits = dict(zip((None,) + controls, arch.serve_logits(
        config, seed, tokens, rows, quants=(None,) + controls)))
    ref = logits[None]
    best = ref.max(-1)
    out = []
    for q in quants:
        tok = picked if q is None else logits[q].argmax(-1)
        gap = best - np.take_along_axis(ref, tok[..., None], -1)[..., 0]
        out.append(gap[valid])
    return out


def check(compared, arch, config, shape, seed, rec, stats,
          compiles_in_window):
    """Fill ``compared`` from the window's record (engine already freed)."""
    limits = config["check"]
    compared.add("requests_failed", stats["failed"], 0)
    compared.add("compiles_in_window", compiles_in_window, 0)
    sample = sample_finished(rec, seed, limits["sample_requests"])
    if not sample:
        compared.add("served_tokens_compared", 0, 1, worse="below")
        return
    t0 = time.perf_counter()
    gaps, = reference_gaps(
        arch, config, shape, seed,
        [(r.prompt_ids, np.asarray(r.output_ids)) for r in sample])
    harness.say("reference", requests=len(sample), tokens=gaps.size,
                seconds=round(time.perf_counter() - t0, 2))
    compared.add("served_tokens_compared", gaps.size,
                 limits["min_tokens_compared"], worse="below")
    compared.add("logit_gap_max", float(gaps.max()), limits["logit_gap_max"])


def run(files, cell, config, traffic, seed, seconds, trace, events, t_start,
        before_window=None):
    import jax

    gen = files.named("generators", traffic["generator"])
    arch = files.named("models", config["model"])
    shape = padded_shape(config, gen, traffic)
    split = {}
    t = time.perf_counter()
    model, engine = build_engine(arch, config, seed)
    split["build_s"] = time.perf_counter() - t
    split["peak_gb_built"] = harness.memory_peak_bytes(cell["chips"]) / 1e9
    t = time.perf_counter()
    warm_up(engine, gen, traffic, config["vocab_size"])
    split["warm_up_s"] = time.perf_counter() - t
    split["peak_gb_warm"] = harness.memory_peak_bytes(cell["chips"]) / 1e9
    sched = gen.schedule(traffic, seed, seconds, config["vocab_size"])
    if before_window is not None:       # the fault tests only
        before_window(engine)
    trace_dir = harness.fresh_trace_dir(cell) if trace else None
    harness.say("setup", **{k: round(v, 2) for k, v in split.items()},
                **events.snapshot())
    compiles0 = events.compiles
    setup_s = time.perf_counter() - t_start
    rec = measure(engine, sched, seconds, trace_dir,
                  trace_s=min(float(traffic.get("trace_seconds", 4.0)),
                              seconds / 2))
    compiles_in_window = events.compiles - compiles0
    stats = request_stats(rec)
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
          compiles_in_window)
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
        sizes = arch.sizes(config)
        harness.read_layers(files, cell, trace_dir, {
            "kind": "serve", "config": config, "model": sizes,
            "traffic": traffic, "record": rec, "stats": stats,
            "work": serve_work.in_interval(rec["requests"], sizes,
                                           *rec["traced"])}, out)
    return out
