#!/usr/bin/env python3
"""By-hand probes of a serving cell on the chip, several in one process so
the set-up is paid once.  Not part of a benchmark run.

    python3 benchmark/tools/serve_probe.py --workload W --sweep 6,8,10,12 --seconds 20
        the rate sweep that finds the knee (one engine, one window a rate)
    python3 benchmark/tools/serve_probe.py --workload W --readings 1,2,3 --seconds 10
        per seed: a short window at the cell's own load, then the reference
        AND the control (fp8) over the same served tokens: the two readings
        a limit is set from
    ... --trace-dump 1   adds one traced window and writes the trace's
        structure (planes, lines, heaviest events) under chiprun_out/
"""
import argparse
import gc
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.lib import harness, trace_reduce  # noqa: E402

OUT = os.path.join(harness.ROOT, "chiprun_out")


def dump_trace(trace_dir, tag):
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(trace_dir)
    data = ProfileData.from_file(path)
    summary = []
    for plane in data.planes:
        for line in plane.lines:
            tot, n = {}, 0
            for e in line.events:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
                n += 1
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:25]
            summary.append({"plane": plane.name, "line": line.name,
                            "events": n, "top": top})
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"trace_summary_{tag}.json"), "w") as f:
        json.dump({"file": path, "bytes": os.path.getsize(path),
                   "lines": summary}, f, indent=1)
    plain = trace_reduce.load_plain(path)
    # a small recorded trace for the tests: the first 0.25 s of it
    lo = min(ev[1] for p in plain["planes"] for ln in p["lines"]
             for ev in ln["events"])
    for p in plain["planes"]:
        for ln in p["lines"]:
            ln["events"] = [ev for ev in ln["events"] if ev[1] < lo + 25e7]
    with gzip.open(os.path.join(OUT, f"trace_plain_{tag}.json.gz"), "wt") as f:
        json.dump(plain, f)


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "serve_probe.jsonl"), "a") as f:
        f.write(line + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--readings", default="")
    ap.add_argument("--trace-dump", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=0,
                    help="windows of --seconds at the cell's own load on one "
                         "engine: how far do two runs of one pattern differ?")
    args = ap.parse_args()
    files = harness.Files()
    cell, config, traffic = files.cell(args.workload)
    device, events = harness.start(cell["chips"])
    serve = files.named("drivers", "serve")
    arch = files.named("models", config["model"])
    gen = files.named("generators", traffic["generator"])
    shape = serve.padded_shape(config, gen, traffic)
    vocab = config["vocab_size"]

    if args.repeat:
        model, engine = serve.build_engine(arch, config, 12)
        serve.warm_up(engine, gen, traffic, vocab)
        for k in range(args.repeat):
            sched = gen.schedule(traffic, 500 + k, args.seconds, vocab)
            rec = serve.measure(engine, sched, args.seconds)
            row = {"workload": args.workload, "window": k,
                   "seconds": args.seconds, "requests": len(sched),
                   "tokens_per_s": rec["counters"][0] / rec["window_s"]}
            for cut in sorted({30.0, 40.0, args.seconds}):
                if cut > args.seconds:
                    continue
                sub = dict(rec, requests=[], dues=[])
                for r, due in zip(rec["requests"], rec["dues"]):
                    if due - rec["t0"] < cut:
                        sub["requests"].append(r)
                        sub["dues"].append(due)
                st = serve.request_stats(sub)
                row[f"ttft_p95@{cut:g}"] = 1e3 * harness.percentile(st["ttft"], 95)
                row[f"ttft_p90@{cut:g}"] = 1e3 * harness.percentile(st["ttft"], 90)
                row[f"tpot_p95@{cut:g}"] = 1e3 * harness.percentile(st["tpot"], 95)
                row[f"n@{cut:g}"] = len(st["ttft"])
            emit(row)
        engine.close()
        del engine, model
        gc.collect()

    if args.sweep or args.trace_dump:
        t = time.perf_counter()
        model, engine = serve.build_engine(arch, config, 11)
        serve.warm_up(engine, gen, traffic, vocab)
        harness.say("probe", setup_s=round(time.perf_counter() - t, 1),
                    **events.snapshot())
        for rate in [float(x) for x in args.sweep.split(",") if x]:
            mix = dict(traffic, rate_per_s=rate)
            sched = gen.schedule(mix, 1000 + int(rate * 10), args.seconds,
                                 vocab)
            c0 = events.compiles
            rec = serve.measure(engine, sched, args.seconds, drain_s=40.0)
            st = serve.request_stats(rec)
            pc = lambda xs, q: round(1e3 * (harness.percentile(xs, q) or 0), 1)
            emit({"workload": args.workload,
                "rate": rate, "offered": len(sched), "failed": st["failed"],
                "tokens_per_s": round(rec["counters"][0] / rec["window_s"], 1),
                "steps_per_s": round(rec["counters"][1] / rec["window_s"], 1),
                "ttft_p50": pc(st["ttft"], 50), "ttft_p95": pc(st["ttft"], 95),
                "tpot_p50": pc(st["tpot"], 50), "tpot_p95": pc(st["tpot"], 95),
                "queue_p95": pc(st["queue"], 95),
                "late_p95": pc(rec["lates"], 95),
                "drain_s": round(rec["drain_s"], 2),
                "compiles": events.compiles - c0})
        if args.trace_dump:
            tdir = os.path.join(harness.ROOT, ".bench_trace", "probe")
            sched = gen.schedule(traffic, 77, 12.0, vocab)
            rec = serve.measure(engine, sched, 12.0, tdir, trace_s=3.0)
            dump_trace(tdir, args.workload)
            red = trace_reduce.reduce(trace_reduce.load_plain(
                trace_reduce.find_xplane(tdir)))
            emit({"workload": args.workload, "busy_s": red["busy_s"],
                  "window_s": red["window_s"],
                  "modules": {k: [len(v), sum(v)] for k, v
                              in red["modules"].items()},
                  "device_ops": red["device_ops"],
                  "idle_gaps": red["idle_gaps"]})
        emit({"workload": args.workload,
              "memory_peak_bytes": harness.memory_peak_bytes(1)})
        engine.close()
        del engine, model
        gc.collect()

    for seed in [int(x) for x in args.readings.split(",") if x]:
        t = time.perf_counter()
        model, engine = serve.build_engine(arch, config, seed)
        serve.warm_up(engine, gen, traffic, vocab)
        sched = gen.schedule(traffic, seed, args.seconds, vocab)
        rec = serve.measure(engine, sched, args.seconds)
        st = serve.request_stats(rec)
        engine.close()
        del engine, model
        gc.collect()
        sample = serve.sample_finished(rec, seed,
                                       config["check"]["sample_requests"])
        t1 = time.perf_counter()
        prog, ctrl = serve.reference_gaps(
            arch, config, shape, seed,
            [(r.prompt_ids, np.asarray(r.output_ids)) for r in sample],
            quants=(None, "fp8"))
        emit({
            "workload": args.workload,
            "seed": seed, "requests": len(sched), "failed": st["failed"],
            "tokens_compared": int(prog.size),
            "program_gap_max": float(prog.max()),
            "program_gap_p99": float(np.percentile(prog, 99)),
            "program_mismatch_share": float((prog > 0).mean()),
            "control_gap_max": float(ctrl.max()),
            "control_gap_p99": float(np.percentile(ctrl, 99)),
            "control_mismatch_share": float((ctrl > 0).mean()),
            "reference_s": round(time.perf_counter() - t1, 1),
            "total_s": round(time.perf_counter() - t, 1)})


if __name__ == "__main__":
    main()
