#!/usr/bin/env python3
"""Run a cell as the driver does — one new process a run, the real command
— several seeds and sets in one chip call, and keep every result line.

    python3 benchmark/tools/run_sets.py --workload W --seeds 1,2,3,4,5,6 \
        --sets 2 --trace-seeds 7,8,9 --seconds 30

Set k runs every seed once with ``--trace 0``; the traced runs follow.
This parent never imports jax (one process owns the chip).  Lines go to
``chiprun_out/sets_<workload>.jsonl`` with the run's wall seconds; the
spreads are printed at the end as the contract measures them (distance
between the quartiles of ``statistics.quantiles(values, n=4)`` over the
median)."""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=log,
                       text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    try:
        row = json.loads(lines[-1]) if lines else {}
    except ValueError:
        row = {"unparsed": lines[-1][:200]}
    row.update(rc=p.returncode, wall_s=round(wall, 1), seed=seed, trace=trace)
    return row


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"sets_{args.workload}.jsonl")
    seeds = [int(x) for x in args.seeds.split(",") if x]
    sets = []
    with open(os.path.join(out_dir, f"sets_{args.workload}.err"), "a") as log, \
            open(path, "a") as out:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = one(args.workload, seed, args.seconds, 0, log)
                row["set"] = k
                rows.append(row)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print(json.dumps({kk: row.get(kk) for kk in (
                    "set", "seed", "rc", "correct", "wall_s")} | {
                    n: m["value"] for n, m in row.get("metrics", {}).items()}),
                    flush=True)
            sets.append(rows)
        for seed in [int(x) for x in args.trace_seeds.split(",") if x]:
            row = one(args.workload, seed, args.seconds, 1, log)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps({kk: row.get(kk) for kk in (
                "seed", "rc", "correct", "wall_s")} | {
                n: m["value"] for n, m in row.get("metrics", {}).items()} | {
                "busy_s": row.get("device", {}).get("busy_s"),
                "window_s": row.get("device", {}).get("window_s")}),
                flush=True)
    for k, rows in enumerate(sets):
        good = [r for r in rows if r.get("metrics")]
        if len(good) < 2:
            continue
        for name in good[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in good]
            use = vals[1:] if name == "setup_s" and k == 0 else vals
            print(json.dumps({"set": k, "metric": name, "n": len(use),
                              "median": statistics.median(use),
                              "spread": spread(use) if len(use) > 1 else None}),
                  flush=True)


if __name__ == "__main__":
    main()
