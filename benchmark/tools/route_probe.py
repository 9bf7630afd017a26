#!/usr/bin/env python3
"""By-hand probe of a ``serve_routed`` cell on the chip: the readings
``check.route_margin``, ``check.routes_followed_share_max`` and
``check.logit_gap_max`` are set from.  Not part of a benchmark run.

    python3 benchmark/tools/route_probe.py --workload W --seeds 1,2,3 --seconds 10

Per seed: a short window at the cell's own load with routes recorded, then
over the same sampled requests, each through the driver's own ``check``:
(a) the program — the reference FOLLOWING the recorded routes within
``--margin`` (default: the configuration's); (b) the fp8 control IN THE
PROGRAM'S PLACE — its own chosen sets followed within the same margin, its
first-ranked tokens for the served ones; it has to come out not correct
(``--who control`` reads (b) alone).
With ``--own 1`` also (c) the reference on its OWN routes: how often the
recorded set differs from it, by what margins, and ``logit_gap_max`` of the
program and of the control that way (the flip-rate finding).
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.lib import harness  # noqa: E402

OUT = os.path.join(harness.ROOT, "chiprun_out")


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "route_probe.jsonl"), "a") as f:
        f.write(line + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--margin", type=float, default=None)
    ap.add_argument("--own", type=int, default=0)
    ap.add_argument("--who", default="program,control")
    args = ap.parse_args()
    files = harness.Files()
    cell, config, traffic = files.cell(args.workload)
    if args.margin is not None:
        config["check"]["route_margin"] = args.margin
    harness.start(cell["chips"])
    serve = files.named("drivers", "serve")
    routed = files.named("drivers", "serve_routed")
    arch = files.named("models", config["model"])
    gen = files.named("generators", traffic["generator"])
    shape = serve.padded_shape(config, gen, traffic)
    vocab = config["vocab_size"]
    for seed in [int(x) for x in args.seeds.split(",") if x]:
        t = time.perf_counter()
        model, engine = serve.build_engine(arch, config, seed)
        serve.warm_up(engine, gen, traffic, vocab)
        moe0 = routed.moe_counters()
        sched = gen.schedule(traffic, seed, args.seconds, vocab)
        rec = serve.measure(engine, sched, args.seconds)
        st = serve.request_stats(rec)
        moe = routed._delta(moe0, routed.moe_counters())
        peak = harness.memory_peak_bytes(1)
        engine.close()
        del engine, model
        gc.collect()
        t1 = time.perf_counter()
        row = {"workload": args.workload, "seed": seed,
               "requests": len(sched), "failed": st["failed"],
               "route_margin": config["check"]["route_margin"]}
        for who in args.who.split(","):
            control = {"program": None, "control": "fp8"}[who]
            compared = harness.Compared()
            rs = routed.check(compared, arch, config, shape, seed, rec, st,
                              0, control=control)
            row[who] = {k: v["value"] for k, v in compared.rows.items()}
            short = rs.get("short", np.zeros(0))
            row[who].update({f"short_p{q}": float(np.percentile(short, q))
                             if len(short) else 0.0 for q in (50, 99, 100)})
            row[who]["correct"] = compared.correct
            row[who]["not_ok"] = [k for k, v in compared.rows.items()
                                  if not v["ok"]]
        sample = serve.sample_finished(rec, seed,
                                       config["check"]["sample_requests"])
        served = routed.served_of(sample)
        if args.own:
            (fol, fol_ctrl), rs = routed.routed_gaps(
                arch, config, shape, seed, served, quants=(None, "fp8"))
            (own, own_ctrl), _ = routed.routed_gaps(
                arch, config, shape, seed, served, quants=(None, "fp8"),
                follow=False)
            short = np.sort(rs.get("short", np.zeros(0)))
            q = lambda p: float(np.percentile(short, p)) if short.size \
                else 0.0
            row["own"] = {
                "routes_differ_share": rs.get("differ", 0)
                / max(1, rs.get("recorded", 0)),
                "short_p50": q(50), "short_p90": q(90), "short_p99": q(99),
                "short_max": q(100),
                "gap_max_followed": float(fol.max()),
                "gap_max_own": float(own.max()),
                "mismatch_share_own": float((own > 0).mean()),
                "control_gap_max_vs_followed": float(fol_ctrl.max()),
                "control_gap_max_vs_own": float(own_ctrl.max())}
        pairs = np.asarray(list(moe["pairs"].values())) if moe else None
        # per expert layer, over the sampled requests' recorded routes
        rt = np.concatenate([r for _, _, r in served], axis=0)
        by_layer = [float(c.max() / c.mean()) for c in (
            np.bincount(rt[:, li].ravel(),
                        minlength=config["n_routed_experts"])
            for li in range(rt.shape[1]))]
        emit(dict(row, **{
            "expert_load_max_over_mean": (
                float(pairs.max() * config["n_routed_experts"] / pairs.sum())
                if pairs is not None and pairs.sum() else None),
            "expert_load_max_over_mean_by_layer": by_layer,
            "experts_touched_per_decode_run": (
                moe["touched"]["decode"] / max(1, moe["dispatches"]["decode"])
                if moe else None),
            "experts_touched_per_chunk": (
                moe["touched"]["prefill"]
                / max(1, moe["dispatches"]["prefill"]) if moe else None),
            "tokens_per_s": rec["counters"][0] / rec["window_s"],
            "tpot_p95_ms": 1e3 * (harness.percentile(st["tpot"], 95) or 0),
            "memory_peak_bytes": peak,
            "reference_s": round(time.perf_counter() - t1, 1),
            "total_s": round(time.perf_counter() - t, 1)}))


if __name__ == "__main__":
    main()
