#!/usr/bin/env python3
"""By-hand readings of a training cell on the chip, many seeds in one
process (the compiled step is reused; only the weights change).  Not part
of a benchmark run.

    python3 benchmark/tools/train_probe.py --workload W --seeds 1,2,3 \
        [--control 1] [--half-batch 1] [--steps 8]

Per seed: the program's first three steps, then the float32 reference, and
the numbers ``correct`` compares.  ``--control`` adds the reference in fp8
in the program's place; ``--half-batch`` the reference trained on half of
every batch (the fault "half of the batch left out"), both read by the same
numbers.  Lines go to standard output and to chiprun_out/train_probe.jsonl.
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

from benchmark.lib import harness, llama_ref, weights  # noqa: E402

OUT = os.path.join(harness.ROOT, "chiprun_out")


def gaps(seen, ref):
    out = {f"loss_gap_step{k}": abs(a - b) / abs(b) for k, (a, b) in
           enumerate(zip(seen["losses"], ref["losses"]), 1)}
    g, where = harness.worst_leaf_gap(seen["grad_norms"], ref["grad_norms"])
    out.update(grad_norm_gap=g, grad_norm_leaf=f"{where[0]}/{where[1]}")
    med = float(np.median(list(ref["grad_norms"].values())))
    skip = {k for k, v in ref["grad_norms"].items() if v < 1e-3 * med}
    g, where = harness.worst_leaf_gap(seen["change_norms"],
                                        ref["change_norms"], skip)
    out.update(param_change_gap=g, change_leaf=f"{where[0]}/{where[1]}",
               left_out=len(skip))
    return out


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "train_probe.jsonl"), "a") as f:
        f.write(line + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--half-batch", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0,
                    help="also run a window of this many seconds (first seed)")
    args = ap.parse_args()
    import paddle_tpu as paddle

    files = harness.Files()
    cell, config, traffic = files.cell(args.workload)
    device, events = harness.start(cell["chips"])
    train = files.named("drivers", "train")
    arch = files.named("models", config["model"])
    gen = files.named("generators", traffic["generator"])
    tr = config["train"]
    m = weights.model_sizes(config)
    opt = train.opt_tuple(config["optimizer"])
    for n, seed in enumerate(int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        model, step = train.build_step(arch, config, seed)
        host = gen.batches(traffic, seed, tr["batch"], tr["seq"],
                           config["vocab_size"])
        data = [paddle.to_tensor(b, dtype="int64") for b in host]
        t1 = time.perf_counter()
        seen = train.first_steps(arch, step, config, seed, data)
        row = {"seed": seed, "what": "program",
               "build_s": round(t1 - t0, 1),
               "first_steps_s": round(time.perf_counter() - t1, 1),
               "losses": seen["losses"], **events.snapshot()}
        if args.steps and n == 0:
            rec = train.measure(step, data, float(args.steps))
            row.update(window_steps=rec["steps"],
                       step_s=rec["window_s"] / rec["steps"],
                       memory_peak_bytes=harness.memory_peak_bytes(1))
        del step, model, data
        gc.collect()
        t2 = time.perf_counter()
        ref = llama_ref.train_reference(m, seed, config["torch_dtype"], host,
                                        opt, updates=train.UPDATES_FOLLOWED)
        row.update(reference_s=round(time.perf_counter() - t2, 1),
                   reference_losses=ref["losses"], **gaps(seen, ref))
        emit(row)
        if args.control and n < args.control:
            t3 = time.perf_counter()
            ctrl = llama_ref.train_reference(
                m, seed, config["torch_dtype"], host, opt,
                updates=train.UPDATES_FOLLOWED, quant="fp8")
            emit({"seed": seed, "what": "control_fp8",
                  "seconds": round(time.perf_counter() - t3, 1),
                  "losses": ctrl["losses"], **gaps(ctrl, ref)})
        if args.half_batch and n < args.half_batch:
            t3 = time.perf_counter()
            half = llama_ref.train_reference(
                m, seed, config["torch_dtype"], host, opt,
                updates=train.UPDATES_FOLLOWED,
                rows=range(tr["batch"] // 2))
            emit({"seed": seed, "what": "fault_half_batch",
                  "seconds": round(time.perf_counter() - t3, 1),
                  "losses": half["losses"], **gaps(half, ref)})
        del ref
        gc.collect()


if __name__ == "__main__":
    main()
