#!/usr/bin/env python3
"""One traced run of a cell, then the account of its set-up (on the chip).

    python3 benchmark/tools/setup_probe.py --workload W --seed N [--seconds 40] [--rows 10]

The run is ``run.py --trace 1`` itself (same process start, same result
line as the last line of standard output).  Afterwards, on standard error
and in ``chiprun_out/setup_record/<workload>.json``: ``setup_s`` of THIS
run beside the seconds ``lib/setup_reduce.py`` reads from the program's
start-up record (each second once, under the innermost entry open), what
is left of ``setup_s`` unnamed, the executables loaded against the
``backend_compiles`` the harness counted from outside, the costliest
``(program, stage)`` rows, and the whole log — what section 5 of
``PERF.md`` is written from.  A cold run: point ``JAX_COMPILATION_CACHE_DIR``
at an empty directory.  For a WARM account run it twice: the compile
cache's key holds an operation's innermost stack frames, and under this
file the helpers called near the top of the stack (the weight makers, the
rope tables: 34 of chat's 64 executables) have other frames than under
``run.py`` — the first run compiles them again, the serving programs hit.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run                          # noqa: E402
from benchmark.lib import harness, setup_reduce    # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rows", type=int, default=10)
    args = ap.parse_args()
    seen = {}
    read_layers, result_line = harness.read_layers, harness.result_line

    def keep_ctx(files, cell, trace_dir, ctx, out):
        seen["ctx"] = ctx
        return read_layers(files, cell, trace_dir, ctx, out)

    def keep_out(files, workload, trace, out, device):
        seen["setup_s"] = out["values"]["setup_s"]
        return result_line(files, workload, trace, out, device)

    harness.read_layers, harness.result_line = keep_ctx, keep_out
    run.main(["--workload", args.workload, "--seed", str(args.seed),
              "--seconds", str(args.seconds), "--trace", "1"])
    t = setup_reduce.table(seen["ctx"]) if "ctx" in seen else None
    if t is None:
        raise SystemExit("the program kept no start-up record")
    named = sum(t["seconds"].values())
    harness.say("setup_record", setup_s=round(seen["setup_s"], 3),
                named_s=round(named, 3),
                unnamed_s=round(seen["setup_s"] - named, 3),
                programs=t["programs"],
                cache_retrieval_s=round(t["retrieval_s"], 3),
                **{k + "_s": round(v, 3) for k, v in t["seconds"].items()})
    for name, stage, own, count in t["rows"][:args.rows]:
        harness.say("setup_record", row=name.replace(" ", "_"), stage=stage,
                    self_s=round(own, 3), count=count)
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "setup_record")
    os.makedirs(out_dir, exist_ok=True)
    log = setup_reduce.of_setup(setup_reduce.entries(),
                                seen["ctx"]["record"]["traced"][0])
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "setup_s": seen["setup_s"], "named_s": named, **t,
                   "log": log}, f, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
