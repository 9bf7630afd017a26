#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run; nothing outlives it.  The cell's entry in
``BENCHMARK.json`` names its configuration and its traffic mix; their files,
the driver (by the configuration's ``kind``), the generator (by the traffic
file's ``generator``) and the per-layer readers (by each metric's name) are
found by name — see ``benchmark/README.md``.  The last line of standard
output is the result object; everything else goes to standard error.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402


def main(argv=None, require_chip=True, t_start=None, root=harness.ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    files = harness.Files(root)
    cell, config, traffic = files.cell(args.workload)
    device, events = harness.start(cell["chips"], require_chip)
    driver = files.named("drivers", config["kind"])
    # ``setup_s`` runs from the start of the process to the start of the
    # window, less the TPU runtime's own start (the one ``jax.devices()``
    # call: 10-14.5 s that differ by seconds from run to run and that no
    # change to the program or the benchmark moves - PERF.md, PR 25); it is
    # printed beside the device on standard error
    if t_start is None:
        t_start = T_PROCESS_START + events.runtime_start_s
    out = driver.run(
        files=files, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        events=events, t_start=t_start)
    line = harness.result_line(files, args.workload, bool(args.trace), out,
                               device)
    out["compared"].print()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
