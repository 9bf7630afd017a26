#!/usr/bin/env python3
"""Look at a traced run's xplane by hand (on the chip), and cut the small
recorded trace the tests of ``lib/span_reduce.py`` check it against.

    python3 benchmark/tools/xplane_probe.py --cell <name> [--record FILE --ms 120]

Reads the newest ``.bench_trace/<cell>/plugins/profile/*/*.xplane.pb`` (what
``run.py --trace 1`` of that cell leaves behind).  Prints the planes and
lines with their event counts, how many device operations carry a scope
path and a sample of them, the program's host spans, and the reduction's
tables (device self time per run by module and scope: what section 5 of
``PERF.md`` is written from).  ``--record``
writes the first ``--ms`` milliseconds of the traced window in
``span_reduce``'s plain form (names cut to 96 characters), gzipped.
"""
import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, span_reduce, trace_reduce  # noqa: E402


def cut(plain, ms):
    """The first ``ms`` of the window the ``bench.`` marks span."""
    marks = [ev for p in plain["planes"] if not p["name"].startswith("/device:")
             for line in p["lines"] for ev in line["events"]
             if ev[0].startswith(trace_reduce.HOST_MARK)]
    lo = min(ev[1] for ev in marks)
    hi = lo + int(ms * 1e6)
    out = []
    for p in plain["planes"]:
        lines = []
        for line in p["lines"]:
            events = [[ev[0][:96], ev[1], ev[2], ev[3]] for ev in line["events"]
                      if ev[1] >= lo and ev[1] + ev[2] <= hi]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return {"planes": out}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--record", default=None)
    ap.add_argument("--ms", type=float, default=120.0)
    ap.add_argument("--grep", default=None,
                    help="list the operations whose name or path holds this")
    args = ap.parse_args()
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(os.path.join(
        harness.ROOT, ".bench_trace", args.cell))
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            print(f"  {plane.name} | {line.name}: "
                  f"{sum(1 for _ in line.events)} events")
    paths = span_reduce.op_paths(path)
    names = frozenset(span_reduce.NAMES)
    scoped = [(n, p) for n, by in paths.items() for p in by.values()
              if span_reduce.scope_of(p, names)]
    print(f"{len(paths)} device operations carry a path, "
          f"{len(scoped)} a name of the vocabulary; for example:")
    for n, p in scoped[:5]:
        print(f"  {trace_reduce.op_name(n)}: {p}")
    plain = span_reduce.load_scoped(path)
    for p in plain["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            seen = {}
            for ev in line["events"]:
                seen.setdefault(ev[0], ev[3])
            for name, detail in seen.items():
                print(f"  {p['name']} | {line['name']} | {name} {detail}")
    red = span_reduce.reduce(plain, span_reduce.NAMES)
    print(f"window {red['window_s']:.4f} s; device self time per run, by "
          f"module and scope (ms):")
    for module, table in sorted(red["self_s"].items()):
        runs = red["module_runs"].get(module, 0.0)
        if runs < 0.5 or sum(table.values()) < 1e-4:
            continue
        per = {k or "(no name)": round(1e3 * v / runs, 4)
               for k, v in sorted(table.items(), key=lambda kv: -kv[1])}
        print(f"  {module}: runs {runs:.2f}, sum "
              f"{1e3 * sum(table.values()) / runs:.4f}, recompute "
              f"{1e3 * red['recompute_s'].get(module, 0.0) / runs:.4f}: "
              f"{json.dumps(per)}")
        made = {k: round(1e3 * v / runs, 4) for k, v in sorted(
            red["inherited_s"].get(module, {}).items(),
            key=lambda kv: -kv[1])}
        print(f"    of which compiler-made operations named after their "
              f"consumer: {json.dumps(made)}")
    if args.grep:
        dev = [p for p in plain["planes"]
               if p["name"].startswith(trace_reduce.DEVICE_PLANE)]
        found = {}
        for ev, self_ns in span_reduce.self_times(
                trace_reduce._line(dev[0], trace_reduce.OP_LINE)):
            if args.grep in ev[0] or args.grep in ev[3]:
                row = found.setdefault((ev[0], ev[3]), [0, 0])
                row[0] += 1
                row[1] += self_ns
        print(f"operations holding {args.grep!r} (events, self ms in the "
              f"whole trace, name, path):")
        for (name, path_), (n, ns) in sorted(found.items(),
                                             key=lambda kv: -kv[1][1])[:60]:
            print(f"  {n} {ns / 1e6:.3f} {name} | {path_}")
    print("host seconds by span:", json.dumps(
        {k: round(v, 5) for k, v in sorted(red["host_s"].items())}),
        "own:", red["host_own_s"])
    if args.record:
        with gzip.open(args.record, "wt") as f:
            json.dump(cut(plain, args.ms), f)
        print(f"wrote {args.record}: {os.path.getsize(args.record)} bytes")


if __name__ == "__main__":
    main()
