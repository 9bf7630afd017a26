"""From a profiler trace (``.xplane.pb``) to the few things the per-layer
readers need: device busy time, device time and runs per XLA module, device
time per operation, and the longest idle gaps named by what the host was doing.

ONE reduction per run; every device-side reader takes its number from the
result, matching names by the patterns kept in the reader's own file.

A trace is first brought into a plain form, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, so that the
reduction can be checked against a small recorded trace kept as JSON
(``benchmark/tests/data``).
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_MARK = "bench."          # the drivers' own TraceAnnotations


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_plain(xplane_path, keep_host=lambda name: name.startswith(HOST_MARK)):
    """The trace in the plain form.  Device planes are kept whole; of the
    host planes only the events ``keep_host`` accepts."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if on_device or keep_host(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def short(name):
    """``jit__serving_decode_steps_impl(123)`` -> the part before ``(``."""
    return name.split("(")[0]


def op_name(text):
    """A device operation's event carries its whole HLO line; its name is
    what stands before `` = `` (``%fusion.12``), kept with the opcode that
    follows the result type where one can be told."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:64]
    depth, i = 0, 0
    for i, ch in enumerate(rest):                 # skip the result type
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(")[0]
    return f"{head} {opcode}"[:64]


def reduce(plain, n_devices=1, top=10):
    """Busy seconds (union of device-operation intervals, averaged over the
    devices used), the traced window, per-module and per-operation device
    seconds, and the longest idle gaps.

    The window is taken from the benchmark's own host annotations when
    there are any (first start to last end), else from the device events."""
    devices = [p for p in plain["planes"]
               if p["name"].startswith(DEVICE_PLANE)
               and p["name"][len(DEVICE_PLANE):].isdigit()][:n_devices]
    host = [ev for p in plain["planes"] if not p["name"].startswith("/device:")
            for line in p["lines"] for ev in line["events"]
            if ev[0].startswith(HOST_MARK)]
    out = {"busy_s": 0.0, "window_s": 0.0, "modules": {}, "module_runs": {},
           "module_s": {}, "ops": {}, "idle_gaps": [], "device_ops": [],
           "n_devices": len(devices)}
    if not devices:
        return out
    dev_events = [ev for p in devices for name in (OP_LINE, MODULE_LINE)
                  for ev in _line(p, name)]
    if not dev_events:
        return out
    if host:
        lo = min(ev[1] for ev in host)
        hi = max(ev[1] + ev[2] for ev in host)
    else:
        lo = min(ev[1] for ev in dev_events)
        hi = max(ev[1] + ev[2] for ev in dev_events)
    out["window_s"] = (hi - lo) / 1e9

    def clip(ev):
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        return (s, e) if e > s else None

    busy = 0.0
    gaps = []
    for p in devices:
        ops = _line(p, OP_LINE) or _line(p, MODULE_LINE)
        merged = _union([c for c in map(clip, ops) if c])
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for ev in _line(p, MODULE_LINE):
            c = clip(ev)
            if c:
                name = short(ev[0])
                out["modules"].setdefault(name, []).append(ev[2] / 1e9)
                # a run cut by the window's edge counts by its part inside
                out["module_runs"][name] = (out["module_runs"].get(name, 0.0)
                                            + (c[1] - c[0]) / ev[2])
                out["module_s"][name] = (out["module_s"].get(name, 0.0)
                                         + (c[1] - c[0]) / 1e9)
        for ev in _line(p, OP_LINE):
            c = clip(ev)
            if c:
                out["ops"][ev[0]] = (out["ops"].get(ev[0], 0.0)
                                     + (c[1] - c[0]) / 1e9)
    out["busy_s"] = busy / len(devices)
    out["device_ops"] = [[op_name(k), v / len(devices)] for k, v in sorted(
        out["ops"].items(), key=lambda kv: -kv[1])[:top]]
    # the longest idle gaps, each named by the host annotation that covers
    # most of it ("host:none" when the benchmark's loop was in none)
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host:none", 0
        for name, hs, hd in host:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out["idle_gaps"].append([best, (e - s) / 1e9])
    return out


def module_times(red, pattern):
    """Whole durations (s) of every run of a module whose short name
    contains ``pattern`` and that touches the window: for a mean time a
    run."""
    return [d for name, ds in red["modules"].items() if pattern in name
            for d in ds]


def module_runs(red, pattern):
    """How many runs of the modules whose short name contains ``pattern``
    lie in the window, a run cut by its edge counted by the part inside
    (per device) — what a reader multiplies a run's work by."""
    n = max(1, red["n_devices"])
    return sum(v for name, v in red["module_runs"].items()
               if pattern in name) / n


def module_seconds(red, pattern):
    """Device seconds inside the window of the modules whose short name
    contains ``pattern`` (per device)."""
    n = max(1, red["n_devices"])
    return sum(v for name, v in red["module_s"].items()
               if pattern in name) / n


def op_seconds(red, pattern):
    """Summed device seconds of every operation whose name contains
    ``pattern`` (per device)."""
    n = max(1, red["n_devices"])
    return sum(v for k, v in red["ops"].items() if pattern in k) / n
