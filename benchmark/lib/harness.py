"""What every run shares: finding a cell's files by the names in
``BENCHMARK.json``, loading generators / drivers / per-layer readers by
name, the look for a chip, JAX's compile events, percentiles and the result
line.  Nothing about one cell, one traffic mix or one metric is written
here.
"""
import importlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmark/
ROOT = os.path.dirname(HERE)                                         # checkout


def say(tag, **kv):
    """A progress line on standard error (standard output carries only
    the result line)."""
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json "
                     f"(known: {[e['name'] for e in entries]})")


class Files:
    """The benchmark's files, found by name: ``<root>/BENCHMARK.json`` and,
    under each directory of its ``paths``, ``<kind>/<name>.<ext>``."""

    def __init__(self, root=ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        self.dirs = [os.path.join(root, p) for p in self.bench["paths"]]

    def path(self, kind, name, ext):
        for d in self.dirs:
            p = os.path.join(d, kind, name + ext)
            if os.path.exists(p):
                return p
        raise SystemExit(f"no {kind}/{name}{ext} under {self.dirs}")

    def named(self, kind, name):
        """``<kind>/<name>.py`` as a module, by file — names may hold dots
        (``device_idle_pct.serve``), which an import statement cannot."""
        path = self.path(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, workload):
        """(cell, config file's content, traffic file's content): both
        files found by the names the cell's entry gives."""
        cell = find(self.bench["workloads"], workload, "workload")
        entry = find(self.bench["configs"], cell["config"], "configuration")
        config = load_json(os.path.join(self.root, entry["file"]))
        traffic = load_json(self.path("traffic", cell["traffic"], ".json"))
        return cell, config, traffic

    def metrics(self, group, workload):
        """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
        return [e for e in self.bench[group]
                if "workloads" not in e or workload in e["workloads"]]


class JaxEvents:
    """Counts of JAX's own compile / persistent-cache events (copied from
    ``chip_smoke.py``): compilations inside the measured window must be 0."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == self.HIT:
            self.hits += 1
        elif name == self.MISS:
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return dict(cache_hits=self.hits, cache_misses=self.misses,
                    backend_compiles=self.compiles,
                    compile_s=round(self.compile_s, 2))


def start(chips, require_chip=True):
    """The device as JAX reports it, the compile cache and the event
    counters (``events.runtime_start_s``: what the ONE ``jax.devices()``
    call took — the TPU runtime's own start, which ``setup_s`` leaves out).
    No TPU, or fewer chips than the cell asks for: exit non-zero with no
    result (there is no CPU fallback).  ``require_chip=False`` is
    for the rehearsal tests only — never reachable from the command line."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # small helper programs (weights, norms, the reference) are cached too,
    # so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t = time.perf_counter()
    devs = jax.devices()
    runtime_start_s = time.perf_counter() - t
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("device", cache_dir=cache_dir,
        runtime_start_s=round(runtime_start_s, 2), **device)
    if require_chip:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: jax reports platform "
                             f"{devs[0].platform!r}; the benchmark has no "
                             f"CPU fallback")
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chips, jax sees "
                             f"{len(devs)}")
    events = JaxEvents()
    events.runtime_start_s = runtime_start_s
    return device, events


def start_trace(trace_dir):
    """Start the profiler with Python-call tracing off: the device lines and
    the drivers' own annotations are all the reduction reads, and tracing
    every Python call slows the host loop that is being measured."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def fresh_trace_dir(cell):
    """``<checkout>/.bench_trace/<cell>``, emptied: where a traced run's
    profile goes (a fixed path inside the checkout, listed in .gitignore)."""
    import shutil

    path = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(path, ignore_errors=True)
    return path


def read_layers(files, cell, trace_dir, ctx, out):
    """The traced run's tail, the same for every driver: ONE reduction of
    the profile, then each per-layer reader of the cell by its name; a
    reader that returns nothing leaves its metric out.  Fills ``out``
    (``layer_values``, ``busy_s``, ``window_s``, ``breakdown``)."""
    import jax

    from benchmark.lib import trace_reduce

    red = trace_reduce.reduce(trace_reduce.load_plain(
        trace_reduce.find_xplane(trace_dir)), cell["chips"])
    ctx = dict(ctx, trace=red, chips=cell["chips"],
               device_kind=jax.devices()[0].device_kind)
    for e in files.metrics("per_layer", cell["name"]):
        v = files.named("layer_metrics", e["name"]).read(ctx)
        if v is not None:
            out["layer_values"][e["name"]] = v
    out.update(busy_s=red["busy_s"], window_s=red["window_s"],
               breakdown={"device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"]})


def memory_peak_bytes(n_devices):
    """Peak bytes in use on the fullest of the devices used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_devices]]
    return int(max(peaks))


def percentile(values, q):
    """The q-th percentile (q in [0, 100]) of ``values``, the plain estimate
    (linear interpolation between the two order statistics around rank
    ``q/100 x (n - 1)``); ``None`` for no values.  The Harrell-Davis
    estimator was tried in its place and spread no less on the chip
    (PERF.md, PR 25), so the estimate everyone knows stays."""
    import numpy as np

    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=float), q))


class Compared:
    """The numbers that decide ``correct``, each beside its limit.  Printed
    as the last lines of standard error and carried in the result line."""

    def __init__(self):
        self.rows = {}

    def add(self, name, value, limit, worse="above"):
        ok = (value <= limit) if worse == "above" else (value >= limit)
        ok = bool(ok and value == value)          # NaN fails
        self.rows[name] = {"value": float(value), "limit": float(limit),
                           "fails_when": worse, "ok": ok}
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows.values())

    def print(self):
        for name, r in self.rows.items():
            say("compared", name=name, value=repr(r["value"]),
                limit=repr(r["limit"]), fails_when=r["fails_when"],
                ok=r["ok"])


def worst_leaf_gap(program, reference, skip=()):
    """Largest, over the leaves, gap between the program's norm and the
    reference's — measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger.  Returns (gap, leaf)."""
    import numpy as np

    med = float(np.median([reference[k] for k in reference]))
    worst, where = 0.0, None
    for k, r in reference.items():
        if k in skip:
            continue
        gap = abs(program[k] - r) / max(r, med)
        if not gap <= worst:           # NaN counts as worst
            worst, where = gap, k
    return worst, where


def result_line(files, workload, trace, out, device):
    """The one JSON object of the last line.  ``out`` is the driver's:
    ``values`` (end-to-end numbers by name), ``layer_values`` (per-layer
    numbers by name, traced run), ``compared``, counts, and with a trace
    ``busy_s`` / ``window_s`` / ``breakdown``."""
    group = "per_layer" if trace else "end_to_end"
    values = out["layer_values"] if trace else out["values"]
    metrics = {}
    for e in files.metrics(group, workload):
        v = values.get(e["name"])
        if v is None:
            if not trace:
                raise SystemExit(f"the driver gave no {e['name']!r}")
            continue            # a reader that found nothing returns nothing
        metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    line = {"correct": out["compared"].correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
        if out.get("breakdown"):
            line["breakdown"] = out["breakdown"]
    line["compared"] = out["compared"].rows
    return line
