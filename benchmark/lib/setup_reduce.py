"""From the program's own start-up record to where ``setup_s`` went.

The program (since PR 37) keeps, in memory, a log of what it did before
the window: JAX's compile stages by the monitored program they ran for,
the phases of construction, the package's import and the collector's
pauses (``paddle_tpu/observability/compilecache.py::startup``; entries
``{stage, t_start, t_end, tid, ...}`` on ``time.perf_counter()``, the
drivers' clock).  The readers here hold only strings.  The profiler session
of a traced run covers the last seconds of the window, so the xplane
cannot show set-up: this log is the one record of it.

**Where set-up ends**, for every driver: at the end of the last compile
stage that ended before the traced sub-window began
(``ctx["record"]["traced"][0]``).  Nothing compiles in a correct window, so
that is set-up's last compile; the float32 reference compiles after the
window and a collection inside the window starts after that end — both are
left out.  An entry belongs to set-up when it STARTED by then (the
``first_call`` that holds the last stage ends a moment after it).

**Each second is counted once**, under the innermost entry open on its
thread: an entry's self time is its duration less what the entries nested
in it cover (choosing-metrics, section 4), so the six seconds-metrics and
``setup_gc_s`` never overlap and their sum stays under ``setup_s``.
``cache_retrieval`` lies inside ``load`` and is not a level of its own (it
is totalled beside the table, not subtracted).  What the sum leaves of ``setup_s`` is what
the record does not name: the benchmark's weight makers' device time, the
warm-up requests' device time and host steps, JAX's own import.

A program that keeps no such record (the parent of PR 37) gives ``None``,
and every reader built on this returns ``None``.
"""
COMPILE_STAGES = ("trace", "lower", "load", "cache_retrieval")
# the metric a phase's self time belongs to, by the phase's name
PHASE_KIND = {"import": "import", "host.gc": "gc",
              **dict.fromkeys(("serving.init", "serving.init.params",
                               "serving.init.cache", "train.build"),
                              "construct")}


def entries():
    """The program's log, or ``None`` where the program keeps none."""
    try:
        from paddle_tpu.observability import compilecache
    except ImportError:
        return None
    log = getattr(compilecache, "startup", None)
    return None if log is None else log.entries()


def of_setup(log, t_window):
    """The entries of set-up (module docstring), or ``[]`` when no compile
    stage ended before ``t_window``."""
    ends = [e["t_end"] for e in log
            if e["stage"] in COMPILE_STAGES and e["t_end"] <= t_window]
    if not ends:
        return []
    cut = max(ends)
    return [e for e in log if e["t_start"] <= cut and e["t_end"] <= t_window]


def self_seconds(log):
    """``[(entry, self seconds)]``: per thread, an entry's duration less
    what its direct children cover.  ``cache_retrieval`` is not a level."""
    out = []
    by_tid = {}
    for e in log:
        if e["stage"] != "cache_retrieval":
            by_tid.setdefault(e["tid"], []).append(e)
    for es in by_tid.values():
        es.sort(key=lambda e: (e["t_start"], -e["t_end"]))
        stack = []                              # [entry, self seconds]
        for e in es:
            while stack and stack[-1][0]["t_end"] <= e["t_start"]:
                out.append(tuple(stack.pop()))
            if stack:
                parent = stack[-1][0]
                stack[-1][1] -= max(0.0, min(e["t_end"], parent["t_end"])
                                    - max(e["t_start"], parent["t_start"]))
            stack.append([e, e["t_end"] - e["t_start"]])
        out.extend(tuple(x) for x in stack)
    return out


def table(ctx):
    """``{"seconds": {kind: self seconds}, "programs": loads, "rows":
    [(name, stage, self seconds, count)] largest first, "retrieval_s"}`` of
    the run's set-up, or ``None`` (no record, no traced sub-window, or no
    compile before it)."""
    log, traced = entries(), ctx["record"].get("traced")
    if log is None or traced is None:
        return None
    log = of_setup(log, traced[0])
    if not log:
        return None
    seconds = dict.fromkeys(("import", "construct", "trace", "lower", "load",
                             "first_call", "gc"), 0.0)
    rows = {}
    for e, own in self_seconds(log):
        kind = (PHASE_KIND.get(e["name"]) if e["stage"] == "phase"
                else e["stage"])
        if kind in seconds:
            seconds[kind] += own
        if e["stage"] == "phase":
            key = (e["name"], "phase")
        elif e["program"] == "-":
            key = (e["fun_name"], e["stage"])
        else:
            key = (e["cache"] + "/" + e["program"], e["stage"])
        row = rows.setdefault(key, [0.0, 0])
        row[0] += own
        row[1] += 1
    return {
        "seconds": seconds,
        "programs": sum(e["stage"] == "load" for e in log),
        "retrieval_s": sum(e["t_end"] - e["t_start"] for e in log
                           if e["stage"] == "cache_retrieval"),
        "rows": sorted(((k[0], k[1], v[0], v[1]) for k, v in rows.items()),
                       key=lambda r: -r[2])}


def seconds(ctx, kind):
    t = table(ctx)
    return None if t is None else t["seconds"][kind]


def programs(ctx):
    t = table(ctx)
    return None if t is None else float(t["programs"])
