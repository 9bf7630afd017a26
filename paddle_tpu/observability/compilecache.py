"""Compile-cache visibility and the start-up record.

A ``jax.jit`` cache miss (new shape / static-arg combination) silently costs
seconds of trace+lower+compile on the dispatch path; a recompile storm —
e.g. a serving bucket set that explodes, or a training loop feeding varying
shapes — shows up only as mysterious latency.  ``CompileCacheMonitor`` makes
it a first-class metric:

* ``mark_trace(program)`` is called from INSIDE the jitted function body —
  host python there runs exactly once per trace, i.e. per cache miss.
* ``call(program, fn, *args)`` wraps the dispatch: if the call traced, the
  wall time of that dispatch (trace + compile; execution is async and
  returns immediately) lands in ``compile_seconds{cache,program}`` and
  ``compile_cache_misses_total`` increments — otherwise it was a cache hit.

Series (shared names, ``cache``/``program`` labels):
``compile_cache_hits_total``, ``compile_cache_misses_total``,
``compile_seconds``.  Host-side memo caches (e.g. the decode-param pytree
cache) reuse the counters via ``hit()``/``miss()`` with no timing.

**The start-up record** (:data:`startup`) is where the seconds before the
first request go, kept in memory by the program itself: a bounded log of
intervals on ``time.perf_counter()``, fed where the work happens.

* *Compile stages, by program.*  ONE listener on JAX's own duration events
  (``jax.monitoring``; registered by the first ``CompileCacheMonitor``)
  turns each trace / lowering / backend-compile / cache-retrieval into an
  entry ``{stage, cache, program, fun_name, t_start, t_end, parent, tid}``
  (:data:`~paddle_tpu.observability.trace.STAGES`; ``load`` is JAX's
  backend-compile event — on a warm persistent cache the key, the
  retrieval, deserialising and loading the executable; on a cold one the
  compilation — and ``cache_retrieval`` lies inside it).  ``call`` notes
  the ``(cache, program)`` it is inside; a stage outside every monitored
  call (helpers, eager operations) is ``program="-"`` under JAX's own
  ``fun_name``.  A jit called inside a traced function ends its own trace
  first: it is folded into the trace that holds it (``inner`` counts
  them), so ``trace`` entries never overlap and the log stays a few
  entries a program (Xing4's three programs trace ~9,600 jits between them).  A miss's whole dispatch is
  the entry ``first_call`` — the two stamps ``compile_seconds`` observes —
  and holds its stages by interval containment on the thread: what is
  left of it is Python outside JAX's stages (flattening the operands,
  hashing the key).  A cache HIT writes nothing.  The same seconds feed
  ``compile_stage_seconds_total{cache,program,stage}``.
* *Phases.*  ``phase(name, **detail)`` is ``trace.span(name, **detail)``
  plus one entry ``{stage: "phase", name, t_start, t_end, parent, tid}``:
  the package's import, an engine's construction
  (``serving.init`` > ``.params`` / ``.cache``), ``train.build``.
* *Collector pauses.*  A generation-2 collection is a ``host.gc`` phase.

The log keeps its FIRST entries when full (set-up is what must survive) and
counts the rest.  ``report()`` is the account by row, largest first.
"""
from __future__ import annotations

import functools
import gc
import threading
import time
import weakref

from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.observability.trace import span

__all__ = ["CompileCacheMonitor", "all_monitors", "StartupLog", "startup",
           "phase", "report"]

_LABELS = ("cache", "program")

# every live monitor, weakly held — analysis.runtime.assert_no_retrace()
# watches all of them by default without keeping any alive
_MONITORS = weakref.WeakSet()

# JAX's duration events (jax/_src/dispatch.py, compiler.py) by stage
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# an event arrives with its duration alone; its start is the listener's
# clock less the duration, late by the microseconds between JAX's stamp
# and ours — the slack by which a trace may seem to start before the one
# that holds it (tracing reaches an inner jit tens of microseconds in)
_FOLD_SLACK_S = 2e-5


def all_monitors():
    """Snapshot list of every live CompileCacheMonitor in the process."""
    return list(_MONITORS)


class _Open(threading.local):
    """What this thread is inside: the monitored call, the innermost phase
    and the collection under way."""
    program = None
    phase = None
    gc = None


_open = _Open()


class StartupLog:
    """Bounded in-memory log of start-up intervals (module docstring).
    ``capacity`` entries are kept — the first ones — and ``dropped`` counts
    what came after."""

    def __init__(self, capacity=16384):
        self.capacity = int(capacity)
        self.dropped = 0
        self._entries = []
        # re-entrant: a collection (``host.gc``) can start inside ``add``
        self._lock = threading.RLock()

    def add(self, entry):
        with self._lock:
            if len(self._entries) < self.capacity:
                self._entries.append(entry)
            else:
                self.dropped += 1

    def add_stage(self, entry):
        """One compile stage that just ended on its thread; returns the
        seconds that are its own.  A trace takes out of the log the traces
        that began inside it on its thread (the jits its function called:
        they ended first, and other entries — a collection, a helper's
        load — may lie between them) and counts them as ``inner``."""
        own = entry["t_end"] - entry["t_start"]
        with self._lock:
            if entry["stage"] == "trace":
                es, began = self._entries, entry["t_start"] - _FOLD_SLACK_S
                i = len(es)
                while i and es[i - 1]["t_end"] >= began:
                    i -= 1
                kept, inner = [], 0
                for e in es[i:]:
                    if e["stage"] == "trace" and e["tid"] == entry["tid"] \
                            and e["t_start"] >= began:
                        inner += 1 + e.get("inner", 0)
                        own -= e["t_end"] - e["t_start"]
                    else:
                        kept.append(e)
                if inner:
                    es[i:] = kept
                    entry["inner"] = inner
            self.add(entry)
        return max(own, 0.0)

    def entries(self):
        """A copy of the log, in the order the intervals ENDED."""
        with self._lock:
            return list(self._entries)


startup = StartupLog()


class phase:
    """``with phase("serving.init", **detail): ...`` — a span of the
    profiler's timeline (``trace.span``) and one entry of :data:`startup`,
    whose ``parent`` is the phase that was open on the thread."""

    __slots__ = ("name", "detail", "seconds", "_span", "_parent", "_t0")

    def __init__(self, name, **detail):
        self.name = name
        self.detail = detail

    def __enter__(self):
        self._parent, _open.phase = _open.phase, self.name
        self._span = span(self.name, **self.detail)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._span.__exit__(*exc)
        _open.phase = self._parent
        self.seconds = t1 - self._t0
        note_phase(self.name, self._t0, t1, parent=self._parent,
                   **self.detail)
        return False


def note_phase(name, t_start, t_end, parent=None, **detail):
    """A phase that was stamped, not opened (the package's import, which
    ends before anything could open it)."""
    entry = {"stage": "phase", "name": name, "t_start": t_start,
             "t_end": t_end, "parent": parent, "tid": threading.get_ident()}
    if detail:
        entry["detail"] = detail
    startup.add(entry)


def _on_duration(event, secs, fun_name="", **_):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    t_end = time.perf_counter()
    cache, program = _open.program or ("-", "-")
    own = startup.add_stage({
        "stage": stage, "cache": cache, "program": program,
        "fun_name": fun_name, "t_start": t_end - secs, "t_end": t_end,
        "parent": _open.phase, "tid": threading.get_ident()})
    _stage_seconds.labels(cache=cache, program=program, stage=stage).inc(own)


def _on_gc(when, info):
    """``gc.callbacks``: a generation-2 collection is a ``host.gc`` phase;
    younger generations return at once."""
    if info["generation"] != 2:
        return
    if when == "start":
        _open.gc = phase("host.gc").__enter__()
    elif _open.gc is not None:
        done, _open.gc = _open.gc, None
        done.__exit__(None, None, None)


_stage_seconds = None


def _install():
    """Register the one ``jax.monitoring`` listener and the collector's
    callback — once a process, by the first monitor."""
    global _stage_seconds
    if _stage_seconds is not None:
        return
    import jax.monitoring

    _stage_seconds = get_registry().counter(
        "compile_stage_seconds_total",
        "wall seconds of JAX's compile stages (trace / lower / load / "
        "cache_retrieval) by the monitored program they ran for",
        labelnames=_LABELS + ("stage",))
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)


def report():
    """The start-up account: rows ``{kind, name, stage, seconds, count}``,
    largest first.  A phase is a row by its name; a compile stage by its
    ``cache/program`` (by JAX's ``fun_name`` outside a monitored call).
    ``first_call`` and phase rows are whole intervals: they hold the stages
    inside them."""
    rows = {}
    for e in startup.entries():
        if e["stage"] == "phase":
            key = ("phase", e["name"], "")
        elif e["program"] == "-":
            key = ("helper", e["fun_name"], e["stage"])
        else:
            key = ("program", f"{e['cache']}/{e['program']}", e["stage"])
        row = rows.setdefault(key, dict(
            zip(("kind", "name", "stage"), key), seconds=0.0, count=0))
        row["seconds"] += e["t_end"] - e["t_start"]
        row["count"] += 1
    return sorted(rows.values(), key=lambda r: -r["seconds"])


class CompileCacheMonitor:
    def __init__(self, cache, registry=None):
        reg = registry if registry is not None else get_registry()
        self.cache = cache
        _MONITORS.add(self)
        _install()
        self._hits = reg.counter(
            "compile_cache_hits_total",
            "dispatches served by an already-compiled program",
            labelnames=_LABELS)
        self._misses = reg.counter(
            "compile_cache_misses_total",
            "dispatches that traced + compiled a new program "
            "(or rebuilt a host-side cache entry)", labelnames=_LABELS)
        self._seconds = reg.histogram(
            "compile_seconds", "wall seconds of dispatches that compiled",
            labelnames=_LABELS)
        self._trace_counts = {}

    # ------------------------------------------------- jit-body trace hook
    def mark_trace(self, program):
        """Call from inside a jitted function body: runs once per trace."""
        self._trace_counts[program] = self._trace_counts.get(program, 0) + 1

    def traces(self, program):
        return self._trace_counts.get(program, 0)

    def trace_counts(self):
        """Copy of the per-program trace counts (retrace-assert snapshots)."""
        return dict(self._trace_counts)

    def call(self, program, fn, *args, **kwargs):
        """Dispatch ``fn`` and classify it as hit or miss via the trace
        count (``fn``'s body must ``mark_trace(program)``).  The two stores
        to ``_open.program`` are all a hit pays for the start-up record; a
        miss is its ``first_call`` entry, from the stamps ``compile_seconds``
        observes."""
        before = self._trace_counts.get(program, 0)
        _open.program = (self.cache, program)
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            _open.program = None
        if self._trace_counts.get(program, 0) > before:
            t1 = time.perf_counter()
            self._misses.labels(cache=self.cache, program=program).inc()
            self._seconds.labels(cache=self.cache, program=program).observe(
                t1 - t0)
            startup.add({"stage": "first_call", "cache": self.cache,
                         "program": program, "fun_name": "", "t_start": t0,
                         "t_end": t1, "parent": _open.phase,
                         "tid": threading.get_ident()})
        else:
            self._hits.labels(cache=self.cache, program=program).inc()
        return out

    def wrap(self, program, fn):
        """``fn`` pre-bound through :meth:`call` (module-level jit entry
        points re-export their instrumented selves)."""
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return self.call(program, fn, *args, **kwargs)
        wrapped.__wrapped__ = fn
        return wrapped

    # -------------------------------------------- host-side memo caches
    def hit(self, program):
        self._hits.labels(cache=self.cache, program=program).inc()

    def miss(self, program, seconds=None):
        self._misses.labels(cache=self.cache, program=program).inc()
        if seconds is not None:
            self._seconds.labels(cache=self.cache,
                                 program=program).observe(seconds)
