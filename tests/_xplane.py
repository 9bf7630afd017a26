"""Reading the program's spans back out of a ``jax.profiler`` trace (the
one sink of ``observability.trace.span``): shared by the tests that hold
the spans' contract."""
import contextlib
import glob
import os


@contextlib.contextmanager
def profiled(trace_dir):
    """A profiler session without Python-call tracing (the spans and the
    runtime's own events are all the tests read)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(trace_dir, prefixes):
    """Events of the host planes whose name starts with one of
    ``prefixes``, as dicts ``{name, start, end, line, **stats}`` sorted by
    start."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tuple(prefixes)):
                    out.append(dict(
                        {k: v for k, v in e.stats}, name=e.name,
                        start=e.start_ns, end=e.start_ns + e.duration_ns,
                        line=line.name))
    return sorted(out, key=lambda e: (e["start"], -e["end"]))


def inside(child, parent):
    return (child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])
