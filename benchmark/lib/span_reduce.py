"""From the SAME profiler trace ``trace_reduce`` reads to what the program's
own names say: device SELF time per named scope and per XLA module, and
the host seconds of the program's spans.

The program (since PR 26) labels its device work with ``jax.named_scope``
(``embed``, ``attn.core``, ``mlp``, ... — ``paddle_tpu/observability/
trace.py`` keeps the one list; the readers here hold only strings) and puts
its host phases on the profiler's timeline as ``serving.*`` / ``train.*``
events.  ``trace_reduce.load_plain`` drops both (it keeps a device event's
name alone and of the host planes only ``bench.`` events), and a reader's
``ctx`` does not say where the trace is, so this module finds the run's
xplane itself (``<checkout>/.bench_trace/*/``: the newest), loads it once
for all readers of a run, and clips to the same window ``ctx["trace"]``
used: first ``bench.`` start to last ``bench.`` end.

Self time: on the ``XLA Ops`` line a ``%while`` (or a call) is ONE event
that spans its body's operations, which are events of the same line —
``trace_reduce``'s ``ops`` counts both.  An event's self time is its
duration less what the events nested inside it cover, so self times of a
module's operations add up to the module's busy time.

Plain form (``benchmark/tests/data/trace_chat_scoped_120ms.json.gz``):
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns, extra], ...]}]}]}`` — ``extra`` is the operation's scope path
(its ``op_name``) on a device line and the span's arguments on a host line.

A program that carries no names (the parent of PR 26) gives empty tables,
and every reader built on them returns ``None``.
"""
import glob
import os
import re

from benchmark.lib import harness, trace_reduce

HOST_PREFIXES = (trace_reduce.HOST_MARK, "serving.", "train.")
# one part of a path: the transforms JAX wraps a scope in, then the scope
PART = re.compile(r"((?:\w+\()*)([^()]*)\)*")
RECOMPUTE = "rematted_computation"
UNSCOPED = ""
# the names the program gives its device work (PERF.md, "spans and counters
# inside the program"): the benchmark's own copy, so that every reader
# shares ONE reduction
NAMES = ("embed", "norm", "attn.qkv", "attn.rope", "attn.kv_write",
         "attn.core", "attn.out", "mlp", "lm_head", "sample", "loss",
         "optimizer", "decode.steps", "attn.core.chunks")
# where an operation's scope path stands in the xplane (seen on a v5e, PR
# 26): NOT in the event's name (the HLO line without its metadata) nor in
# the event's own stats, but in the stats of the event's METADATA entry
# (``XEventMetadata.stats``), which ``jax.profiler.ProfileData`` does not
# show — so those entries are read from the file's bytes
PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"

_cache = {}


def newest_xplane(root=None):
    """The xplane of the run in progress: the newest under
    ``<checkout>/.bench_trace/*/`` (``harness.fresh_trace_dir`` empties a
    cell's directory before each traced run), or ``None``."""
    files = glob.glob(os.path.join(root or harness.ROOT, ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of one protobuf message in ``buf[start:end]``:
    an int for a varint or fixed field, a ``(start, end)`` range of ``buf``
    for a length-delimited one (read only when wanted: skipping a plane's
    lines costs nothing)."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v = int.from_bytes(buf[i:i + n], "little")
            i += n
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _entries(buf, rng):
    """The values of a protobuf map field's entry (key = 1, value = 2)."""
    for no, v in _fields(buf, *rng):
        if no == 2:
            yield v


# an operation the compiler made (a weight's re-layout copy, an async
# slice and its wait) has no op_name of its own.  It is given the path of
# the nearest operation that consumes it (else produces for it), marked
# INHERITED.  The operand graph is each program's HLO module, which the
# profiler keeps in the ``/host:metadata`` plane; a trace without it names
# no such operation.
INHERITED = "~"
HLO_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _nearest_named(start, edges, nodes):
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for n in frontier:
            for m in edges(n):
                if m not in seen:
                    seen.add(m)
                    if nodes[m][0]:
                        return nodes[m][0]
                    nxt.append(m)
        frontier = nxt
    return ""


def _inherit(nodes):
    """The paths the unnamed operations of one program, ``{instruction:
    [path, operands]}``, take: that of their nearest named consumer, else
    producer (breadth first, so the nearest wins)."""
    consumers = {}
    for name, (_, operands) in nodes.items():
        for o in operands:
            if o in nodes:
                consumers.setdefault(o, []).append(name)
    up = lambda n: consumers.get(n, ())
    down = lambda n: [o for o in nodes[n][1] if o in nodes]
    found = {}
    for name, (path, _) in nodes.items():
        if not path:
            near = (_nearest_named(name, up, nodes)
                    or _nearest_named(name, down, nodes))
            if near:
                found[name] = INHERITED + near
    return found


def hlo_nodes(buf, rng):
    """``{"%instruction": [op_name, ["%operand", ...]]}`` of one serialized
    ``HloProto`` (hlo_module = 1; ``HloModuleProto``: computations = 3;
    ``HloComputationProto``: instructions = 2; ``HloInstructionProto``:
    name = 1, metadata = 7 (``OpMetadata``: op_name = 2), id = 35,
    operand_ids = 36)."""
    text = lambda r: buf[r[0]:r[1]].decode(errors="replace")
    by_id, rows = {}, []
    for no, module in _fields(buf, *rng):
        if no != 1:
            continue
        for no2, comp in _fields(buf, *module):
            if no2 != 3:
                continue
            for no3, ins in _fields(buf, *comp):
                if no3 != 2:
                    continue
                name, path, uid, operands = "", "", None, []
                for no4, v in _fields(buf, *ins):
                    if no4 == 1:
                        name = "%" + text(v)
                    elif no4 == 7:
                        path = next((text(x) for n5, x in _fields(buf, *v)
                                     if n5 == 2), "")
                    elif no4 == 35:
                        uid = v
                    elif no4 == 36 and isinstance(v, tuple):   # packed
                        i = v[0]
                        while i < v[1]:
                            o, i = _varint(buf, i)
                            operands.append(o)
                    elif no4 == 36:
                        operands.append(v)
                by_id[uid] = name
                rows.append((name, path, operands))
    return {name: [path, [by_id[o] for o in operands if o in by_id]]
            for name, path, operands in rows}


def op_paths(xplane_path):
    """``{event name: {program id: scope path}}`` of the device planes,
    from the xplane's bytes (``tsl``'s ``XSpace``: planes = 1; ``XPlane``:
    name = 2, event_metadata = 4, stat_metadata = 5; ``XEventMetadata``:
    name = 2, stats = 5; ``XStat``: metadata_id = 1, uint64 = 3, str = 5,
    bytes = 6; ``XStatMetadata``: id = 1, name = 2).  The path is the
    operation's ``op_name`` (the stat ``tf_op`` of its event-metadata
    entry, which ends in ``:``); an operation without one inherits
    (``_inherit``, over its program's HLO module) and its path starts
    with ``~``."""
    with open(xplane_path, "rb") as f:
        buf = f.read()
    text = lambda r: buf[r[0]:r[1]].decode(errors="replace")
    own = {}                 # (program id, "%instruction") -> [event, path]
    graphs = {}              # program id -> hlo_nodes
    for no, plane in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name, metas, stat_names = "", [], {}
        for no2, v in _fields(buf, *plane):
            if no2 == 2:
                name = text(v)
            elif no2 == 4:
                metas.extend(_entries(buf, v))
            elif no2 == 5:
                for entry in _entries(buf, v):
                    d = dict(_fields(buf, *entry))
                    if 1 in d and 2 in d:
                        stat_names[d[1]] = text(d[2])
        on_device = name.startswith(trace_reduce.DEVICE_PLANE)
        if not on_device and name != HLO_PLANE:
            continue
        for meta in metas:
            ev_name, stats = "", {}
            for no3, v in _fields(buf, *meta):
                if no3 == 2:
                    ev_name = text(v)
                elif no3 == 5:
                    stat = dict(_fields(buf, *v))
                    stats[stat_names.get(stat.get(1))] = stat
            if not on_device:
                proto = stats.get(HLO_STAT, {}).get(6)
                if proto:
                    graphs[_program_of(ev_name)] = hlo_nodes(buf, proto)
                continue
            head, eq, _ = ev_name.partition(" = ")
            if eq:           # an HLO line, not a step or a module
                path = stats.get(PATH_STAT, {}).get(5)
                own[stats.get(PROGRAM_STAT, {}).get(3, 0), head] = [
                    ev_name, text(path).rstrip(":") if path else ""]
    inherited = {program: _inherit(nodes)
                 for program, nodes in graphs.items()}
    out = {}
    for (program, head), (ev_name, path) in own.items():
        path = path or inherited.get(program, {}).get(head, "")
        if path:
            out.setdefault(ev_name, {})[program] = path
    return out


def _program_of(module_event_name):
    """``jit__step_fn(123)`` -> 123 (the program id a module's run is
    named with), 0 when there is none."""
    inner = module_event_name.rpartition("(")[2].rstrip(")")
    return int(inner) if inner.isdigit() else 0


def load_scoped(xplane_path):
    """The trace in this module's plain form: device lines whole, each
    operation with its scope path; of the host planes the ``bench.`` marks
    and the program's spans with their arguments."""
    from jax.profiler import ProfileData

    paths = op_paths(xplane_path)
    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        on_device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        lines = {line.name: line for line in plane.lines}
        out = []
        for line in plane.lines:
            if on_device and line.name == trace_reduce.OP_LINE:
                # an HLO line that stands in two programs (a decode and a
                # prefill fusion of one shape) has a path in each: told
                # apart by the program whose run holds the operation
                runs = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     _program_of(e.name))
                    for e in lines[trace_reduce.MODULE_LINE].events
                ) if trace_reduce.MODULE_LINE in lines else []
                events, i, short = [], 0, {}
                for e in line.events:
                    name = e.name
                    if name not in short:     # ~10^4 names, ~10^6 events
                        short[name] = trace_reduce.op_name(name)
                    by_program = paths.get(name, {})
                    path = ""
                    if len(by_program) == 1:
                        path, = by_program.values()
                    elif by_program:
                        t = int(e.start_ns)
                        while i + 1 < len(runs) and runs[i + 1][0] <= t:
                            i += 1
                        while i > 0 and runs[i][0] > t:
                            i -= 1
                        path = by_program.get(runs[i][2], "") if runs else ""
                    events.append([short[name], int(e.start_ns),
                                   int(e.duration_ns), path])
            elif on_device:
                events = [[e.name, int(e.start_ns), int(e.duration_ns), ""]
                          for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns),
                           {k: v for k, v in e.stats}]
                          for e in line.events
                          if e.name.startswith(HOST_PREFIXES)]
            if events:
                out.append({"name": line.name, "events": events})
        if out:
            planes.append({"name": plane.name, "lines": out})
    return {"planes": planes}


def scope_of(path, names):
    """The innermost of ``names`` in an operation's path: the last
    ``/``-separated part that IS one of them once the transforms JAX wraps
    a scope in are taken off (``transpose(jvp(attn.core))`` ->
    ``attn.core``); a jitted function of the same name (``jit(norm)``) is
    not a scope.  ``UNSCOPED`` when none is."""
    for part in reversed(path.split("/")):
        m = PART.fullmatch(part)
        if m and m.group(2) in names and "jit(" not in m.group(1):
            return m.group(2)
    return UNSCOPED


def self_times(events):
    """``[(event, self_ns)]`` for events ``[name, start, duration, ...]`` of
    ONE line: each event's duration less what the events nested inside it
    cover (children are charged to themselves, never twice)."""
    out = []
    stack = []           # [event, end, covered_by_children]

    def close():
        ev, end, covered = stack.pop()
        out.append((ev, max(0, ev[2] - covered)))
        if stack:
            stack[-1][2] += ev[2]

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= ev[1]:
            close()
        if stack and ev[1] + ev[2] > stack[-1][1]:
            # overlaps its predecessor without nesting in it (async
            # copies): charged whole, its sibling's cover unchanged
            out.append((ev, ev[2]))
            continue
        stack.append([ev, ev[1] + ev[2], 0])
    while stack:
        close()
    return out


def _union_ns(intervals):
    return sum(e - s for s, e in trace_reduce._union(intervals))


def reduce(plain, names, n_devices=1):
    """The tables the readers share.  ``names``: the scope and loop names
    to tell apart (the readers' own list).

    ``self_s[module][scope]``: device self seconds inside the window of
    the operations of ``module`` (short name) under ``scope`` (``""`` for
    none), per device; ``inherited_s``: the part of ``self_s`` that is
    compiler-made operations named after their consumer (``~`` paths);
    ``recompute_s[module]``: those whose path holds
    ``rematted_computation``; ``module_runs``: as ``trace_reduce``'s;
    ``host_s[span]``: seconds of the union of the span's events inside the
    window; ``host_own_s``: seconds inside ``serving.step`` or
    ``serving.submit`` and outside ``serving.drain.wait``; ``window_s``.
    """
    names = frozenset(names)
    devices = [p for p in plain["planes"]
               if p["name"].startswith(trace_reduce.DEVICE_PLANE)
               and p["name"][len(trace_reduce.DEVICE_PLANE):].isdigit()
               ][:n_devices]
    host = [ev for p in plain["planes"]
            if not p["name"].startswith("/device:")
            for line in p["lines"] for ev in line["events"]]
    marks = [ev for ev in host if ev[0].startswith(trace_reduce.HOST_MARK)]
    out = {"window_s": 0.0, "self_s": {}, "inherited_s": {},
           "recompute_s": {}, "module_runs": {}, "host_s": {},
           "host_own_s": None}
    if not marks:
        return out
    lo = min(ev[1] for ev in marks)
    hi = max(ev[1] + ev[2] for ev in marks)
    out["window_s"] = (hi - lo) / 1e9

    def clip(ev):
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        return (s, e) if e > s else None

    # host spans: union per name, and the engine thread's own work
    by_name = {}
    for ev in host:
        c = clip(ev)
        if c and not ev[0].startswith(trace_reduce.HOST_MARK):
            by_name.setdefault(ev[0], []).append(c)
    out["host_s"] = {k: _union_ns(v) / 1e9 for k, v in by_name.items()}
    inside = by_name.get("serving.step", []) + by_name.get(
        "serving.submit", [])
    if inside:
        waits = trace_reduce._union(by_name.get("serving.drain.wait", []))
        own = 0
        for s, e in trace_reduce._union(inside):
            own += (e - s) - sum(min(e, we) - max(s, ws)
                                 for ws, we in waits if we > s and ws < e)
        out["host_own_s"] = own / 1e9

    n = max(1, len(devices))
    for p in devices:
        modules = sorted(trace_reduce._line(p, trace_reduce.MODULE_LINE),
                         key=lambda e: e[1])
        for ev in modules:
            c = clip(ev)
            if c:
                k = trace_reduce.short(ev[0])
                out["module_runs"][k] = (out["module_runs"].get(k, 0.0)
                                         + (c[1] - c[0]) / ev[2] / n)
        clipped = []
        for ev in trace_reduce._line(p, trace_reduce.OP_LINE):
            c = clip(ev)
            if c:
                clipped.append([ev[0], c[0], c[1] - c[0], ev[3]])
        i = 0
        for ev, self_ns in sorted(self_times(clipped),
                                  key=lambda pair: pair[0][1]):
            # the module the operation ran in: the one whose run holds
            # the operation's start
            while i + 1 < len(modules) and modules[i + 1][1] <= ev[1]:
                i += 1
            m = modules[i] if modules else None
            module = (trace_reduce.short(m[0])
                      if m and m[1] <= ev[1] < m[1] + m[2] else UNSCOPED)
            scope = scope_of(ev[3], names)
            tables = [out["self_s"]]
            if ev[3].startswith(INHERITED) and scope:
                tables.append(out["inherited_s"])
            for t in tables:
                table = t.setdefault(module, {})
                table[scope] = table.get(scope, 0.0) + self_ns / 1e9 / n
            if RECOMPUTE in ev[3]:
                out["recompute_s"][module] = (
                    out["recompute_s"].get(module, 0.0) + self_ns / 1e9 / n)
    return out


def for_run(ctx):
    """The reduction of the run in progress (one load of the xplane and
    one reduction per run, whatever the number of readers), or ``None``
    when no trace is to be found."""
    path = newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if _cache.get("key") != key:
        _cache.clear()
        _cache.update(key=key, reduced=reduce(
            load_scoped(path), NAMES, ctx.get("chips", 1)))
    return _cache["reduced"]


def module_table(red, pattern):
    """(self seconds by scope summed over the modules whose short name
    holds ``pattern``, their runs in the window), or ``(None, 0)`` when no
    such module ran or none of its operations carries a name."""
    if red is None:
        return None, 0.0
    table, runs = {}, 0.0
    for module, scopes in red["self_s"].items():
        if pattern in module:
            for k, v in scopes.items():
                table[k] = table.get(k, 0.0) + v
    for module, r in red["module_runs"].items():
        if pattern in module:
            runs += r
    if not runs or not any(k != UNSCOPED for k in table):
        return None, 0.0
    return table, runs


def ms_per_run(ctx, pattern, scopes):
    """Device self milliseconds under ``scopes`` per run of the modules
    matching ``pattern``: the per-run readers' one line."""
    table, runs = module_table(for_run(ctx), pattern)
    if table is None:
        return None
    t = sum(table.get(s, 0.0) for s in scopes)
    return 1e3 * t / runs if t else None


def coverage_pct(ctx):
    """Share of the window's device self time that falls under one of
    ``NAMES``."""
    red = for_run(ctx)
    if red is None:
        return None
    total = sum(v for t in red["self_s"].values() for v in t.values())
    named = sum(v for t in red["self_s"].values() for k, v in t.items()
                if k != UNSCOPED)
    return 100.0 * named / total if total and named else None


def recompute_ms_per_run(ctx, pattern):
    """Device self milliseconds of the operations run AGAIN inside the
    backward (``rematted_computation`` in their path) per run of the
    modules matching ``pattern``."""
    red = for_run(ctx)
    table, runs = module_table(red, pattern)
    if table is None:
        return None
    t = sum(v for m, v in red["recompute_s"].items() if pattern in m)
    return 1e3 * t / runs if t else None


def host_own_pct(ctx):
    """Share of the traced window the engine thread spends inside
    ``serving.step`` or ``serving.submit`` and not inside
    ``serving.drain.wait``."""
    red = for_run(ctx)
    if red is None or not red["host_own_s"] or not red["window_s"]:
        return None
    return 100.0 * red["host_own_s"] / red["window_s"]


def ttft_legs(record):
    """Per finished request of the window, the legs of its time to first
    token after it was due, in seconds, from ``Request.timeline()`` and
    the request's own stamps: ``queue`` (due -> first ``prefilling`` mark:
    admission), ``prefill`` (-> the mark of the FINAL chunk's dispatch,
    which says ``final``) and ``lag`` (-> ``t_first``: the first token
    handed to the request).  The three add up to the request's TTFT from
    due.  A program whose marks do not say ``final`` gives ``[]``."""
    legs = []
    for r, due in zip(record["requests"], record["dues"]):
        if r.status != "done" or r.t_first is None:
            continue
        marks = [m for m in r.timeline() if m["phase"] == "prefilling"]
        final = [m["t"] for m in marks if m.get("final")]
        if not marks or not final:
            continue
        legs.append({"queue": marks[0]["t"] - due,
                     "prefill": final[-1] - marks[0]["t"],
                     "lag": r.t_first - final[-1],
                     "ttft": r.t_first - due})
    return legs


def leg_p95_ms(ctx, leg):
    legs = ttft_legs(ctx["record"])
    if not legs:
        return None
    return 1e3 * harness.percentile([x[leg] for x in legs], 95)
