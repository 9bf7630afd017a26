"""Profiler (reference python/paddle/profiler/profiler.py:358).

TPU-native: host events are recorded by an in-process tracer (the HostTracer
analog of paddle/fluid/platform/profiler/host_tracer.cc); device activity is
delegated to jax.profiler (XLA's TPU tracer = the CustomTracer plugin hooks of
device_ext.h:666).  Chrome-trace export + summary tables kept API-compatible."""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from enum import Enum


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


class _HostTracer:
    """Process-wide host event sink."""

    def __init__(self):
        self.events = []
        self.enabled = False
        self._lock = threading.Lock()

    def add(self, name, start_ns, end_ns, event_type="UserDefined"):
        if not self.enabled:
            return
        with self._lock:
            self.events.append({
                "name": name, "ts": start_ns / 1000.0,
                "dur": (end_ns - start_ns) / 1000.0,
                "ph": "X", "pid": os.getpid(),
                "tid": threading.get_ident() % 2**31,
                "cat": event_type,
            })


_tracer = _HostTracer()


def get_host_tracer():
    """The process-wide host event sink of user ``RecordEvent`` scopes
    (chrome-trace export, ``perf_counter`` clock).  Framework spans
    (``paddle_tpu.observability.trace.span``) do NOT come here: they are
    events of the ``jax.profiler`` timeline, beside the device lines."""
    return _tracer


class RecordEvent:
    """User-scope event (reference python/paddle/profiler/utils.py RecordEvent)."""

    def __init__(self, name, event_type="UserDefined"):
        self.name = name
        self.event_type = event_type
        self._begin = None

    def begin(self):
        self._begin = time.perf_counter_ns()

    def end(self):
        if self._begin is not None:
            _tracer.add(self.name, self._begin, time.perf_counter_ns(), self.event_type)
            self._begin = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """reference profiler.py make_scheduler: step → ProfilerState fn."""

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        period = closed + ready + record
        if repeat and step >= repeat * period:
            return ProfilerState.CLOSED
        pos = step % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step):
    return ProfilerState.RECORD


def _export_path(dir_name, worker_name, suffix):
    """Collision-proof export path: the second-resolution timestamp alone
    silently overwrote when two exports landed in the same second (two
    profiler cycles, or two processes sharing a dir without worker_name) —
    a pid + process-monotonic sequence number disambiguates both."""
    name = worker_name or f"host_{os.getpid()}"
    seq = next(_EXPORT_SEQ)
    return os.path.join(
        dir_name,
        f"{name}_time_{int(time.time())}_{os.getpid()}_{seq}{suffix}")


_EXPORT_SEQ = itertools.count()


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready callback factory (reference profiler.py)."""

    def handle(prof):
        os.makedirs(dir_name, exist_ok=True)
        path = _export_path(dir_name, worker_name, ".paddle_trace.json")
        prof.export(path, "json")
        return path

    return handle


def export_protobuf(dir_name, worker_name=None):
    def handle(prof):
        os.makedirs(dir_name, exist_ok=True)
        path = _export_path(dir_name, worker_name, ".pb")
        prof.export(path, "pb")
        return path

    return handle


def load_profiler_result(filename):
    with open(filename) as f:
        return json.load(f)


class Profiler:
    """reference profiler.py:358 Profiler: targets/scheduler/on_trace_ready;
    start/stop/step; summary."""

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU]
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(closed=start, ready=0, record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_trace_dir = None
        self._step_info = {}
        self._benchmark = _Benchmark()

    # ------------------------------------------------------------------ control
    def start(self):
        self.current_state = self._scheduler(self.step_num)
        _tracer.enabled = self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        ) and not self.timer_only
        _tracer.events.clear()
        self._benchmark.begin()
        if ProfilerTarget.TPU in self.targets and not self.timer_only:
            try:
                import jax

                self._device_trace_dir = os.path.join("/tmp", f"paddle_tpu_trace_{os.getpid()}")
                jax.profiler.start_trace(self._device_trace_dir)
            except Exception:
                self._device_trace_dir = None

    def stop(self):
        _tracer.enabled = False
        self._benchmark.end()
        if self._device_trace_dir is not None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_trace_dir = None
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        self._benchmark.step(num_samples)
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        _tracer.enabled = self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        ) and not self.timer_only

    def step_info(self, unit=None):
        return self._benchmark.step_info(unit)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # ------------------------------------------------------------------- export
    def export(self, path, format="json"):
        data = {"traceEvents": list(_tracer.events),
                "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(data, f)
        return path

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit='ms', views=None):
        agg = {}
        for e in _tracer.events:
            st = agg.setdefault(e["name"], [0, 0.0, 0.0, float("inf")])
            st[0] += 1
            st[1] += e["dur"]
            st[2] = max(st[2], e["dur"])
            st[3] = min(st[3], e["dur"])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(us)':>14}{'Max(us)':>12}{'Min(us)':>12}"]
        order = sorted(agg.items(), key=lambda kv: -kv[1][1])
        for name, (calls, total, mx, mn) in order:
            lines.append(f"{name[:40]:<40}{calls:>8}{total:>14.2f}{mx:>12.2f}{mn if calls else 0:>12.2f}")
        table = "\n".join(lines)
        print(table)
        return table


class _Benchmark:
    """Throughput tracker (reference python/paddle/profiler/timer.py)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self._last = None
        self.samples = 0
        self.steps = 0
        self.step_times = []

    def begin(self):
        self._t0 = self._last = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self.step_times.append(now - self._last)
        self._last = now
        self.steps += 1
        if num_samples:
            self.samples += num_samples

    def end(self):
        pass

    def step_info(self, unit=None):
        if not self.step_times:
            return "no steps recorded"
        import numpy as np

        arr = np.asarray(self.step_times)
        total = arr.sum()
        ips = (self.samples / total) if (self.samples and total > 0) else (len(arr) / total)
        u = unit or ("samples/sec" if self.samples else "steps/sec")
        return (f"avg: {arr.mean()*1000:.3f} ms, max: {arr.max()*1000:.3f} ms, "
                f"min: {arr.min()*1000:.3f} ms, ips: {ips:.2f} {u}")


def benchmark():
    return _BENCHMARK


_BENCHMARK = _Benchmark()
