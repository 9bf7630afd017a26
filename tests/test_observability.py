"""Observability subsystem (paddle_tpu/observability).

Tier-1 coverage for the three parts — registry, exporter, span tracing —
plus the cross-cutting guarantees: Prometheus text-format validity for
every registered series, deterministic exporter shutdown (no leaked
thread/socket), span events nesting correctly on the profiler's timeline
(the xplane of a ``jax.profiler`` session), compile-cache hit/miss accounting, and the overhead guard — the
instrumented serving engine's token outputs are byte-identical to an
uninstrumented run.
"""
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as paddle_profiler
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import (
    MetricsExporter, MetricsRegistry, get_registry, span,
)
from paddle_tpu.serving import Request, ServingEngine

from _xplane import host_events, inside, profiled


def _tiny_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(dtype="float32")
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


# --------------------------------------------------------------- registry
class TestMetricsRegistry:
    def test_counter(self):
        reg = MetricsRegistry()
        c = reg.counter("events_total", "events")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)

    def test_labeled_children_are_independent(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total", "hits", labelnames=("kind",))
        c.labels(kind="a").inc(3)
        c.labels(kind="b").inc()
        assert c.labels(kind="a").value == 3
        assert c.labels(kind="b").value == 1
        # positional + keyword forms resolve to the same child
        assert c.labels("a") is c.labels(kind="a")
        with pytest.raises(ValueError):
            c.labels(kind="a", extra="x")
        with pytest.raises(ValueError):  # unlabeled use of a labeled family
            c.inc()

    def test_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth", "queue depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5

    def test_histogram_buckets_and_percentile(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "latency")
        vals = [0.001, 0.002, 0.004, 0.1, 0.25]
        for v in vals:
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(sum(vals))
        p50, p95 = h.percentile(50), h.percentile(95)
        assert min(vals) <= p50 <= p95 <= max(vals)
        # log2 buckets: interpolated percentile is within one 2x bucket
        assert 0.002 <= p50 <= 0.008
        assert 0.125 <= p95 <= 0.25
        # single repeated value collapses to itself
        h2 = reg.histogram("one_seconds", "one")
        for _ in range(10):
            h2.observe(1.0)
        assert h2.percentile(50) == pytest.approx(1.0)
        assert reg.histogram("empty_seconds", "e").percentile(50) is None

    def test_get_or_create_and_conflicts(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "x")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("x_total", labelnames=("k",))
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("bad name")
        with pytest.raises(ValueError, match="reserved"):
            reg.histogram("h", labelnames=("le",))

    def test_snapshot_and_json_one_line(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "c", labelnames=("k",)).labels(k="v").inc(2)
        reg.histogram("h_seconds", "h").observe(0.5)
        snap = reg.snapshot()
        assert snap["c_total"]["type"] == "counter"
        assert snap["c_total"]["series"][0] == {
            "labels": {"k": "v"}, "value": 2.0}
        assert snap["h_seconds"]["series"][0]["count"] == 1
        line = reg.to_json()
        assert "\n" not in line
        assert json.loads(line) == snap

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "a counter", ("k",)).labels(
            k='we"ird\nvalue').inc()
        reg.gauge("g", "a gauge").set(3)
        reg.histogram("h_seconds", "a histogram").observe(0.01)
        text = reg.to_prometheus()
        assert text.endswith("\n")
        _assert_prometheus_valid(text)
        # cumulative histogram series end at +Inf == count
        assert 'h_seconds_bucket{le="+Inf"} 1' in text
        assert "h_seconds_count 1" in text


_COMMENT_RE = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


def _assert_prometheus_valid(text):
    """Every line is a HELP/TYPE comment or a well-formed sample line."""
    assert text.strip(), "empty exposition"
    for line in text.strip("\n").split("\n"):
        ok = _COMMENT_RE.match(line) or _SAMPLE_RE.match(line)
        assert ok, f"invalid Prometheus exposition line: {line!r}"


# --------------------------------------------------------------- exporter
class TestExporter:
    """Satellite CI check: ephemeral-port scrape of /metrics + /healthz,
    line-syntax validation of every registered series, clean shutdown."""

    def test_scrape_and_clean_shutdown(self):
        reg = MetricsRegistry()
        reg.counter("scrape_c_total", "c", ("k",)).labels(k="v").inc(4)
        reg.gauge("scrape_g", "g").set(1.5)
        reg.histogram("scrape_h_seconds", "h").observe(0.02)
        exp = MetricsExporter(registry=reg, port=0).start()
        try:
            assert exp.running and exp.port > 0
            body = urllib.request.urlopen(
                f"{exp.url}/metrics", timeout=5).read().decode()
            _assert_prometheus_valid(body)
            for name in reg.names():  # every registered series is scraped
                assert name in body
            hz = json.loads(urllib.request.urlopen(
                f"{exp.url}/healthz", timeout=5).read().decode())
            # liveness detail reads the serving gauges; this registry has
            # no engine, so every detail field is null but present
            assert hz == {"status": "ok", "last_step_age_seconds": None,
                          "queue_depth": None, "inflight_steps": None}
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{exp.url}/nope", timeout=5)
            url, port = exp.url, exp.port
        finally:
            exp.stop()
        # deterministic shutdown: no exporter thread survives, the socket
        # no longer accepts, and the handle reports not-running
        assert not exp.running
        assert not any("paddle-tpu-metrics-exporter" in t.name
                       for t in threading.enumerate())
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(f"{url}/metrics", timeout=1)
        # idempotent stop
        exp.stop()

    def test_scrape_tracks_live_updates(self):
        reg = MetricsRegistry()
        c = reg.counter("live_total", "live")
        with MetricsExporter(registry=reg, port=0) as exp:
            c.inc()
            b1 = urllib.request.urlopen(
                f"{exp.url}/metrics", timeout=5).read().decode()
            c.inc(9)
            b2 = urllib.request.urlopen(
                f"{exp.url}/metrics", timeout=5).read().decode()
        assert "live_total 1" in b1 and "live_total 10" in b2


# -------------------------------------------------------------------- spans
class TestSpans:
    """``span`` has ONE sink: the profiler's timeline (a
    ``jax.profiler.TraceAnnotation``).  With a session on, a span is an
    event of the host plane carrying its details; with none it records
    nothing anywhere."""

    def test_span_is_an_event_of_the_profilers_timeline(self, tmp_path):
        with profiled(tmp_path):
            with span("phase.outer", step=3):
                with span("phase.inner", rid="r7", slot=1):
                    pass
        outer, inner = host_events(tmp_path, ["phase."])
        assert outer["name"] == "phase.outer" and str(outer["step"]) == "3"
        assert inner["name"] == "phase.inner"
        assert str(inner["rid"]) == "r7" and str(inner["slot"]) == "1"
        assert inside(inner, outer)

    def test_span_nests_with_its_own_name(self, tmp_path):
        with profiled(tmp_path):
            with span("phase.re"):
                with span("phase.re"):
                    pass
        a, b = host_events(tmp_path, ["phase.re"])
        assert inside(b, a)

    def test_span_decorator(self, tmp_path):
        @span("phase.fn", kind="unit")
        def f(x):
            return x + 1

        with profiled(tmp_path):
            assert f(1) == 2 and f(2) == 3
        evs = host_events(tmp_path, ["phase.fn"])
        assert len(evs) == 2 and all(e["kind"] == "unit" for e in evs)
        assert f.__name__ == "f"

    def test_span_without_a_session_writes_nowhere(self):
        """No histogram, no list: the registry gains no series and the
        ``paddle.profiler`` host tracer no event."""
        reg = get_registry()
        before = set(reg.snapshot())
        tracer = paddle_profiler.get_host_tracer()
        n0 = len(tracer.events)
        with span("phase.quiet", step=1):
            pass
        assert set(reg.snapshot()) == before
        assert reg.get("span_seconds") is None
        assert len(tracer.events) == n0

    def test_serving_spans_nest_in_the_profilers_trace(self, tmp_path):
        """Spans of a B2 serving smoke are events of the xplane:
        dispatch / drain nested inside steps, the one blocking fetch
        inside its drain, chunks inside spend_prefill."""
        model = _tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 9, 6)]
        eng = ServingEngine(model, batch_size=2, max_len=64)
        with profiled(tmp_path):
            for p, n in zip(prompts, (4, 6, 3)):
                eng.submit(Request(p, n))
            eng.run()
        by_name = {}
        for e in host_events(tmp_path, ["serving."]):
            by_name.setdefault(e["name"], []).append(e)
        steps = by_name["serving.step"]
        assert len(by_name["serving.submit"]) == 3
        for child, parent in (("serving.dispatch", "serving.step"),
                              ("serving.drain", "serving.step"),
                              ("serving.admit", "serving.step"),
                              ("serving.spend_prefill", "serving.step"),
                              ("serving.prefill_chunk",
                               "serving.spend_prefill"),
                              ("serving.drain.wait", "serving.drain"),
                              ("serving.emit", "serving.drain")):
            assert by_name.get(child), f"{child} spans missing"
            for c in by_name[child]:
                assert any(inside(c, p) for p in by_name[parent]), \
                    f"{child} at {c['start']} not nested in a {parent}"
        assert [int(s["step"]) for s in steps] == sorted(
            int(s["step"]) for s in steps)


# ------------------------------------------------- engine instrumentation
class TestServingInstrumentation:
    def test_instrumented_outputs_byte_identical(self):
        """The overhead guard (acceptance criterion): instrumentation is
        host-side bookkeeping only — token outputs are byte-identical with
        it enabled (default) vs disabled."""
        model = _tiny_model(seed=1)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 9, 6, 12)]
        new_lens = [6, 4, 8, 5]

        def run(**kw):
            eng = ServingEngine(model, batch_size=2, max_len=64, **kw)
            for p, n in zip(prompts, new_lens):
                eng.submit(Request(p, int(n)))
            return {r.rid: r for r in eng.run()}

        reg = MetricsRegistry()
        on = run(registry=reg)
        off = run(instrument=False)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(on[i].output_ids,
                                          off[i].output_ids)
        # and the instrumented run actually recorded the workload
        def val(name):
            return reg.get(name).labels(policy="continuous").value

        assert val("serving_requests_admitted_total") == len(prompts)
        assert val("serving_requests_retired_total") == len(prompts)
        assert val("serving_tokens_emitted_total") == sum(new_lens)
        assert val("serving_queue_depth") == 0
        assert val("serving_slots_occupied") == 0
        assert val("serving_slots_total") == 2
        ttft = reg.get("serving_ttft_seconds").labels(policy="continuous")
        e2e = reg.get("serving_e2e_seconds").labels(policy="continuous")
        tpot = reg.get("serving_tpot_seconds").labels(policy="continuous")
        assert ttft.count == len(prompts) and e2e.count == len(prompts)
        assert tpot.count == len(prompts)
        assert reg.get("serving_queue_wait_seconds").labels(
            policy="continuous").count == len(prompts)
        # prefill counter is bucket-labeled; total admissions match
        pre = reg.get("serving_prefill_total")
        total = sum(s["value"] for s in
                    pre._snapshot()["series"])
        assert total == len(prompts)
        _assert_prometheus_valid(reg.to_prometheus())

    def test_spec_accept_rate_recorded(self):
        model = _tiny_model(seed=3)
        rng = np.random.default_rng(3)
        prompts = [np.tile(rng.integers(0, 256, (4,)), r) for r in (3, 4)]
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, mode="spec",
                            spec_k=4, registry=reg)
        for p in prompts:
            eng.submit(Request(p, 8))
        eng.run()
        drafted = reg.get("serving_spec_drafted_total").labels(
            policy="continuous").value
        accepted = reg.get("serving_spec_accepted_total").labels(
            policy="continuous").value
        rate = reg.get("serving_spec_accept_rate").labels(
            policy="continuous", source="prompt_lookup").value
        assert drafted > 0 and 0 <= accepted <= drafted
        assert rate == pytest.approx(accepted / drafted)


# ------------------------------------------------------ compile caches
class TestCompileCacheMetrics:
    @staticmethod
    def _val(name, **labels):
        fam = get_registry().get(name)
        if fam is None:
            return 0.0
        return fam.labels(**labels).value

    def test_decode_compile_hit_miss(self):
        from paddle_tpu.models.llama_decode import decode_greedy
        model = _tiny_model(seed=4)
        ids = paddle.to_tensor(np.arange(1, 6)[None], dtype="int64")
        lab = dict(cache="llama_decode", program="decode")
        m0 = self._val("compile_cache_misses_total", **lab)
        h0 = self._val("compile_cache_hits_total", **lab)
        # max_len=37 is a unique static lmax in this process: first call
        # must trace+compile, the second must hit the jit cache
        np.asarray(decode_greedy(model, ids, max_new_tokens=3, max_len=37))
        m1 = self._val("compile_cache_misses_total", **lab)
        assert m1 == m0 + 1
        sec = get_registry().get("compile_seconds").labels(**lab)
        assert sec.count >= m1 - m0
        np.asarray(decode_greedy(model, ids, max_new_tokens=3, max_len=37))
        assert self._val("compile_cache_misses_total", **lab) == m1
        assert self._val("compile_cache_hits_total", **lab) == h0 + 1
        # the host-side param-pytree cache: 1 miss then 1 hit
        plab = dict(cache="llama_decode", program="decode_params")
        assert self._val("compile_cache_hits_total", **plab) >= 1

    def test_train_step_metrics(self, tmp_path):
        from paddle_tpu import nn
        from paddle_tpu.static.functionalize import build_train_step
        lab = dict(cache="functionalize", program="train_step")
        reg = get_registry()
        s0 = reg.get("train_steps_total").value
        m0 = self._val("compile_cache_misses_total", **lab)
        h0 = self._val("compile_cache_hits_total", **lab)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 4))
        opt = paddle.optimizer.SGD(learning_rate=1e-3,
                                   parameters=net.parameters())
        step = build_train_step(net, nn.MSELoss(), opt)
        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        with profiled(tmp_path):
            step(x, y)
            step(x, y)
        assert reg.get("train_steps_total").value == s0 + 2
        assert self._val("compile_cache_misses_total", **lab) == m0 + 1
        assert self._val("compile_cache_hits_total", **lab) == h0 + 1
        # the dispatch is asynchronous: no series times it (the step's
        # time is the device's); each is a train.step span of the
        # profiler's timeline, carrying its step number
        assert reg.get("train_step_dispatch_seconds") is None
        spans = host_events(tmp_path, ["train.step"])
        assert [int(e["step"]) for e in spans] == [1, 2]


# ------------------------------------------------- analysis.runtime guard
class TestRetraceGuardIntegration:
    """analysis.assert_no_retrace over the REAL monitors: the no-args form
    watches every live CompileCacheMonitor through the weak registry in
    observability.compilecache, so a steady-state train loop passes and a
    shape-churn step is pinned to the exact cache/program that retraced."""

    def _step(self):
        from paddle_tpu import nn
        from paddle_tpu.static.functionalize import build_train_step
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 4))
        opt = paddle.optimizer.SGD(learning_rate=1e-3,
                                   parameters=net.parameters())
        return build_train_step(net, nn.MSELoss(), opt)

    def test_steady_state_train_loop_is_retrace_free(self):
        from paddle_tpu.analysis import assert_no_retrace

        step = self._step()
        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step(x, y)  # warmup: the one legitimate trace
        with assert_no_retrace():
            for _ in range(3):
                step(x, y)

    def test_ragged_batch_retrace_is_caught(self):
        from paddle_tpu.analysis import RetraceError, assert_no_retrace

        step = self._step()
        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step(x, y)
        with pytest.raises(RetraceError, match="functionalize/train_step"):
            with assert_no_retrace():
                # a ragged final batch: the classic silent recompile
                step(x[:1], y[:1])
