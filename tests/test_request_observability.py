"""Request-lifecycle observability (flight recorder, request timelines,
SLO tracking, /debug endpoints).

The load-bearing properties: (1) recording is pure host bookkeeping —
token outputs are BYTE-IDENTICAL recorder-on vs recorder-off across
greedy/spec, with zero retraces over a ragged mixed workload; (2) an
anomaly (timeout / poison / retry exhaustion) auto-dumps exactly one
flight-recorder snapshot that reconstructs the request's full lifecycle;
(3) the /debug/* JSON endpoints are safe to scrape from
another thread while the engine serves.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.observability import MetricsExporter, MetricsRegistry
from paddle_tpu.observability.flightrecorder import (
    FlightRecorder, RequestTrace, TERMINAL_PHASES,
)
from paddle_tpu.observability.slo import SLObjective, SLOTracker
from paddle_tpu.serving import FaultPlan, Request, ServingEngine
from tests.test_serving import _tiny_model

_PROMPTS = [np.arange(1, 7), np.arange(2, 11)]
_NEW = [8, 6]

# ragged mixed workload for the identity/retrace acceptance runs: prompt
# lengths span buckets, output lengths force mid-run retire + re-admit
_RAGGED_P = [5, 9, 6, 12, 3, 17]
_RAGGED_N = [6, 4, 8, 5, 7, 3]


def _ragged_reqs(seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, (p,)), n)
            for p, n in zip(_RAGGED_P, _RAGGED_N)]


def _run_ragged(model, **kw):
    eng = ServingEngine(model, batch_size=2, max_len=64, **kw)
    for p, n in _ragged_reqs():
        eng.submit(Request(p, int(n)))
    done = eng.run()
    return eng, {r.rid: list(r.output_ids) for r in done}


# ------------------------------------------------------------ ring buffer
class TestFlightRecorderRing:
    def test_overflow_evicts_oldest(self):
        fr = FlightRecorder(capacity=4, policy="t")
        for i in range(6):
            fr.record("dispatch", step=i)
        assert len(fr) == 4 and fr.dropped == 2
        steps = [e["step"] for e in fr.events()]
        assert steps == [2, 3, 4, 5]   # oldest two gone, order kept
        assert [e["step"] for e in fr.events(last=2)] == [4, 5]

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_jsonl_round_trip(self):
        fr = FlightRecorder(policy="continuous")
        fr.record("submit", step=0, rid=7, prompt_len=5)
        fr.record("retire", step=3, rid=7, slot=1, status="done")
        lines = fr.to_jsonl().strip().split("\n")
        evs = [json.loads(ln) for ln in lines]
        assert [e["kind"] for e in evs] == ["submit", "retire"]
        assert evs[0]["prompt_len"] == 5 and evs[0]["policy"] == "continuous"
        assert evs[1]["status"] == "done" and evs[1]["slot"] == 1
        assert evs[0]["t_ns"] <= evs[1]["t_ns"]

    def test_chrome_trace_one_track_per_rid(self):
        fr = FlightRecorder()
        fr.record("dispatch", step=0)                     # batch: track 0
        fr.record("submit", step=0, rid="a")
        fr.record("submit", step=0, rid="b")
        fr.record("retire", step=2, rid="a", status="done")
        fr.record("stall", step=1, seconds=0.25)
        tr = fr.chrome_trace()
        evs = tr["traceEvents"]
        tids = {e["args"]["rid"]: e["tid"] for e in evs
                if e["args"].get("rid") is not None}
        assert tids == {"a": 1, "b": 2}  # discovery order, stable per rid
        batch = [e for e in evs if e["args"].get("rid") is None]
        assert batch and all(e["tid"] == 0 for e in batch)
        stall = next(e for e in evs if e["name"] == "stall")
        assert stall["dur"] == pytest.approx(0.25 * 1e6)   # µs slice
        assert all(e["ph"] == "X" for e in evs)            # _HostTracer shape

    def test_auto_dump_file_hook_and_bound(self, tmp_path):
        fired = []
        fr = FlightRecorder(dump_dir=str(tmp_path), dump_last=2,
                            on_dump=fired.append)
        for i in range(5):
            fr.record("dispatch", step=i)
        rec = fr.auto_dump("poisoned")
        assert fired == ["poisoned"]
        assert [e["step"] for e in rec["events"]] == [3, 4]  # last dump_last
        with open(rec["path"], encoding="utf-8") as f:
            disk = [json.loads(ln) for ln in f]
        assert disk == rec["events"]
        for _ in range(20):                                  # bounded memory
            fr.auto_dump("timed_out")
        assert len(fr.dumps) == 16


# ------------------------------------------------------- request timelines
class TestRequestTimeline:
    def test_lifecycle_phases_ordered(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        rs = [eng.submit(Request(p, n))
              for p, n in zip(_PROMPTS, _NEW)]
        eng.run()
        for r in rs:
            tl = r.timeline()
            phases = [e["phase"] for e in tl]
            assert phases[0] == "queued"
            assert "prefilling" in phases and "decoding" in phases
            assert phases[-1] == "done"
            # strictly ordered: queued -> prefilling -> decoding -> done
            assert phases.index("prefilling") < phases.index("decoding")
            ts = [e["t"] for e in tl]
            assert ts == sorted(ts)

    def test_timeline_empty_before_submit(self):
        r = Request(_PROMPTS[0], 4)
        assert r.timeline() == []

    def test_chunked_prefill_marks_chunks(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64,
                            prefill_chunk=4, prefill_budget=1)
        r = eng.submit(Request(np.arange(1, 30), 3))
        eng.run()
        chunks = [e["chunk"] for e in r.timeline()
                  if e["phase"] == "prefilling" and "chunk" in e]
        assert chunks == sorted(chunks) and len(chunks) >= 2

    def test_recorder_off_disables_timelines(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            recorder=False)
        r = eng.submit(Request(_PROMPTS[0], 4))
        eng.run()
        assert eng.recorder is None and r.timeline() == []

    def test_phase_histograms_populated(self):
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        for p, n in zip(_PROMPTS, _NEW):
            eng.submit(Request(p, n))
        eng.run()
        for series in ("serving_queue_seconds", "serving_prefill_seconds",
                       "serving_decode_seconds"):
            h = reg.get(series).labels(policy="continuous")
            assert h.count == len(_PROMPTS), series

    def test_durations_cover_reached_legs_only(self):
        tr = RequestTrace("x")
        tr.mark("queued")
        tr.mark("timed_out")           # expired while still queued
        d = tr.durations()
        assert set(d) == {"queue"} and d["queue"] >= 0.0
        assert tr.phase == "timed_out" and "timed_out" in TERMINAL_PHASES


# ------------------------------------------ identity + retrace acceptance
class TestRecorderByteIdentity:
    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_outputs_identical_recorder_on_off(self, mode):
        """Acceptance: the recorder-on engine's outputs are byte-identical
        to recorder-off across greedy/spec on a ragged mixed workload."""
        model = _tiny_model()
        kw = dict(mode=mode)
        if mode == "spec":
            kw["spec_k"] = 4
        eng_on, on = _run_ragged(model, **kw)
        _, off = _run_ragged(model, recorder=False, **kw)
        assert on == off
        # and the recorder actually saw the run: one submit per request,
        # one retire per request, dispatches in between
        kinds = [e["kind"] for e in eng_on.recorder.events()]
        assert kinds.count("submit") == len(_RAGGED_P)
        assert kinds.count("retire") == len(_RAGGED_P)
        assert "dispatch" in kinds and "drain" in kinds

    def test_recording_is_retrace_free(self):
        """Acceptance: a warmed recorder-on engine serves the ragged mixed
        workload with ZERO retraces — recording never perturbs program
        identity."""
        from paddle_tpu.analysis import assert_no_retrace
        model = _tiny_model()
        _run_ragged(model)        # warmup traces
        with assert_no_retrace():
            _run_ragged(model)


# ----------------------------------------------------- anomaly auto-dumps
class TestAnomalyAutoDump:
    def test_poison_dumps_once_and_reconstructs_lifecycle(self, tmp_path):
        """Acceptance: an injected poison produces exactly ONE auto-dump
        whose events reconstruct the victim's full lifecycle — submit,
        admit, dispatches, the poison injection, and the terminal retire."""
        model = _tiny_model()
        reg = MetricsRegistry()
        fr = FlightRecorder(dump_dir=str(tmp_path), policy="continuous")
        plan = FaultPlan(poison={0: 2})
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg,
                            recorder=fr, faults=plan)
        for p, n in zip(_PROMPTS, _NEW):
            eng.submit(Request(p, n))
        statuses = eng.drain()
        assert statuses[0] == "poisoned"
        assert [d["reason"] for d in fr.dumps] == ["poisoned"]
        assert reg.get("flight_recorder_dumps_total").labels(
            policy="continuous", reason="poisoned").value == 1
        evs = fr.dumps[0]["events"]
        mine = [e for e in evs if e["rid"] == 0]
        kinds = [e["kind"] for e in mine]
        for k in ("submit", "admit", "poison", "retire"):
            assert k in kinds, f"lifecycle missing {k}: {kinds}"
        retire = mine[-1]
        assert retire["kind"] == "retire" and retire["status"] == "poisoned"
        assert "dispatch" in [e["kind"] for e in evs]   # batch context too
        with open(fr.dumps[0]["path"], encoding="utf-8") as f:
            assert [json.loads(ln) for ln in f] == evs

    def test_timeout_dumps_once(self):
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=1, max_len=64, registry=reg)
        eng.submit(Request(_PROMPTS[0], 4))
        late = eng.submit(Request(_PROMPTS[1], 4, deadline_ms=0))
        statuses = eng.drain()
        assert statuses[late.rid] == "timed_out"
        fr = eng.recorder
        assert [d["reason"] for d in fr.dumps] == ["timed_out"]
        assert reg.get("flight_recorder_dumps_total").labels(
            policy="continuous", reason="timed_out").value == 1
        mine = [e for e in fr.dumps[0]["events"] if e["rid"] == late.rid]
        assert [e["kind"] for e in mine][-1] == "retire"
        assert mine[-1]["status"] == "timed_out"

    def test_done_and_cancel_do_not_dump(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64)
        eng.submit(Request(_PROMPTS[0], 4, rid="a"))
        q = eng.submit(Request(_PROMPTS[1], 4, rid="b"))
        eng.cancel("b")
        eng.drain()
        assert q.status == "cancelled"
        assert eng.recorder.dumps == []

    def test_retry_exhaustion_dumps(self):
        from paddle_tpu.serving import InjectedDispatchError
        model = _tiny_model()
        reg = MetricsRegistry()
        plan = FaultPlan(dispatch_error_steps={1},
                         dispatch_error_attempts=10)
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg,
                            retry_attempts=2, retry_backoff=1e-4,
                            faults=plan)
        eng.submit(Request(_PROMPTS[0], 6))
        with pytest.raises(InjectedDispatchError):
            eng.run()
        fr = eng.recorder
        assert [d["reason"] for d in fr.dumps] == ["retry_exhausted"]
        assert reg.get("flight_recorder_dumps_total").labels(
            policy="continuous", reason="retry_exhausted").value == 1
        retries = [e for e in fr.dumps[0]["events"]
                   if e["kind"] == "retry"]
        assert retries and retries[-1].get("exhausted") is True
        assert retries[-1]["error"] == "InjectedDispatchError"


# ---------------------------------------------------------- SLO tracking
class _FakeReq:
    """Minimal retired-request stand-in for SLOTracker math tests."""

    def __init__(self, ttft=None, tpot=None, latency=None, n_out=0,
                 slo_class=None):
        self.ttft = ttft
        self.tpot = tpot
        self.latency = latency
        self.output_ids = [0] * n_out
        self.slo_class = slo_class


class TestSLOTracker:
    def test_attainment_and_burn_math(self):
        trk = SLOTracker(objectives=[SLObjective("interactive", ttft=0.5,
                                                 target=0.9)], window=8)
        for _ in range(3):
            trk.observe(_FakeReq(ttft=0.1))
        trk.observe(_FakeReq(ttft=2.0))            # one miss
        assert trk.attainment("interactive") == pytest.approx(0.75)
        # burn = (1 - 0.75) / (1 - 0.9) = 2.5x the error budget
        assert trk.burn_rate("interactive") == pytest.approx(2.5)
        snap = trk.snapshot()["classes"]["interactive"]
        assert snap["window_requests"] == 4 and snap["good"] == 3
        assert snap["burn_rate"] == pytest.approx(2.5)

    def test_window_slides(self):
        trk = SLOTracker(objectives=[SLObjective("i", ttft=0.5)], window=2)
        trk.observe(_FakeReq(ttft=9.0, slo_class="i"))    # bad
        trk.observe(_FakeReq(ttft=0.1, slo_class="i"))
        trk.observe(_FakeReq(ttft=0.1, slo_class="i"))    # evicts the bad
        assert trk.attainment("i") == 1.0

    def test_no_first_token_fails_latency_objectives(self):
        obj = SLObjective("i", ttft=10.0)
        assert obj.met_by(_FakeReq(ttft=None)) is False
        thr = SLObjective("b", min_tok_per_s=1.0)
        assert thr.met_by(_FakeReq(latency=2.0, n_out=10)) is True
        assert thr.met_by(_FakeReq(latency=None, n_out=10)) is False

    def test_unknown_class_tracked_trivially_good(self):
        trk = SLOTracker(window=4)
        assert trk.observe(_FakeReq(slo_class="typo")) is True
        assert trk.attainment("typo") == 1.0
        assert "typo" in trk.snapshot()["classes"]

    def test_empty_window_attains(self):
        trk = SLOTracker()
        assert trk.attainment("interactive") == 1.0
        assert trk.burn_rate("interactive") == 0.0

    def test_engine_feeds_slo_and_gauges(self):
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        eng.submit(Request(_PROMPTS[0], _NEW[0]))             # default class
        eng.submit(Request(_PROMPTS[1], _NEW[1], slo_class="batch"))
        eng.drain()
        snap = eng.slo_snapshot()["classes"]
        assert snap["interactive"]["window_requests"] == 1
        assert snap["batch"]["window_requests"] == 1
        g = reg.get("serving_slo_window_requests")
        assert g.labels(policy="continuous", slo_class="batch").value == 1


# -------------------------------------------- /debug + /healthz endpoints
def _get_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "application/json"
        return json.loads(resp.read().decode())


class TestDebugEndpoints:
    def test_preregistered_series_on_first_scrape(self):
        """A scrape BEFORE any traffic already shows the full new series
        set: phase histograms, every dumps-counter reason child, and the
        SLO gauges for every configured class."""
        model = _tiny_model()
        reg = MetricsRegistry()
        ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        for series in ("serving_queue_seconds", "serving_prefill_seconds",
                       "serving_decode_seconds"):
            assert reg.get(series).labels(policy="continuous").count == 0
        dumps = reg.get("flight_recorder_dumps_total")
        for reason in ("timed_out", "poisoned", "retry_exhausted"):
            assert dumps.labels(policy="continuous",
                                reason=reason).value == 0
        att = reg.get("serving_slo_attainment")
        for cls in ("interactive", "batch"):
            assert att.labels(policy="continuous",
                              slo_class=cls).value == 1.0
        assert reg.get("serving_last_step_unixtime").labels(
            policy="continuous").value == 0

    def test_live_scrape_during_serving_run(self):
        """Acceptance: /debug/{requests,flightrecorder,slo} and /healthz
        serve valid JSON while a B=2 engine is mid-run, scraped from
        another thread."""
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        for p, n in _ragged_reqs():
            eng.submit(Request(p, int(n)))
        errors = []

        def serve():
            try:
                eng.run()
            except Exception as e:  # surfaced by the main thread's assert
                errors.append(e)

        with MetricsExporter(registry=reg,
                             debug_sources=eng.debug_sources()) as exp:
            t = threading.Thread(target=serve)
            t.start()
            saw_live = False
            try:
                while t.is_alive():
                    reqs = _get_json(f"{exp.url}/debug/requests")
                    assert {"n_tracked", "requests"} <= set(reqs)
                    rec = _get_json(f"{exp.url}/debug/flightrecorder")
                    assert rec["enabled"] and rec["capacity"] > 0
                    slo = _get_json(f"{exp.url}/debug/slo")
                    assert "classes" in slo
                    hz = _get_json(f"{exp.url}/healthz")
                    assert hz["status"] == "ok"
                    if hz["last_step_age_seconds"] is not None:
                        saw_live = True
                        assert hz["last_step_age_seconds"] < 60
                        assert hz["queue_depth"] is not None
                        assert hz["inflight_steps"] is not None
                    time.sleep(0.01)
            finally:
                t.join(timeout=60)
            assert not errors and not eng.has_work
            assert saw_live, "never scraped a live step stamp mid-run"
            # post-run: every request visible with a terminal phase, and
            # each payload survives a strict JSON round-trip
            reqs = _get_json(f"{exp.url}/debug/requests")
            assert reqs["n_tracked"] == len(_RAGGED_P)
            assert all(r["phase"] == "done" for r in reqs["requests"])
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{exp.url}/debug/nope", timeout=5)

    def test_concurrent_scrapes_are_thread_safe(self):
        """Several scrape threads hammer the snapshot providers directly
        (no HTTP in the way) while the engine serves — no exceptions, no
        torn state."""
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        for p, n in _ragged_reqs():
            eng.submit(Request(p, int(n)))
        stop = threading.Event()
        errors = []

        def scrape():
            srcs = eng.debug_sources()
            while not stop.is_set():
                try:
                    for fn in srcs.values():
                        json.dumps(fn(), default=str)
                except Exception as e:
                    errors.append(e)
                    return

        threads = [threading.Thread(target=scrape) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            eng.run()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert errors == []

    def test_broken_debug_source_returns_500_not_crash(self):
        reg = MetricsRegistry()
        boom = {"boom": lambda: (_ for _ in ()).throw(RuntimeError("x"))}
        with MetricsExporter(registry=reg, debug_sources=boom) as exp:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{exp.url}/debug/boom", timeout=5)
            assert ei.value.code == 500
            body = json.loads(ei.value.read().decode())
            assert body["error"] == "RuntimeError"
            # the server thread survives the broken provider
            with urllib.request.urlopen(f"{exp.url}/healthz",
                                        timeout=5) as r:
                assert r.status == 200

    def test_debug_source_validation(self):
        exp = MetricsExporter(registry=MetricsRegistry())
        with pytest.raises(ValueError):
            exp.add_debug_source("a/b", dict)
        with pytest.raises(ValueError):
            exp.add_debug_source("", dict)
        with pytest.raises(TypeError):
            exp.add_debug_source("x", 42)
