"""Serving reliability layer (paddle_tpu/serving): deadlines,
cancellation, load shedding, poison-request quarantine, dispatch retry,
and the deterministic fault-injection harness.

The load-bearing property throughout: every reliability path retires
through the SAME write-drop parking the scheduler already uses, so the
clean path is a strict no-op (byte-identical outputs, zero retraces) and
a faulted run's surviving requests stay byte-identical to an unfaulted
run of the same workload.
"""
import numpy as np
import pytest

from paddle_tpu.serving import (
    EngineOverloaded, FaultPlan, InjectedDispatchError, Request,
    ServingEngine,
)
from tests.test_serving import _run, _tiny_model

_PROMPTS = [np.arange(1, 7), np.arange(2, 11)]
_NEW = [8, 6]


def _clean_outputs(model, **kw):
    outs = _run(model, _PROMPTS, _NEW, batch_size=2, max_len=64, **kw)
    return {rid: list(r.output_ids) for rid, r in outs.items()}


class TestCleanPathNoOp:
    def test_defaults_leave_statuses_done_and_counters_zero(self):
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        for p, n in zip(_PROMPTS, _NEW):
            eng.submit(Request(p, n))
        statuses = eng.drain()
        assert statuses == {0: "done", 1: "done"}
        lbl = dict(policy="continuous")
        for series in ("serving_requests_shed_total",
                       "serving_requests_timed_out_total",
                       "serving_requests_cancelled_total",
                       "serving_requests_poisoned_total",
                       "serving_dispatch_retries_total"):
            assert reg.get(series).labels(**lbl).value == 0

    def test_counters_pre_registered_at_construction(self):
        """Satellite: a Prometheus scrape sees every reliability series
        zero-valued BEFORE the first shed/timeout/cancel/poison — and the
        labeled stream_cb family exports its error="Exception" child."""
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        reg = MetricsRegistry()
        ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        lbl = dict(policy="continuous")
        for series in ("serving_requests_shed_total",
                       "serving_requests_timed_out_total",
                       "serving_requests_cancelled_total",
                       "serving_requests_poisoned_total",
                       "serving_dispatch_retries_total"):
            assert reg.get(series).labels(**lbl).value == 0
        errs = reg.get("serving_stream_cb_errors_total")
        assert errs.labels(policy="continuous",
                           error="Exception").value == 0


class TestDispatchRetry:
    def test_retry_preserves_byte_identity(self):
        """Tentpole acceptance: transient dispatch failures at several
        steps are retried and the run's outputs are byte-identical to an
        unfaulted run — the fault fires BEFORE the real dispatch, so the
        retry re-issues an identical program."""
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        ref = _clean_outputs(model)
        reg = MetricsRegistry()
        plan = FaultPlan(dispatch_error_steps={1, 3})
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg,
                            retry_backoff=1e-4, faults=plan)
        reqs = [eng.submit(Request(p, n))
                for p, n in zip(_PROMPTS, _NEW)]
        statuses = eng.drain()
        assert statuses == {0: "done", 1: "done"}
        for r in reqs:
            assert list(r.output_ids) == ref[r.rid]
        assert plan.stats["dispatch_errors"] == 2
        assert reg.get("serving_dispatch_retries_total").labels(
            policy="continuous").value == 2

    def test_retry_exhaustion_reraises(self):
        model = _tiny_model()
        plan = FaultPlan(dispatch_error_steps={1},
                         dispatch_error_attempts=10)
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            retry_attempts=2, retry_backoff=1e-4,
                            faults=plan)
        eng.submit(Request(_PROMPTS[0], 6))
        with pytest.raises(InjectedDispatchError):
            eng.run()
        # exactly retry_attempts errors were consumed before giving up
        assert plan.stats["dispatch_errors"] == 2

    def test_rate_draws_are_seed_deterministic(self):
        """Two runs of the same workload against same-seed plans inject
        identically and produce identical outputs."""
        stats, outs = [], []
        for _ in range(2):
            model = _tiny_model()
            plan = FaultPlan(seed=3, dispatch_error_rate=0.5)
            eng = ServingEngine(model, batch_size=2, max_len=64,
                                retry_backoff=1e-4, faults=plan)
            rs = [eng.submit(Request(p, n))
                  for p, n in zip(_PROMPTS, _NEW)]
            eng.run()
            stats.append(dict(plan.stats))
            outs.append([list(r.output_ids) for r in rs])
        assert stats[0] == stats[1]
        assert stats[0]["dispatch_errors"] > 0
        assert outs[0] == outs[1]


class TestPoisonQuarantine:
    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_poisoned_request_quarantined_cohabitant_exact(self, mode):
        """Tentpole acceptance: a NaN payload in one slot retires that
        request with status "poisoned"; its cohabitant's output stays
        byte-identical to an unfaulted run, and the freed slot re-admits
        a queued request that completes normally."""
        model = _tiny_model()
        kw = dict(mode=mode)
        if mode == "spec":
            kw["spec_k"] = 4
        ref = _clean_outputs(model, **kw)
        plan = FaultPlan(poison={0: 2})
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            faults=plan, **kw)
        r0 = eng.submit(Request(_PROMPTS[0], _NEW[0]))
        r1 = eng.submit(Request(_PROMPTS[1], _NEW[1]))
        # a third request queued behind the full batch proves the
        # quarantined slot frees for re-admission
        r2 = eng.submit(Request(np.arange(3, 9), 4))
        statuses = eng.drain()
        assert statuses[0] == "poisoned" and plan.stats["poisoned"] == 1
        assert statuses[1] == "done" and statuses[2] == "done"
        assert list(r1.output_ids) == ref[1]
        assert len(r2.output_ids) == 4
        # the poisoned request keeps its pre-fault partial output as a
        # prefix of the clean run (never garbage tokens)
        assert list(r0.output_ids) == ref[0][:len(r0.output_ids)]

    def test_poison_counter_and_no_emit_after_quarantine(self):
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        reg = MetricsRegistry()
        plan = FaultPlan(poison={0: 1})
        eng = ServingEngine(model, batch_size=1, max_len=64, registry=reg,
                            faults=plan)
        r0 = eng.submit(Request(_PROMPTS[0], 10))
        statuses = eng.drain()
        assert statuses == {0: "poisoned"}
        assert len(r0.output_ids) < 10
        assert reg.get("serving_requests_poisoned_total").labels(
            policy="continuous").value == 1


class TestDeadlines:
    def test_queued_deadline_expires_before_admission(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64)
        # slot holder without a deadline; the queued request's
        # deadline_ms=0 is already past when the next step runs
        r0 = eng.submit(Request(_PROMPTS[0], 6))
        r1 = eng.submit(Request(_PROMPTS[1], 6, deadline_ms=0))
        statuses = eng.drain()
        assert statuses[r0.rid] == "done"
        assert statuses[r1.rid] == "timed_out"
        assert r1.output_ids == [] and r1.done

    def test_midflight_deadline_frees_slot_keeps_partial(self):
        import time
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        ref = _clean_outputs(model)
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        r0 = eng.submit(Request(_PROMPTS[0], _NEW[0], deadline_ms=60_000))
        r1 = eng.submit(Request(_PROMPTS[1], _NEW[1]))
        r2 = eng.submit(Request(np.arange(3, 9), 4))
        for _ in range(3):
            eng.step()
        r0._t_deadline = time.perf_counter() - 1.0   # force expiry now
        statuses = eng.drain()
        assert statuses[r0.rid] == "timed_out"
        assert statuses[r1.rid] == "done" and statuses[r2.rid] == "done"
        # partial output is a clean-run prefix; cohabitant exact
        assert list(r0.output_ids) == ref[0][:len(r0.output_ids)]
        assert list(r1.output_ids) == ref[1]
        assert reg.get("serving_requests_timed_out_total").labels(
            policy="continuous").value == 1


class TestCancellation:
    def test_cancel_queued_and_unknown(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64)
        eng.submit(Request(_PROMPTS[0], 4, rid="res"))
        q = eng.submit(Request(_PROMPTS[1], 4, rid="waiting"))
        assert eng.cancel("waiting") is True
        assert q.done and q.status == "cancelled" and q.output_ids == []
        assert eng.cancel("nope") is False
        statuses = eng.drain()
        assert statuses == {"res": "done", "waiting": "cancelled"}
        assert eng.cancel("res") is False   # already finished

    def test_cancel_mid_prefill_chunked(self):
        """A slot still spending prompt chunks (engine._pf) cancels
        cleanly: its chunk state is dropped and the slot re-admits."""
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64,
                            prefill_chunk=4, prefill_budget=1)
        long = eng.submit(Request(np.arange(1, 30), 5, rid="long"))
        nxt = eng.submit(Request(_PROMPTS[0], 4, rid="next"))
        eng.step()
        assert eng._pf, "request should still be mid-prefill"
        assert eng.cancel("long") is True
        statuses = eng.drain()
        assert statuses == {"long": "cancelled", "next": "done"}
        assert long.output_ids == [] and len(nxt.output_ids) == 4

    def test_cancel_mid_flight_cohabitant_exact(self):
        """Cancelling a decoding request — including one with tokens
        riding the inflight pipelined dispatch — keeps its cohabitant
        byte-identical and frees the slot for a queued request."""
        model = _tiny_model()
        ref = _clean_outputs(model)
        eng = ServingEngine(model, batch_size=2, max_len=64)
        r0 = eng.submit(Request(_PROMPTS[0], _NEW[0], rid="victim"))
        r1 = eng.submit(Request(_PROMPTS[1], _NEW[1], rid="bystander"))
        r2 = eng.submit(Request(np.arange(3, 9), 4, rid="readmit"))
        for _ in range(3):
            eng.step()
        assert eng.cancel("victim") is True
        statuses = eng.drain()
        assert statuses == {"victim": "cancelled", "bystander": "done",
                            "readmit": "done"}
        assert list(r1.output_ids) == ref[1]
        assert list(r0.output_ids) == ref[0][:len(r0.output_ids)]
        assert len(r2.output_ids) == 4

    def test_reliability_paths_are_retrace_free(self):
        """Acceptance: cancel, deadline expiry and poison quarantine all
        retire through write-drop parking — a warmed engine runs the
        whole reliability gauntlet with ZERO retraces."""
        import time
        from paddle_tpu.analysis import assert_no_retrace
        model = _tiny_model()
        kw = dict(batch_size=2, max_len=64)

        def gauntlet():
            eng = ServingEngine(model, faults=FaultPlan(poison={"p": 2}),
                                **kw)
            ra = eng.submit(Request(_PROMPTS[0], _NEW[0], rid="a"))
            eng.submit(Request(_PROMPTS[1], _NEW[1], rid="p"))
            eng.submit(Request(np.arange(3, 9), 4, rid="late",
                               deadline_ms=60_000))
            for _ in range(3):
                eng.step()
            eng.cancel("a")
            for r in eng._kv.reqs:
                if r is not None and r.rid == "late":
                    r._t_deadline = time.perf_counter() - 1.0
            return eng.drain(), ra

        gauntlet()                       # warmup: the legitimate traces
        with assert_no_retrace():
            statuses, ra = gauntlet()
        assert statuses["a"] == "cancelled"
        assert statuses["p"] == "poisoned"
        assert statuses["late"] in ("timed_out", "done")


class TestLoadShedding:
    def test_bounded_queue_sheds_and_recovers(self):
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=1, max_len=64,
                            max_pending=1, registry=reg)
        eng.submit(Request(_PROMPTS[0], 4))
        shed = Request(_PROMPTS[1], 4)
        with pytest.raises(EngineOverloaded):
            eng.submit(shed)
        assert shed.status == "shed" and shed.rid is None
        assert reg.get("serving_requests_shed_total").labels(
            policy="continuous").value == 1
        # once the queue drains into the slot, admission reopens
        eng.step()
        ok = eng.submit(Request(_PROMPTS[1], 4))
        statuses = eng.drain()
        assert statuses == {0: "done", ok.rid: "done"}

    def test_shed_never_consumes_engine_state(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=1, max_len=64,
                            max_pending=0)
        with pytest.raises(EngineOverloaded):
            eng.submit(Request(_PROMPTS[0], 4))
        assert not eng.has_work and eng._finished == []
        # a shed request never burned an auto rid
        eng2 = ServingEngine(model, batch_size=1, max_len=64)
        assert eng2.submit(Request(_PROMPTS[0], 4)).rid == 0

    def test_max_pending_validation(self):
        model = _tiny_model()
        with pytest.raises(ValueError, match="max_pending"):
            ServingEngine(model, batch_size=1, max_len=64, max_pending=-1)


class TestDrainClose:
    def test_close_keeps_partial_outputs_and_is_idempotent(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        r0 = eng.submit(Request(_PROMPTS[0], 20))
        q = eng.submit(Request(_PROMPTS[1], 20))
        eng.submit(Request(np.arange(3, 9), 20))
        for _ in range(4):
            eng.step()
        statuses = eng.close()
        assert not eng.has_work
        assert set(statuses.values()) == {"cancelled"}
        # the inflight dispatch drained first: the resident requests kept
        # the tokens it carried
        assert len(r0.output_ids) > 0
        assert statuses == eng.close()   # second close changes nothing

    def test_drain_returns_terminal_status_map(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        eng.submit(Request(_PROMPTS[0], 4, rid="a"))
        eng.submit(Request(_PROMPTS[1], 3, rid="b"))
        assert eng.drain() == {"a": "done", "b": "done"}
        assert not eng.has_work


class TestFaultHarness:
    def test_slow_steps_fire_and_are_counted(self):
        model = _tiny_model()
        plan = FaultPlan(slow_steps={1: 1e-4, 2: 1e-4})
        eng = ServingEngine(model, batch_size=1, max_len=64, faults=plan)
        eng.submit(Request(_PROMPTS[0], 6))
        eng.drain()
        assert plan.stats["slow_steps"] == 2

    def test_cb_crashes_counted_by_type_decode_unharmed(self):
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        ref = _clean_outputs(model)
        reg = MetricsRegistry()
        plan = FaultPlan(cb_crash_steps={1, 2})
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            registry=reg, faults=plan)
        got = []
        r0 = eng.submit(Request(_PROMPTS[0], _NEW[0],
                                stream_cb=lambda r, ids: got.extend(ids)))
        r1 = eng.submit(Request(_PROMPTS[1], _NEW[1]))
        statuses = eng.drain()
        assert statuses == {0: "done", 1: "done"}
        assert list(r0.output_ids) == ref[0]
        assert list(r1.output_ids) == ref[1]
        assert plan.stats["cb_crashes"] > 0
        errs = reg.get("serving_stream_cb_errors_total")
        assert errs.labels(policy="continuous",
                           error="InjectedStreamCbError").value \
            == plan.stats["cb_crashes"]
        # tokens emitted on non-crash steps still reached the callback
        assert 0 < len(got) < len(r0.output_ids)
