"""Continuous-batching serving engine (paddle_tpu/serving).

The load-bearing property on the CPU mesh at f32: iteration-level
scheduling — retiring finished slots and admitting new prompts into them
between compiled steps — leaves every other slot's greedy continuation
BYTE-IDENTICAL to an uninterrupted run, and every request's output
byte-identical to a standalone ``decode_greedy`` of its own prompt.
"""
import functools

import numpy as np
import pytest

import _wide_runs as W
import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama_decode import decode_greedy
from paddle_tpu.serving import Request, ServingEngine


def _tiny_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(dtype="float32")
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _greedy_ref(model, prompt, n, max_len):
    """``decode_greedy`` of one prompt alone: the independent reference."""
    return np.asarray(decode_greedy(
        model, paddle.to_tensor(prompt[None], dtype="int64"),
        max_new_tokens=n, max_len=max_len))[0].tolist()


def _run(model, prompts, new_lens, **kw):
    eng = ServingEngine(model, **kw)
    for p, n in zip(prompts, new_lens):
        eng.submit(Request(p, int(n)))
    done = eng.run()
    assert not eng.has_work
    return {r.rid: r for r in done}


class TestServingSmoke:
    """Fast tier-1 smoke: B2, 4 tiny requests through the full scheduler
    (two fit at once, two admitted into retired slots)."""

    def test_b2_four_requests_match_decode_greedy(self):
        model = _tiny_model()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 9, 6, 12)]
        new_lens = [6, 4, 8, 5]
        outs = _run(model, prompts, new_lens, batch_size=2, max_len=64)
        for i, (p, n) in enumerate(zip(prompts, new_lens)):
            r = outs[i]
            assert list(r.output_ids) == _greedy_ref(model, p, n, 64)
            assert r.done and r.t_done >= r.t_first >= r.t_submit

    def test_streaming_and_detokenizer(self):
        model = _tiny_model()
        got = []
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            detokenizer=lambda ids: " ".join(map(str, ids)))
        r = eng.submit(Request(np.arange(1, 6), 5,
                               stream_cb=lambda r, ids: got.extend(ids)))
        eng.run()
        assert got == r.output_ids and len(got) == 5
        assert r.text == " ".join(map(str, r.output_ids))

    def test_submit_validation(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=32)
        with pytest.raises(ValueError, match="cache rows"):
            eng.submit(Request(np.arange(16), 32))
        with pytest.raises(ValueError, match="cache rows"):
            eng.submit(Request(np.arange(40), 4))
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request(np.arange(4), 0)
        with pytest.raises(ValueError):
            ServingEngine(model, mode="beam")
        with pytest.raises(ValueError, match="only prefill"):
            ServingEngine(model, prefill_chunk=None)
        with pytest.raises(ValueError, match="only prefill"):
            ServingEngine(model, prefill_chunk=0)

    @pytest.mark.parametrize("removed", [
        dict(policy="fifo"), dict(policy="continuous"),
        dict(pipeline=False), dict(prompt_buckets=(8, 16))])
    def test_removed_options_are_rejected(self, removed):
        """One prefill, one policy, one step loop: the keywords that used
        to select another are not accepted."""
        with pytest.raises(TypeError, match="unexpected keyword"):
            ServingEngine(_tiny_model(), **removed)


class TestAdmissionInvariance:
    """The acceptance property: writing a new prompt into a retired slot
    leaves every other slot's greedy continuation byte-identical to an
    uninterrupted run (CPU mesh, f32)."""

    def test_admission_leaves_other_slots_byte_identical(self):
        model = _tiny_model()
        rng = np.random.default_rng(2)
        # slot 0's request retires after 3 tokens; r1/r2 keep decoding
        prompts = [rng.integers(0, 256, (p,)) for p in (6, 10, 8)]
        late = rng.integers(0, 256, (7,))

        kw = dict(batch_size=3, max_len=64, sync_every=1)
        # run A: r3 queued -> admitted into r0's slot mid-flight
        a = _run(model, prompts + [late], [3, 20, 20, 10], **kw)
        # run B: uninterrupted — no admission ever happens
        b = _run(model, prompts, [3, 20, 20], **kw)
        for i in (1, 2):
            np.testing.assert_array_equal(a[i].output_ids, b[i].output_ids)
        # and the admitted request is itself byte-identical to a fresh run
        c = _run(model, [late], [10], **kw)
        np.testing.assert_array_equal(a[3].output_ids, c[0].output_ids)

    def test_spec_admission_matches_greedy(self):
        """Speculative serving composes with mixed-length slots and
        admission: lossless vs the greedy engine on the same workload."""
        model = _tiny_model()
        rng = np.random.default_rng(3)
        # repetitive prompts = the lookup-friendly regime (bonus path runs)
        prompts = [np.tile(rng.integers(0, 256, (4,)), r)
                   for r in (2, 3, 2, 4, 3)]
        new_lens = [10, 16, 8, 12, 14]
        kw = dict(batch_size=3, max_len=64)
        g = _run(model, prompts, new_lens, mode="greedy", **kw)
        s = _run(model, prompts, new_lens, mode="spec", spec_k=4, **kw)
        for i in g:
            np.testing.assert_array_equal(s[i].output_ids, g[i].output_ids)


class TestRequestTiming:
    """ttft / tpot derived properties: None until their stamps exist, then
    consistent with the recorded perf_counter stamps."""

    def test_properties_none_until_available(self):
        r = Request(np.arange(1, 5), 8)
        assert r.ttft is None and r.tpot is None and r.latency is None
        r.t_submit = 10.0
        assert r.ttft is None  # submitted but no first token yet
        r.t_first = 10.25
        assert r.ttft == pytest.approx(0.25)
        assert r.tpot is None  # not done yet

    def test_tpot_excludes_first_token(self):
        r = Request(np.arange(1, 5), 8)
        r.t_submit, r.t_first, r.t_done = 1.0, 2.0, 5.0
        r.output_ids = [7, 8, 9, 10]  # 3 tokens after the first, 3 seconds
        assert r.tpot == pytest.approx(1.0)
        assert r.latency == pytest.approx(4.0)
        # single-token output: divisor clamps to 1, never div-by-zero
        r.output_ids = [7]
        assert r.tpot == pytest.approx(3.0)

    def test_live_requests_get_monotone_stamps(self):
        model = _tiny_model()
        outs = _run(model, [np.arange(1, 7), np.arange(2, 11)], [5, 4],
                    batch_size=1, max_len=64)
        for r in outs.values():
            assert r.ttft is not None and r.ttft >= 0
            assert r.tpot is not None and r.tpot >= 0
            assert r.latency >= r.ttft

    def test_crashing_stream_cb_does_not_kill_scheduler(self):
        """Satellite: a raising stream_cb is swallowed (and counted) — the
        batch keeps decoding and every request still completes exactly."""
        from paddle_tpu.observability import MetricsRegistry
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)

        def boom(r, ids):
            raise RuntimeError("user callback bug")

        prompts = [np.arange(1, 6), np.arange(3, 12)]
        r0 = eng.submit(Request(prompts[0], 5, stream_cb=boom))
        r1 = eng.submit(Request(prompts[1], 4))
        done = eng.run()
        assert len(done) == 2 and r0.done and r1.done
        for r, p in ((r0, prompts[0]), (r1, prompts[1])):
            ref = np.asarray(decode_greedy(
                model, paddle.to_tensor(p[None], dtype="int64"),
                max_new_tokens=len(r.output_ids), max_len=64))[0]
            np.testing.assert_array_equal(np.array(r.output_ids), ref)
        errs = reg.get("serving_stream_cb_errors_total")
        assert errs.labels(policy="continuous",
                           error="RuntimeError").value == len(r0.output_ids)


class TestRetirement:
    def test_eos_truncates_and_frees_slot(self):
        model = _tiny_model()
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 256, (6,))
        full = _run(model, [prompt], [8], batch_size=2, max_len=64)[0]
        eos = full.output_ids[2]
        # same prompt with that EOS: stops at (and includes) token 3; the
        # freed slot then serves the queued second request
        eng = ServingEngine(model, batch_size=1, max_len=64)
        r0 = eng.submit(Request(prompt, 8, eos_token_id=eos))
        r1 = eng.submit(Request(prompt, 4))
        eng.run()
        assert r0.output_ids == full.output_ids[:3]
        assert r0.done and r1.done
        np.testing.assert_array_equal(r1.output_ids, full.output_ids[:4])

    def test_sync_every_amortized_dispatch_is_exact(self):
        """sync_every > 1 (inner-scan token blocks) changes dispatch
        granularity only — outputs stay byte-identical."""
        model = _tiny_model()
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 8, 11)]
        new_lens = [7, 13, 5]
        kw = dict(batch_size=2, max_len=64)
        one = _run(model, prompts, new_lens, sync_every=1, **kw)
        four = _run(model, prompts, new_lens, sync_every=4, **kw)
        for i in one:
            np.testing.assert_array_equal(four[i].output_ids,
                                          one[i].output_ids)


class TestPipelinedDispatch:
    """The engine double-buffers the decode loop: step N+1 is dispatched
    before step N's tokens are synced, so host emit/admit work overlaps
    device compute.  The contract under test: one dispatch stays
    outstanding between iterations, and a slot that retires while a step
    is already inflight leaves every stream as a fresh engine serves it
    (the one-step-late retirement invariant)."""

    def test_step_leaves_a_dispatch_outstanding(self):
        """The double buffer is real: each iteration drains the PREVIOUS
        iteration's dispatch, so between scheduler iterations exactly one
        dispatched step stays inflight (regression: dispatch-then-drain of
        the SAME record in one iteration — no overlap at all)."""
        model = _tiny_model(seed=11)
        eng = ServingEngine(model, batch_size=1, max_len=64)
        r = eng.submit(Request(np.arange(1, 7), 4))
        eng.step()  # admit + final prefill chunk + dispatch step 1; the
        # first token is a device future riding the inflight record
        assert eng._inflight is not None
        assert len(r.output_ids) == 0
        eng.step()  # dispatch step 2, drain step 1 (first + block 1)
        assert eng._inflight is not None
        assert len(r.output_ids) == 2
        eng.run()
        assert r.done and eng._inflight is None and len(r.output_ids) == 4

    def test_retire_during_inflight_step(self):
        """Regression: a slot retiring (EOS) at drain time while the NEXT
        step over its old request is already dispatched.  The stale
        inflight tokens must be discarded (Request-identity check) and the
        request admitted into the freed slot must decode byte-identically
        to a fresh engine."""
        model = _tiny_model(seed=9)
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, 256, (6,))
        full = _run(model, [prompt], [8], batch_size=2, max_len=64)[0]
        eos = full.output_ids[2]
        other = rng.integers(0, 256, (9,))
        ref = _run(model, [other], [7], batch_size=1, max_len=64)[0]
        # batch_size=1 forces the race: every drain-retirement happens with
        # a dispatched step for the same slot outstanding
        eng = ServingEngine(model, batch_size=1, max_len=64)
        r0 = eng.submit(Request(prompt, 8, eos_token_id=eos))
        r1 = eng.submit(Request(other, 7))
        eng.run()
        assert r0.done and r0.output_ids == full.output_ids[:3]
        assert r1.done
        np.testing.assert_array_equal(r1.output_ids, ref.output_ids)

    def test_ragged_serving_steps_are_retrace_free(self):
        """Acceptance: once a warmup run has traced the prefill chunk and
        the decode step, a second mixed ragged run — admissions,
        retirements, pipelined double-buffered dispatch, chunked reads —
        triggers ZERO retraces: the chunked trip count is a traced scalar,
        not a shape, and every scheduler iteration reuses the same
        compiled programs."""
        from paddle_tpu.analysis import assert_no_retrace

        model = _tiny_model(seed=12)
        rng = np.random.default_rng(12)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 9, 14, 7)]
        new_lens = [6, 4, 9, 5]
        kw = dict(batch_size=2, max_len=64, decode_chunk=16)
        _run(model, prompts, new_lens, **kw)  # warmup: the legitimate traces
        with assert_no_retrace():
            _run(model, prompts, new_lens, **kw)

    def test_pipeline_metrics_and_full_drain(self):
        """run() leaves no step inflight; the stall histogram saw every
        drain and the inflight gauge is back to zero."""
        from paddle_tpu.observability import MetricsRegistry

        model = _tiny_model(seed=10)
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg)
        eng.submit(Request(np.arange(1, 8), 6))
        eng.submit(Request(np.arange(2, 12), 5))
        done = eng.run()
        assert len(done) == 2 and not eng.has_work
        lbl = dict(policy="continuous")
        assert reg.get("serving_inflight_steps").labels(**lbl).value == 0
        assert reg.get(
            "serving_pipeline_stall_seconds").labels(**lbl).count > 0


class TestChunkedPrefill:
    """Chunked prefill (serving_prefill_chunk) under budgeted
    prefill/decode interleaving: byte-identical to ``decode_greedy``,
    O(1) compiled programs, retrace-free steady state,
    and invisible to resident decode streams."""

    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_chunked_prefill_matches_decode_greedy(self, mode):
        """Byte-identity with ``decode_greedy`` of each prompt — the
        independent reference — across prompt lengths that are <, =, a
        multiple of, and a non-multiple of the chunk size (P=8), in both
        scheduler modes."""
        model = _tiny_model(seed=21)
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 8, 16, 13)]
        new_lens = [6, 5, 4, 7]
        chunk = _run(model, prompts, new_lens, batch_size=2, max_len=64,
                     mode=mode, prefill_chunk=8, prefill_budget=2)
        for i, (p, n) in enumerate(zip(prompts, new_lens)):
            assert list(chunk[i].output_ids) == _greedy_ref(model, p, n, 64)

    @pytest.mark.parametrize("paged", [{}, dict(kv_block=8)],
                             ids=["dense", "paged"])
    def test_prompt_above_half_of_max_len_is_served(self, paged):
        """Admission is by rows alone (prompt + max_new + headroom within
        ``max_len``): a 44-token prompt in a 64-row engine is served, and
        as ``decode_greedy`` serves it; a decode worker calls it viable;
        one row too many is still refused."""
        model = _tiny_model(seed=25)
        rng = np.random.default_rng(25)
        prompt = rng.integers(0, 256, (44,))
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            prefill_chunk=8, **paged)
        assert eng.adoption_viable(Request(prompt, 8))
        r = eng.submit(Request(prompt, 8))
        eng.run()
        assert list(r.output_ids) == _greedy_ref(model, prompt, 8, 64)
        too_long = Request(rng.integers(0, 256, (55,)), 8)    # 55 + 8 + 2
        assert not eng.adoption_viable(too_long)
        with pytest.raises(ValueError, match="cache rows"):
            eng.submit(too_long)

    @pytest.mark.parametrize("prompt_len,bucket", [
        (5, 16), (16, 16), (17, 32), (40, 64), (70, 100)])
    def test_prefill_counter_is_labelled_by_the_prompts_power_of_two(
            self, prompt_len, bucket):
        """``serving_prefill_total``'s ``bucket`` is the next power of two
        at or above the prompt length, from 16 up to ``max_len``."""
        from paddle_tpu.observability import MetricsRegistry

        reg = MetricsRegistry()
        eng = ServingEngine(_tiny_model(), batch_size=1, max_len=100,
                            prefill_chunk=8, registry=reg)
        eng.submit(Request(np.arange(prompt_len) % 256, 2))
        eng.run()
        counter = reg.get("serving_prefill_total")
        assert counter.labels(policy="continuous", bucket=bucket).value == 1

    def test_prefill_program_count_is_one_a_width(self):
        """Eight DISTINCT prompt lengths cost ONE serving_prefill_chunk
        trace a WIDTH of the run ladder (1 and 2 chunks at the default
        budget; offset / prompt_len / slot are traced operands, only the
        run's rows are a shape) — all of them in the first step that
        spends prefill, none later."""
        from paddle_tpu.models.llama_decode import _mon

        model = _tiny_model(seed=22)
        rng = np.random.default_rng(22)
        lens = (3, 5, 7, 9, 11, 14, 17, 21)
        # a geometry of this test's own: the jit cache is process-wide
        eng = ServingEngine(model, batch_size=2, max_len=72, prefill_chunk=8)
        count = lambda: _mon.trace_counts().get("serving_prefill_chunk", 0)
        before = count()
        reqs = [eng.submit(Request(rng.integers(0, 256, (p,)), 3))
                for p in lens]
        eng.step()
        assert count() - before == len(eng._widths) == 2
        eng.run()
        assert count() - before == 2
        assert all(r.status == "done" for r in reqs)

    def test_staggered_admissions_are_retrace_free(self):
        """Acceptance: steady-state serving with long prompts admitted
        mid-decode and drip-fed under prefill_budget=1 triggers ZERO
        retraces after a warmup run."""
        from paddle_tpu.analysis import assert_no_retrace

        model = _tiny_model(seed=23)
        rng = np.random.default_rng(23)

        def go():
            eng = ServingEngine(model, batch_size=2, max_len=64,
                                prefill_chunk=4, prefill_budget=1,
                                decode_chunk=16)
            eng.submit(Request(rng.integers(0, 256, (17,)), 6))
            for _ in range(3):
                eng.step()
            eng.submit(Request(rng.integers(0, 256, (23,)), 4))
            for _ in range(2):
                eng.step()
            eng.submit(Request(rng.integers(0, 256, (9,)), 5))
            eng.run()

        go()  # warmup: the legitimate traces
        with assert_no_retrace():
            go()

    def test_resident_stream_unaffected_by_mid_prefill(self):
        """Regression: a resident slot's per-step token stream is
        byte-identical whether or not another slot is mid-prefill beside
        it (the prefilling slot stays parked via masked_lengths until its
        final chunk)."""
        model = _tiny_model(seed=24)
        rng = np.random.default_rng(24)
        prompt = rng.integers(0, 256, (6,))
        other = rng.integers(0, 256, (21,))
        kw = dict(batch_size=2, max_len=64, prefill_chunk=4,
                  prefill_budget=1)
        eng = ServingEngine(model, **kw)
        alone = eng.submit(Request(prompt.copy(), 10))
        eng.run()
        eng2 = ServingEngine(model, **kw)
        beside = eng2.submit(Request(prompt.copy(), 10))
        for _ in range(4):
            eng2.step()
        # a long prompt lands while the resident slot is mid-stream and
        # drips through prefill one chunk per step
        eng2.submit(Request(other, 4))
        eng2.run()
        assert list(beside.output_ids) == list(alone.output_ids)


class TestSubmitValidation2:
    """rid bookkeeping (PR-5 satellites)."""

    def test_auto_rids_only_advance_on_assignment(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        r0 = eng.submit(Request(np.arange(1, 5), 2))
        assert r0.rid == 0
        with pytest.raises(ValueError, match="cache rows"):
            eng.submit(Request(np.arange(0, 70), 2))
        # the rejected submit must not have burned an auto rid
        assert eng.submit(Request(np.arange(1, 6), 2)).rid == 1

    def test_user_rid_collision_rejected(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        eng.submit(Request(np.arange(1, 5), 2, rid="job-a"))
        with pytest.raises(ValueError, match="already in use"):
            eng.submit(Request(np.arange(1, 6), 2, rid="job-a"))
        auto = eng.submit(Request(np.arange(1, 7), 2))
        with pytest.raises(ValueError, match="already in use"):
            eng.submit(Request(np.arange(1, 8), 2, rid=auto.rid))

    def test_user_int_rid_bumps_auto_counter(self):
        """A caller-provided int rid can no longer alias a FUTURE auto
        rid: the auto counter jumps past it."""
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64)
        eng.submit(Request(np.arange(1, 5), 2, rid=5))
        assert eng.submit(Request(np.arange(1, 6), 2)).rid == 6


class TestKVCacheGuards:
    """Slot double-assign / double-release are loud ValueErrors, not
    silent corruption (reliability-layer satellite)."""

    def _mgr(self):
        from paddle_tpu.serving.kv_cache import KVCacheManager
        return KVCacheManager(n_layers=1, batch_size=2, max_len=8,
                              num_kv_heads=1, head_dim=4, dtype="float32")

    def test_double_assign_raises(self):
        kv = self._mgr()
        a = Request(np.arange(1, 4), 2, rid="a")
        kv.assign(0, a)
        with pytest.raises(ValueError, match="already holds request 'a'"):
            kv.assign(0, Request(np.arange(1, 4), 2, rid="b"))
        # the occupant survives the rejected assign
        assert kv.reqs[0] is a and kv.free_slots() == [1]

    def test_double_release_raises(self):
        kv = self._mgr()
        kv.assign(1, Request(np.arange(1, 4), 2))
        kv.release(1)
        with pytest.raises(ValueError, match="already free"):
            kv.release(1)
        assert kv.free_slots() == [0, 1]


class TestWideRuns:
    """The chunks a scheduler step spends on ONE prompt ride in one run of
    the prefill program (``[1, k * P]`` rows): whatever the budget, what is
    served is what the chunk-a-run engine (``prefill_budget=1``) serves."""

    MODES = {
        "dense": {},
        # (a paged cache's span is whole blocks)
        "paged": dict(kv_block=16, max_len=128),
        "paged_wide_block": dict(kv_block=32, max_len=128),
        "int8": dict(kv_dtype="int8"),
        "ngram": dict(mode="spec", spec_k=4),
        "draft": dict(mode="spec"),
        "draft_paged": dict(mode="spec", kv_block=16, max_len=128),
        "prefill_only": dict(kv_block=16, max_len=128, prefill_only=True),
    }

    @staticmethod
    def make(budget, mode="dense", **kw):
        from paddle_tpu.serving.engine import SpecConfig

        kw = {**dict(batch_size=2, max_len=W.LMAX, prefill_chunk=W.P,
                     decode_chunk=16, prefill_budget=budget),
              **TestWideRuns.MODES[mode], **kw}
        if mode.startswith("draft"):
            paddle.seed(1)
            draft = LlamaForCausalLM(LlamaConfig.tiny(
                dtype="float32", num_hidden_layers=1))
            draft.eval()
            kw["spec"] = SpecConfig(source="draft_model", draft_model=draft,
                                    spec_k=4)
        return ServingEngine(_tiny_model(seed=31), **kw)

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def served(mode, budget, length):
        # (a prefill-only engine's requests carry their first token alone)
        new = 1 if mode == "prefill_only" else W.NEW
        _, reqs = W.serve(TestWideRuns.make, budget, length, new=new,
                          mode=mode)
        return W.streams(reqs)

    @pytest.mark.parametrize("length", W.LENGTHS)
    @pytest.mark.parametrize("budget", W.BUDGETS)
    def test_streams_are_the_chunk_a_run_engines(self, budget, length):
        got = self.served("dense", budget, length)
        assert [s for s, _ in got] == ["done", "done"]
        assert got == self.served("dense", 1, length)

    @pytest.mark.parametrize("length", (W.P + 1, 3 * W.P, None))
    @pytest.mark.parametrize("mode", sorted(set(MODES) - {"dense"}))
    def test_streams_in_every_mode(self, mode, length):
        got = self.served(mode, 4, length)
        assert [s for s, _ in got] == ["done", "done"]
        assert got == self.served(mode, 1, length)

    def test_budget_one_is_a_chunk_a_run(self):
        from paddle_tpu.observability import MetricsRegistry

        reg = MetricsRegistry()
        eng, _ = W.serve(self.make, 1, 4 * W.P + 1, registry=reg)
        value = lambda name: reg.get(name).labels(policy="continuous").value
        assert eng._widths == [1] and eng._widths_warm
        assert value("serving_prefill_runs_total") == 5 + 3 \
            == value("serving_prefill_chunks_total")

    def test_the_fused_prefill_kernel_keeps_a_chunk_a_run(self):
        """Its appends are DMA windows that rely on ``offset % rows == 0``,
        and a run starts where the last one ended."""
        eng = self.make(4, prefill_impl="pallas")
        assert eng._widths == [1]

    @pytest.mark.parametrize("mode", ["dense", "paged", "ngram", "draft",
                                      "draft_paged"])
    def test_every_width_is_compiled_by_the_first_prefill_step(self, mode):
        from paddle_tpu.models.llama_decode import _mon

        W.check_warm_set(self.make, _mon, mode=mode,
                         programs=2 if mode.startswith("draft") else 1)


@pytest.mark.slow
class TestServingMixedWorkload:
    """Long mixed-length workload: every request completes, outputs are
    byte-identical across the greedy scheduler and speculative serving."""

    def test_mixed_lengths_all_policies_agree(self):
        model = _tiny_model(seed=7)
        rng = np.random.default_rng(7)
        n_req = 16
        plens = rng.integers(8, 49, n_req)
        olens = rng.integers(8, 33, n_req)
        prompts = [rng.integers(0, 256, (p,)) for p in plens]
        kw = dict(batch_size=4, max_len=128)
        cont = _run(model, prompts, olens, sync_every=2, **kw)
        spec = _run(model, prompts, olens, mode="spec", spec_k=4, **kw)
        assert len(cont) == n_req
        for i in range(n_req):
            assert len(cont[i].output_ids) == olens[i]
            np.testing.assert_array_equal(spec[i].output_ids,
                                          cont[i].output_ids)
