"""Resident draft-model speculative decoding (SpecConfig source=
"draft_model") — the ISSUE-20 tentpole.

The acceptance properties on the CPU mesh at f32:

* LOSSLESS: a draft-model spec engine's token streams are BYTE-
  IDENTICAL to the greedy engine on the same workload, across
  paged/dense KV x f32/int8 x TP 1x4 mesh x
  disaggregated 1P+1D — the verify forward's own greedy picks are the
  only emission path, so draft quality moves throughput, never bytes;
* the draft model is a POOL TENANT, not a second pool: its chains draw
  the shared free list through their own block tables and radix
  namespace, and after a drain the draft tenant's accounting returns
  to exactly zero (no leaked blocks, no stranded reservations);
* adaptive draft length moves the depth along a compiled-rung ladder
  from sliding-window accept rates, and a WARM engine runs the whole
  ladder at ZERO retraces (each rung is its own program, warmed once);
* tree-structured candidates (``spec_tree="top2"``) verify a top-2
  branch at the first draft position in the same batched forward —
  still byte-identical to greedy, dense caches only (loud error on
  paged);
* ``SpecConfig`` validation is loud at construction, and a draft_model
  source with no draft model falls back to prompt-lookup with a
  once-per-process warning instead of a crash.
"""
import warnings

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu.analysis import assert_no_retrace
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.engine import AcceptWindow, SpecConfig
import paddle_tpu.serving.engine as engine_mod

GEOM = dict(batch_size=2, max_len=96, decode_chunk=16, prefill_chunk=8,
            instrument=False, recorder=False)
PAGED = dict(kv_block=8, max_live_tokens=None)


def _model(seed=0, layers=2, **kw):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(dtype="float32", num_hidden_layers=layers, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _draft(seed=1, **kw):
    """A 1-layer shrunk drafter sharing tiny()'s KV geometry (nkv=2,
    hd=16) — pool-shareable with the 2-layer target."""
    return _model(seed=seed, layers=1, **kw)


def _prompts(rng, sizes):
    return [rng.integers(1, 200, size=int(s)).astype(np.int32)
            for s in sizes]


def _run(model, prompts, new_lens, **kw):
    eng = ServingEngine(model, **kw)
    for p, n in zip(prompts, new_lens):
        eng.submit(Request(p, int(n)))
    done = eng.run()
    assert not eng.has_work
    return {r.rid: list(r.output_ids) for r in done}, eng


def _sc(draft, **kw):
    return SpecConfig(source="draft_model", draft_model=draft, spec_k=4,
                      **kw)


# ---------------------------------------------------------------------------
# SpecConfig / AcceptWindow units (pure host)
# ---------------------------------------------------------------------------

class TestSpecConfig:
    def test_source_enum(self):
        with pytest.raises(ValueError, match="source"):
            SpecConfig(source="oracle")

    @pytest.mark.parametrize("bad", [0, -1, True, 2.0, "4"])
    def test_spec_k_validated(self, bad):
        with pytest.raises(ValueError, match="spec_k"):
            SpecConfig(spec_k=bad)

    def test_k_min_le_spec_k(self):
        with pytest.raises(ValueError, match="k_min"):
            SpecConfig(spec_k=2, k_min=3)

    @pytest.mark.parametrize("bad", [0, True, "8"])
    def test_adaptive_window_validated(self, bad):
        with pytest.raises(ValueError, match="adaptive_window"):
            SpecConfig(adaptive_window=bad)

    def test_tree_requires_draft_model_source(self):
        with pytest.raises(ValueError, match="tree"):
            SpecConfig(source="prompt_lookup", tree="top2")
        with pytest.raises(ValueError, match="tree"):
            SpecConfig(source="draft_model", tree="top3")

    def test_spec_kwarg_requires_spec_mode(self):
        with pytest.raises(ValueError, match="mode"):
            ServingEngine(_model(), mode="greedy", spec=SpecConfig(),
                          **GEOM)

    def test_tree_requires_dense_caches(self):
        with pytest.raises(ValueError, match="dense"):
            ServingEngine(_model(), mode="spec",
                          spec=_sc(_draft(), tree="top2"),
                          **{**GEOM, "kv_block": 8})

    def test_draft_model_requires_chunked_prefill(self):
        kw = dict(GEOM)
        kw["prefill_chunk"] = None
        with pytest.raises(ValueError, match="chunked"):
            ServingEngine(_model(), mode="spec", spec=_sc(_draft()), **kw)

    def test_paged_geometry_mismatch_is_loud(self):
        # draft with nkv=4 vs target nkv=2: blocks are not model-agnostic
        # bytes, so paged sharing must refuse
        bad = _draft(num_key_value_heads=4)
        with pytest.raises(ValueError, match="geometry"):
            ServingEngine(_model(), mode="spec", spec=_sc(bad),
                          **{**GEOM, "kv_block": 8})
        # the same drafter is fine on dense caches (separate arrays)
        ServingEngine(_model(), mode="spec", spec=_sc(bad), **GEOM)

    def test_draft_layer_count_capped_by_target(self):
        deep = _model(seed=2, layers=3)
        with pytest.raises(ValueError, match="layer count"):
            ServingEngine(_model(), mode="spec", spec=_sc(deep),
                          **{**GEOM, "kv_block": 8})

    def test_dict_spec_accepted(self):
        eng = ServingEngine(
            _model(), mode="spec",
            spec={"source": "prompt_lookup", "spec_k": 3}, **GEOM)
        assert eng._spec.spec_k == 3

    def test_missing_draft_model_falls_back_with_one_warning(self,
                                                             monkeypatch):
        monkeypatch.setattr(engine_mod, "_SPEC_FALLBACK_WARNED", False)
        with pytest.warns(RuntimeWarning, match="prompt-lookup"):
            eng = ServingEngine(_model(), mode="spec",
                                spec=SpecConfig(source="draft_model"),
                                **GEOM)
        assert eng._spec.source == "prompt_lookup"
        assert not eng._dspec
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ServingEngine(_model(), mode="spec",
                          spec=SpecConfig(source="draft_model"), **GEOM)


class TestAcceptWindow:
    def test_validation(self):
        with pytest.raises(ValueError):
            AcceptWindow(0)
        w = AcceptWindow(4)
        with pytest.raises(ValueError):
            w.push(4, 5)
        with pytest.raises(ValueError):
            w.push(4, -1)

    def test_empty_rate_is_none(self):
        assert AcceptWindow(3).rate() is None

    def test_rate_and_sliding(self):
        w = AcceptWindow(2)
        w.push(4, 4)
        assert w.rate() == pytest.approx(1.0)
        w.push(4, 0)
        assert w.rate() == pytest.approx(0.5)
        w.push(4, 0)  # slides the all-accepted round out
        assert w.rate() == pytest.approx(0.0)
        assert len(w) == 2

    def test_reset(self):
        w = AcceptWindow(3)
        w.push(2, 1)
        w.reset()
        assert w.rate() is None and len(w) == 0


# ---------------------------------------------------------------------------
# byte-identity matrix: draft-model spec vs greedy
# ---------------------------------------------------------------------------

class TestDraftSpecByteIdentity:
    def _matrix_run(self, **extra):
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (7, 12, 9))
        new_lens = [20, 14, 18]
        base, _ = _run(_model(), prompts, new_lens, mode="greedy", **GEOM)
        out, eng = _run(_model(), prompts, new_lens, mode="spec",
                        **{**GEOM, **extra})
        assert base == out, extra
        return eng

    @pytest.mark.parametrize("paged", [False, True])
    def test_matches_greedy(self, paged):
        extra = dict(spec=_sc(_draft()))
        if paged:
            extra.update(PAGED)
        self._matrix_run(**extra)

    @pytest.mark.slow  # compiles its own int8 draft+verify program family
    def test_matches_greedy_int8_kv(self):
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (7, 12, 9))
        new_lens = [20, 14, 18]
        base, _ = _run(_model(), prompts, new_lens, mode="greedy",
                       kv_dtype="int8", **{**GEOM, **PAGED})
        out, _ = _run(_model(), prompts, new_lens, mode="spec",
                      spec=_sc(_draft()), kv_dtype="int8",
                      **{**GEOM, **PAGED})
        assert base == out

    def test_matches_greedy_adaptive_k(self):
        self._matrix_run(spec=_sc(_draft(), adaptive_window=3, k_min=1),
                         **PAGED)

    def test_matches_greedy_tree(self):
        eng = self._matrix_run(spec=_sc(_draft(), tree="top2"))
        assert eng._pk.spec_tree == "top2"

    @pytest.mark.slow  # third tree-program family (adaptive rungs x tree)
    def test_matches_greedy_tree_pipelined_adaptive(self):
        self._matrix_run(spec=_sc(_draft(), tree="top2",
                                  adaptive_window=3))

    @pytest.mark.slow  # compiles the TP draft program family on the mesh
    def test_tp_mesh_matches_single_device_greedy(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
        # tiny() has nkv=2 — bump to 4 so heads divide the mesh axis
        tgt = _model(num_key_value_heads=4)
        drf = _draft(num_key_value_heads=4)
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (7, 12, 9))
        new_lens = [16, 12, 14]
        base, _ = _run(_model(num_key_value_heads=4), prompts, new_lens,
                       mode="greedy", **GEOM)
        for extra in (dict(), dict(**PAGED),
                      dict(spec=None, **PAGED)):
            kw = dict(GEOM)
            kw.update(extra)
            kw["spec"] = _sc(drf, adaptive_window=3) \
                if extra.get("spec", 0) is None else _sc(drf)
            out, _ = _run(tgt, prompts, new_lens, mode="spec", mesh=mesh,
                          **kw)
            assert base == out, extra

    @pytest.mark.slow  # spins a full 1P+1D coordinator + its own geometry
    def test_disagg_1p1d_matches_colocated_greedy(self):
        from paddle_tpu.serving import (DecodeWorker, DisaggCoordinator,
                                        PrefillWorker)
        model = _model()
        rng = np.random.default_rng(11)
        prompts = _prompts(rng, (21, 9, 14))
        geom = dict(GEOM, prefill_chunk=16, decode_chunk=16, kv_block=16,
                    batch_size=3, max_len=128)
        eng = ServingEngine(model, mode="greedy", **geom)
        base = [eng.submit(Request(p, 12)) for p in prompts]
        eng.run()
        coord = DisaggCoordinator(
            PrefillWorker(model, **geom),
            DecodeWorker(model, mode="spec", spec=_sc(_draft()), **geom),
            instrument=False)
        dis = [coord.submit(Request(p, 12)) for p in prompts]
        coord.run()
        assert coord.stats()["migrations_ok"] == len(prompts)
        for b, d in zip(base, dis):
            assert b.status == d.status == "done"
            assert list(b.output_ids) == list(d.output_ids)
        # the decode worker rebuilt draft KV locally and drained clean
        kv = coord._decode[0].engine._kv
        assert kv.draft_blocks_used() == 0
        assert kv.outstanding() == 0
        eng.close()
        coord.close()


# ---------------------------------------------------------------------------
# shared-pool draft tenancy accounting
# ---------------------------------------------------------------------------

class TestDraftTenancy:
    def test_accounting_returns_to_zero_after_drain(self):
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, (7, 12, 9, 11))
        new_lens = [16, 10, 14, 12]
        reg = MetricsRegistry()
        out, eng = _run(_model(), prompts, new_lens, mode="spec",
                        spec=_sc(_draft()), registry=reg,
                        **{**GEOM, **PAGED, "instrument": True})
        kv = eng._kv
        assert kv.live_tokens() == 0
        # target prefixes may park evictable; draft chains are freed
        # OUTRIGHT at refcount 0 (never parked, never demoted)
        assert kv.blocks_used() == kv.evictable_count()
        assert kv.draft_blocks_used() == 0
        assert kv.outstanding() == 0
        used = reg.get("serving_kv_blocks_used")
        assert used.labels(policy="continuous", model="draft").value == 0
        assert used.labels(policy="continuous", model="target").value \
            == kv.blocks_used()

    def test_draft_radix_reuse_while_chain_live(self):
        # the draft radix matches only while the registering chain is
        # LIVE (draft blocks free outright at retire — they never park
        # evictable), so a same-prefix admission that lands mid-run
        # adopts the resident draft chain instead of re-prefilling it
        rng = np.random.default_rng(3)
        p = rng.integers(1, 200, size=24).astype(np.int32)
        eng = ServingEngine(_model(), mode="spec", spec=_sc(_draft()),
                            **{**GEOM, **PAGED})
        eng.submit(Request(p, 16))
        for _ in range(64):
            eng.step()
            if eng._kv.match_draft_prefix(p)[0] > 0:
                break
        off, blocks = eng._kv.match_draft_prefix(p)
        assert off > 0 and len(blocks) > 0
        eng.submit(Request(p, 8))  # adopts the live draft chain
        eng.run()
        # ...and at retire the radix empties with the chains
        assert eng._kv.match_draft_prefix(p)[0] == 0
        assert eng._kv.draft_blocks_used() == 0
        assert eng._kv.outstanding() == 0

    def test_accept_rate_real_and_high_with_self_draft(self):
        # a same-seed copy of the target as its own drafter: every draft
        # token IS the target's greedy pick, so the accept rate is ~1.0 —
        # pins that acceptance is measured for real, not vacuously
        rng = np.random.default_rng(9)
        prompts = _prompts(rng, (7, 12))
        reg = MetricsRegistry()
        _, eng = _run(_model(), prompts, [16, 16], mode="spec",
                      spec=_sc(_model()), registry=reg,
                      **{**GEOM, **PAGED, "instrument": True})
        rate = reg.get("serving_spec_accept_rate").labels(
            policy="continuous", source="draft_model").value
        assert rate > 0.5
        info = reg.get("serving_spec_draft_source")
        assert info.labels(policy="continuous",
                           source="draft_model").value == 1
        assert info.labels(policy="continuous",
                           source="prompt_lookup").value == 0

    def test_flight_recorder_draft_verify_rewind_events(self):
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (7, 12))
        eng = ServingEngine(_model(), mode="spec", spec=_sc(_draft()),
                            **{**dict(GEOM, recorder=True), **PAGED})
        for p in prompts:
            eng.submit(Request(p, 10))
        eng.run()
        events = eng.recorder.snapshot(last=4096)["events"]
        kinds = {e["kind"] for e in events}
        assert {"draft", "verify", "rewind"} <= kinds
        d = next(e for e in events if e["kind"] == "draft")
        assert d["source"] == "draft_model" and d["k"] >= 1
        v = next(e for e in events if e["kind"] == "verify")
        assert 0 <= v["accepted"] <= v["drafted"]


# ---------------------------------------------------------------------------
# adaptive draft depth
# ---------------------------------------------------------------------------

class TestAdaptiveDepth:
    def test_rung_ladder_shape(self):
        eng = ServingEngine(
            _model(), mode="spec",
            spec=SpecConfig(spec_k=8, adaptive_window=4, k_min=1), **GEOM)
        assert eng._k_rungs == [1, 2, 4, 8]
        assert eng._k_cur == 8

    def test_depth_descends_on_rejection(self):
        eng = ServingEngine(
            _model(), mode="spec",
            spec=SpecConfig(spec_k=4, adaptive_window=2, k_min=1), **GEOM)
        # feed all-rejected rounds through the policy for slot 0
        for _ in range(2):
            eng._adapt_k([(0, 0)], 4)
        assert eng._k_want[0] == len(eng._k_rungs) - 2
        k1 = eng._next_k([0])
        assert k1 == eng._k_rungs[-2]       # one rung per round
        # recovery: all-accepted rounds climb back (the first push still
        # shares the window with a rejected round, so three are needed
        # before the windowed rate clears the 0.8 up-hysteresis)
        for _ in range(3):
            eng._adapt_k([(0, k1)], k1)
        assert eng._next_k([0]) == eng._k_rungs[-1]

    def test_batch_depth_is_min_over_live(self):
        eng = ServingEngine(
            _model(), mode="spec", batch_size=2,
            spec=SpecConfig(spec_k=4, adaptive_window=1, k_min=1),
            max_len=96, prefill_chunk=8, instrument=False, recorder=False)
        eng._adapt_k([(0, 4), (1, 0)], 4)   # slot 1 rejects everything
        assert eng._next_k([0, 1]) < 4
        # slot 1 retires: its pessimism leaves with it, and the batch
        # depth climbs back toward slot 0's rung (one rung per round)
        eng._reset_spec_slot(1)
        for _ in range(len(eng._k_rungs)):
            k = eng._next_k([0])
            eng._adapt_k([(0, k)], k)
        assert eng._k_cur == 4

    def test_spec_draft_k_gauge_tracks_depth(self):
        reg = MetricsRegistry()
        eng = ServingEngine(
            _model(), mode="spec", registry=reg,
            spec=SpecConfig(spec_k=4, adaptive_window=1, k_min=1),
            **{**GEOM, "instrument": True})
        g = reg.get("serving_spec_draft_k").labels(policy="continuous")
        assert g.value == 4
        eng._adapt_k([(0, 0)], 4)
        eng._next_k([0])
        assert g.value == 2


# ---------------------------------------------------------------------------
# warm-path zero retraces with the draft resident
# ---------------------------------------------------------------------------

class TestWarmDraftZeroRetrace:
    def test_staggered_wave_adaptive_k_no_retrace(self):
        rng = np.random.default_rng(13)
        prompts = _prompts(rng, (7, 12, 9, 21, 11))
        new_lens = [14, 10, 16, 8, 12]

        def wave(eng):
            # staggered: two up front, the rest fed mid-run so chains
            # grow, rewind, release and re-admit while the adaptive
            # ladder moves
            it = iter(zip(prompts, new_lens))
            for p, n in [next(it), next(it)]:
                eng.submit(Request(p, int(n)))
            for p, n in it:
                eng.step()
                eng.submit(Request(p, int(n)))
            eng.run()

        kw = dict(mode="spec",
                  spec=_sc(_draft(), adaptive_window=2, k_min=1),
                  **{**GEOM, **PAGED})
        wave(ServingEngine(_model(), **kw))       # warm: traces all rungs
        eng2 = ServingEngine(_model(), **kw)
        with assert_no_retrace():
            wave(eng2)
        assert eng2._kv.draft_blocks_used() == 0
