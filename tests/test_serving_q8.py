"""int8 quantized KV cache (``kv_dtype="int8"``) through the serving
stack: quantize-on-append / dequant-in-loop.

The load-bearing properties:

- **Parity/drift**: greedy decoding on the q8 cache tracks the f32
  reference within a small drift budget across the full scheduler matrix
  (greedy/spec x paged/dense); the tiny f32 test model
  has wide logit margins, so observed drift is typically zero, and the
  budget (25% of emitted tokens) is a backstop against argmax ties.
- **Byte-identity of q8-internal invariants**: everything that was
  byte-identical at f32 stays byte-identical at q8 — paged == dense.
  Quantization changes values, never scheduling.
- **Zero retraces**: a warmed q8 engine serves a staggered ragged wave
  without a single new trace — the (int8 data, f16 scale) tuple leaves
  change program identity ONCE, at warmup, not per step.
- **Reliability composes**: NaN poison detection still fires through the
  quantized path (int8 can't hold a NaN — the fault injector poisons the
  scale leaf, which dequant propagates to the logits).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.analysis import assert_no_retrace
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.ops.decode_attention import (
    _q8_dequant, _q8_quantize, init_kv_cache, init_kv_pool)
from paddle_tpu.serving import FaultPlan, Request, ServingEngine
from tests.test_serving import _run, _tiny_model

_RNG = np.random.default_rng(21)
_PROMPTS = [_RNG.integers(1, 200, size=p) for p in (5, 11, 8)]
_NEW = [7, 5, 6]

# q8 engines under test share one geometry; ``paged`` swaps in the block
# pool the same way the f32 parity suites do
_BASE = dict(batch_size=2, max_len=64, decode_chunk=16)
_PAGED = dict(kv_block=16, max_live_tokens=2 * 64)


def _outputs(model, **kw):
    done = _run(model, _PROMPTS, _NEW, **_BASE, **kw)
    return {rid: list(r.output_ids) for rid, r in sorted(done.items())}


# the matrix and the byte-identity tests revisit the same engine configs;
# outputs are deterministic for a given config, so run each engine once
_MEMO = {}


def _outputs_memo(model, **kw):
    key = tuple(sorted((k, str(v)) for k, v in kw.items()))
    if key not in _MEMO:
        _MEMO[key] = _outputs(model, **kw)
    return _MEMO[key]


def _drift(a, b):
    """Fraction of per-request aligned tokens that differ."""
    diff = total = 0
    for rid in a:
        assert len(a[rid]) == len(b[rid])  # scheduling never drifts
        total += len(a[rid])
        diff += sum(x != y for x, y in zip(a[rid], b[rid]))
    return diff / max(total, 1)


# ---------------------------------------------------------------------------
# scale scheme: quantize -> dequantize round-trip error bound
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_error_bounded_by_half_step(self):
        """Per-(position, head) absmax scaling: the round-trip error is at
        most half a quantization step, plus the f16 rounding of the scale
        itself (the scale is ROUNDED to f16 before the divide, so storage
        and arithmetic agree)."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 3, 2, 16)) * 3.0,
                        dtype=jnp.float32)
        q, s = _q8_quantize(x)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float16
        assert s.shape == x.shape[:-1]
        y = _q8_dequant(q, s)
        step = np.asarray(s, np.float32)[..., None]  # one int8 step
        err = np.abs(np.asarray(y) - np.asarray(x))
        # half a step, with 2% headroom for the f16 scale rounding
        assert np.all(err <= step * 0.5 * 1.02 + 1e-6)

    def test_zero_rows_round_trip_exactly(self):
        x = jnp.zeros((2, 5, 3, 8), jnp.float32)
        q, s = _q8_quantize(x)
        assert not np.any(np.asarray(q)) and not np.any(np.asarray(s))
        assert not np.any(np.asarray(_q8_dequant(q, s)))


# ---------------------------------------------------------------------------
# dtype validation (satellite small-fix)
# ---------------------------------------------------------------------------

class TestDtypeValidation:
    def test_init_kv_cache_rejects_unknown_dtype(self):
        with pytest.raises(ValueError, match="unsupported KV cache dtype"):
            init_kv_cache(2, 64, 2, 16, dtype="int4")

    def test_init_kv_pool_rejects_unknown_dtype(self):
        with pytest.raises(ValueError, match="unsupported KV cache dtype"):
            init_kv_pool(8, 16, 2, 16, dtype="float8")

    def test_engine_rejects_unknown_kv_dtype(self):
        with pytest.raises(ValueError, match="unsupported KV cache dtype"):
            ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                          kv_dtype="int4")

    def test_int8_allocates_tuple_leaves(self):
        kc, vc = init_kv_cache(2, 64, 2, 16, dtype="int8")
        for data, scale in (kc, vc):
            assert data.dtype == jnp.int8 and data.shape == (2, 64, 2, 16)
            assert scale.dtype == jnp.float16 and scale.shape == (2, 64, 2)


# ---------------------------------------------------------------------------
# parity/drift matrix vs f32 + byte-identity of q8-internal invariants
# ---------------------------------------------------------------------------

class TestParityMatrix:
    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_q8_tracks_f32(self, mode, paged):
        model = _tiny_model()
        kw = dict(mode=mode)
        if mode == "spec":
            kw["spec_k"] = 4
        if paged:
            kw.update(_PAGED)
        ref = _outputs_memo(model, **kw)
        q8 = _outputs_memo(model, kv_dtype="int8", **kw)
        assert _drift(q8, ref) <= 0.25

    def test_q8_paged_matches_dense_byte_identical(self):
        model = _tiny_model()
        dense = _outputs_memo(model, kv_dtype="int8", mode="greedy")
        paged = _outputs_memo(model, kv_dtype="int8", mode="greedy", **_PAGED)
        assert dense == paged


# ---------------------------------------------------------------------------
# zero-retrace acceptance
# ---------------------------------------------------------------------------

class TestZeroRetrace:
    def test_warm_q8_engine_staggered_wave(self):
        """The (int8 data, f16 scale) cache tuple specializes the
        programs once at warmup; a second engine serving a LARGER
        staggered wave triggers zero retraces."""
        model = _tiny_model()
        rng = np.random.default_rng(3)

        def wave(n):
            return [rng.integers(1, 200, size=int(p))
                    for p in rng.integers(4, 20, size=n)]

        kw = dict(batch_size=2, max_len=64, decode_chunk=16,
                  kv_dtype="int8", **_PAGED)
        eng = ServingEngine(model, **kw)
        for p in wave(4):
            eng.submit(Request(p, 5))
        eng.run()
        eng2 = ServingEngine(model, **kw)
        with assert_no_retrace():
            for p in wave(8):
                eng2.submit(Request(p, 7))
            eng2.run()


# ---------------------------------------------------------------------------
# reliability composes: poison quarantine through the quantized path
# ---------------------------------------------------------------------------

class TestPoisonQuarantineQ8:
    def test_nan_detection_fires_through_int8_cache(self):
        """int8 storage can't hold a NaN, so the fault injector poisons
        the parallel SCALE leaf — dequant propagates it into the logits
        and the existing non-finite quarantine retires the request, while
        the cohabitant stays byte-identical to a clean q8 run."""
        model = _tiny_model()
        kw = dict(kv_dtype="int8")
        ref = _outputs(model, **kw)
        plan = FaultPlan(poison={0: 2})
        eng = ServingEngine(model, faults=plan, **_BASE, **kw)
        reqs = [eng.submit(Request(p, n)) for p, n in zip(_PROMPTS, _NEW)]
        statuses = eng.drain()
        assert statuses[0] == "poisoned" and plan.stats["poisoned"] == 1
        # pre-fault partial output is a clean-run prefix, never garbage
        assert list(reqs[0].output_ids) == \
            ref[0][:len(reqs[0].output_ids)]
        for r in reqs[1:]:
            assert statuses[r.rid] == "done"
            assert list(r.output_ids) == ref[r.rid]


# ---------------------------------------------------------------------------
# observability: info gauge, analytic HBM gauge, recorder dispatch detail
# ---------------------------------------------------------------------------

class TestQ8Observability:
    def test_info_gauge_and_analytic_hbm(self):
        model = _tiny_model()  # 2 layers, 2 kv heads, head_dim 16
        reg = MetricsRegistry()
        ServingEngine(model, batch_size=2, max_len=64, registry=reg,
                      kv_dtype="int8")
        mode = reg.get("serving_kv_quant_mode")
        assert mode.labels(policy="continuous", mode="int8").value == 1
        assert mode.labels(policy="continuous", mode="off").value == 0
        hbm = reg.get("serving_hbm_gb_per_tok_q8")
        # layers * 2 * Hkv * (D + 2 scale bytes) = 2*2*2*18 = 144 B/tok
        assert hbm.labels(policy="continuous").value == \
            pytest.approx(144 / 1e9)

    def test_unquantized_engine_reads_off(self):
        reg = MetricsRegistry()
        ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                      registry=reg)
        mode = reg.get("serving_kv_quant_mode")
        assert mode.labels(policy="continuous", mode="off").value == 1
        assert mode.labels(policy="continuous", mode="int8").value == 0
        assert reg.get("serving_hbm_gb_per_tok_q8").labels(
            policy="continuous").value == 0

    def test_recorder_dispatch_events_carry_kv_quant(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64,
                            recorder=True, kv_dtype="int8")
        eng.submit(Request(_PROMPTS[0], 4))
        eng.run()
        dispatches = [e for e in eng.recorder.events()
                      if e["kind"] == "dispatch"]
        assert dispatches
        assert all(e["kv_quant"] == "int8" for e in dispatches)
