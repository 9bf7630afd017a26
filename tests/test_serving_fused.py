"""Fused Pallas decode attention (``attn_impl="pallas"``) and int8
quantized decode weights (``weight_dtype="int8"``) through the serving
stack.

The load-bearing properties:

- **Parity/drift**: greedy decoding with the fused kernel tracks the
  reference ``lax.while_loop`` read within the q8 drift budget across
  the scheduler matrix (greedy/spec x paged/dense x kv f32/int8), under
  TP on a 4-way mesh, and with quantized weights — the tiny f32 test
  model has wide logit margins, so observed drift is typically zero and
  the 25% budget is a backstop against argmax ties.
- **Fallback is loud and bitwise**: unsupported geometry (full-length
  read, attn_bias, non-dividing chunk) drops to the reference path
  BITWISE-identical to ``attn_impl=None``, with a once-per-process log
  so the downgrade is never silent.
- **Zero retraces**: ``attn_impl``/``weight_dtype`` are static knobs —
  a warmed fused engine serves a larger staggered wave without a single
  new trace.
- **Observability**: the ``serving_decode_kernel`` and
  ``serving_weight_quant_mode`` info gauges and the analytic
  ``serving_hbm_gb_per_tok_w8`` gauge reflect the knobs, and flight-
  recorder dispatch events carry both.
"""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.analysis import assert_no_retrace
from paddle_tpu.models.llama_decode import (
    _QUANT_WEIGHTS, quantize_decode_weights)
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.ops import paged_attention_pallas as pap
from paddle_tpu.ops.decode_attention import decode_attention
from paddle_tpu.serving import Request, ServingEngine
from tests.test_serving import _run, _tiny_model
from tests.test_serving_tp import _mesh, _tp_model

_RNG = np.random.default_rng(21)
_PROMPTS = [_RNG.integers(1, 200, size=p) for p in (5, 11, 8)]
_NEW = [7, 5, 6]

_BASE = dict(batch_size=2, max_len=64, decode_chunk=16)
_PAGED = dict(kv_block=16, max_live_tokens=2 * 64)

_BUDGET = 0.25  # same flip-rate budget as the q8 parity suite


def _outputs(model, **kw):
    done = _run(model, _PROMPTS, _NEW, **_BASE, **kw)
    return {rid: list(r.output_ids) for rid, r in sorted(done.items())}


# the matrix revisits the same engine configs; outputs are deterministic
# for a given config, so run each engine once
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
# fused vs reference parity matrix
# ---------------------------------------------------------------------------

class TestFusedParityMatrix:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["kvf32", "kvint8"])
    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_fused_tracks_reference(self, mode, paged, kv_dtype):
        model = _tiny_model()
        kw = dict(mode=mode)
        if mode == "spec":
            kw["spec_k"] = 4
        if paged:
            kw.update(_PAGED)
        if kv_dtype is not None:
            kw["kv_dtype"] = kv_dtype
        ref = _outputs_memo(model, **kw)
        fused = _outputs_memo(model, attn_impl="pallas", **kw)
        assert _drift(fused, ref) <= _BUDGET

    def test_explicit_reference_is_byte_identical_to_default(self):
        """attn_impl='reference' is a NAME for the default path, not a
        third implementation."""
        model = _tiny_model()
        assert _outputs_memo(model, mode="greedy") == \
            _outputs_memo(model, attn_impl="reference", mode="greedy")


# ---------------------------------------------------------------------------
# int8 weight quantization: drift and composition with the fused kernel
# ---------------------------------------------------------------------------

class TestWeightQuantDrift:
    def test_w8_tracks_reference(self):
        model = _tiny_model()
        ref = _outputs_memo(model, mode="greedy")
        w8 = _outputs_memo(model, weight_dtype="int8", mode="greedy")
        assert _drift(w8, ref) <= _BUDGET

    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_fully_quantized_fused_tracks_reference(self, paged):
        """The all-in config — fused kernel + int8 KV + int8 weights —
        stays inside the same budget as each piece alone."""
        model = _tiny_model()
        kw = dict(mode="greedy")
        if paged:
            kw.update(_PAGED)
        ref = _outputs_memo(model, **kw)
        q = _outputs_memo(model, attn_impl="pallas", kv_dtype="int8",
                          weight_dtype="int8", **kw)
        assert _drift(q, ref) <= _BUDGET

    def test_quantize_round_trip_error_bounded(self):
        """Per-output-channel absmax scaling: dequantized weights are
        within half a quantization step of the original (plus f16 scale
        rounding headroom), and the model's param cache is untouched."""
        model = _tiny_model()
        from paddle_tpu.models.llama_decode import _decode_params_of
        params, _ = _decode_params_of(model, 64)
        qp = quantize_decode_weights(params, "int8")
        assert "wq_scale" not in params["layers"][0]  # no cache mutation
        for lp, qlp in zip(params["layers"], qp["layers"]):
            for name in _QUANT_WEIGHTS:
                q, s = qlp[name], qlp[name + "_scale"]
                assert q.dtype == jnp.int8 and s.dtype == jnp.float16
                assert s.shape == (lp[name].shape[1],)
                y = np.asarray(q, np.float32) * np.asarray(s, np.float32)
                step = np.asarray(s, np.float32)[None, :]
                err = np.abs(y - np.asarray(lp[name], np.float32))
                assert np.all(err <= step * 0.5 * 1.02 + 1e-6)


# ---------------------------------------------------------------------------
# tensor parallel: fused kernel + quantized weights on a 4-way mesh
# ---------------------------------------------------------------------------

class TestFusedTP:
    def test_fused_tracks_reference_under_tp(self):
        mesh = _mesh()
        model = _tp_model()
        kw = dict(mode="greedy", **_PAGED)
        ref = _outputs_memo(model, mesh=mesh, **kw)
        fused = _outputs_memo(model, mesh=mesh, attn_impl="pallas",
                              kv_dtype="int8", weight_dtype="int8", **kw)
        assert _drift(fused, ref) <= _BUDGET


# ---------------------------------------------------------------------------
# zero-retrace acceptance
# ---------------------------------------------------------------------------

class TestZeroRetraceFused:
    def test_warm_fused_engine_staggered_wave(self):
        """attn_impl/weight_dtype are static knobs: they specialize the
        programs once at warmup; a second engine serving a LARGER
        staggered wave triggers zero retraces."""
        model = _tiny_model()
        rng = np.random.default_rng(3)

        def wave(n):
            return [rng.integers(1, 200, size=int(p))
                    for p in rng.integers(4, 20, size=n)]

        kw = dict(batch_size=2, max_len=64, decode_chunk=16,
                  attn_impl="pallas", kv_dtype="int8",
                  weight_dtype="int8", **_PAGED)
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
# fallback selection: unsupported geometry -> reference path, loud once
# ---------------------------------------------------------------------------

class TestFallbackSelection:
    def test_fused_supported_geometry_gate(self):
        assert pap.fused_supported("blhd", None, 16, 64) is None
        assert "layout" in pap.fused_supported("bhld", None, 16, 64)
        assert "attn_bias" in pap.fused_supported("blhd", 0.0, 16, 64)
        assert "full-length" in pap.fused_supported("blhd", None, None, 64)
        assert "divide" in pap.fused_supported("blhd", None, 24, 64)
        assert "divide" in pap.fused_supported("blhd", None, 128, 64)

    def test_unsupported_geometry_is_bitwise_reference(self, caplog,
                                                       monkeypatch):
        """chunk_size=None has no fused equivalent: the 'pallas' call
        must produce the EXACT bits of the default path and log the
        downgrade."""
        monkeypatch.setattr(pap, "_warned", set())
        rng = np.random.default_rng(7)
        b, t, h, hkv, d, lmax = 2, 1, 4, 2, 16, 32
        q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(b, t, hkv, d)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, lmax, hkv, d)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, lmax, hkv, d)), jnp.float32)
        lengths = jnp.asarray([5, 9], jnp.int32)
        ref = decode_attention(q, kn, vn, kc, vc, lengths)
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.ops.paged_attention_pallas"):
            got = decode_attention(q, kn, vn, kc, vc, lengths,
                                   attn_impl="pallas")
        for a, b_ in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
        msgs = [r.getMessage() for r in caplog.records
                if "falling back to the reference path" in
                r.getMessage()]
        assert len(msgs) == 1
        assert "chunk_size=None" in msgs[0]

    def test_fallback_logs_once_per_process(self, caplog, monkeypatch):
        monkeypatch.setattr(pap, "_warned", set())
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.ops.paged_attention_pallas"):
            pap.warn_fallback("decode_attention", "reason-a")
            pap.warn_fallback("decode_attention", "reason-a")  # deduped
            pap.warn_fallback("decode_attention", "reason-b")  # new key
        assert len(caplog.records) == 2

    def test_unknown_attn_impl_raises(self):
        with pytest.raises(ValueError, match="unknown attn_impl"):
            ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                          attn_impl="flash")

    def test_unknown_weight_dtype_raises(self):
        with pytest.raises(ValueError,
                           match="unsupported decode weight dtype"):
            ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                          weight_dtype="int4")


# ---------------------------------------------------------------------------
# observability: info gauges, analytic HBM gauge, recorder dispatch detail
# ---------------------------------------------------------------------------

class TestFusedObservability:
    def test_info_gauges_and_analytic_hbm(self):
        model = _tiny_model()
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=2, max_len=64, registry=reg,
                            attn_impl="pallas", weight_dtype="int8")
        kern = reg.get("serving_decode_kernel")
        assert kern.labels(policy="continuous", impl="fused").value == 1
        assert kern.labels(policy="continuous", impl="reference").value == 0
        mode = reg.get("serving_weight_quant_mode")
        assert mode.labels(policy="continuous", mode="int8").value == 1
        assert mode.labels(policy="continuous", mode="off").value == 0
        wbytes = sum(
            lp[n].size + 2 * lp[n + "_scale"].size
            for lp in eng._params["layers"] for n in _QUANT_WEIGHTS)
        assert reg.get("serving_hbm_gb_per_tok_w8").labels(
            policy="continuous").value == pytest.approx(wbytes / 1e9)

    def test_reference_engine_reads_reference_and_off(self):
        reg = MetricsRegistry()
        ServingEngine(_tiny_model(), batch_size=2, max_len=64, registry=reg)
        kern = reg.get("serving_decode_kernel")
        assert kern.labels(policy="continuous", impl="reference").value == 1
        assert kern.labels(policy="continuous", impl="fused").value == 0
        mode = reg.get("serving_weight_quant_mode")
        assert mode.labels(policy="continuous", mode="off").value == 1
        assert mode.labels(policy="continuous", mode="int8").value == 0
        assert reg.get("serving_hbm_gb_per_tok_w8").labels(
            policy="continuous").value == 0

    def test_recorder_dispatch_events_carry_knobs(self):
        model = _tiny_model()
        eng = ServingEngine(model, batch_size=2, max_len=64, recorder=True,
                            attn_impl="pallas", weight_dtype="int8")
        eng.submit(Request(_PROMPTS[0], 4))
        eng.run()
        dispatches = [e for e in eng.recorder.events()
                      if e["kind"] == "dispatch"]
        assert dispatches
        assert all(e["attn_impl"] == "fused" for e in dispatches)
        assert all(e["weight_dtype"] == "int8" for e in dispatches)


# ---------------------------------------------------------------------------
# kernel-level: the pieces the TPU lowering forced (PR 21) — f16 scale
# leaves as int16 bits converted with integer math, the [.., Hkv, C]
# scale view — against XLA's own casts and the reference chunked read
# ---------------------------------------------------------------------------

class TestScaleBitHelpers:
    def test_f16_bits_to_f32_is_exact_for_every_half(self):
        bits = np.arange(65536, dtype=np.uint16)
        got = np.asarray(pap.f16_bits_to_f32(
            jnp.asarray(bits.view(np.int16).astype(np.int32))))
        want = bits.view(np.float16).astype(np.float32)
        # bit for bit, NaN payloads included
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))

    def test_f32_to_f16_bits_is_xlas_cast(self):
        rng = np.random.default_rng(7)
        halves = np.arange(0x7C00, dtype=np.uint16).view(np.float16) \
            .astype(np.float64)
        mids = ((halves[:-1] + halves[1:]) / 2).astype(np.float32)
        x = np.concatenate([
            halves.astype(np.float32), mids, -mids,
            np.nextafter(mids, np.float32(np.inf)),
            np.nextafter(mids, np.float32(-np.inf)),
            rng.integers(0, 2 ** 32, 500_000, dtype=np.uint64)
            .astype(np.uint32).view(np.float32),
            np.array([65504, 65519.99, 65520, 65536, 1e30, np.inf, -np.inf,
                      0.0, -0.0, 2.0 ** -24, 2.0 ** -25, 2.0 ** -14],
                     np.float32)])
        got = (np.asarray(pap.f32_to_f16_bits(jnp.asarray(x)))
               & 0xFFFF).astype(np.uint16)
        want = np.asarray(jnp.asarray(x).astype(jnp.float16)).view(np.uint16)
        nan = np.isnan(x)
        assert np.array_equal(got[~nan], want[~nan])
        # a NaN stays a NaN (the poison-quarantine signal rides the scale)
        assert np.all((got[nan] & 0x7C00) == 0x7C00)
        assert np.all((got[nan] & 0x03FF) != 0)

    def test_scale_view_round_trips_and_keeps_heads_off_the_lanes(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (3, 16, 4)).astype(np.float16))
        v = pap.scale_view(x)
        assert v.shape == (3, 4, 16) and v.dtype == jnp.int16
        back = pap.scale_unview(v, x)
        assert np.array_equal(np.asarray(back).view(np.uint16),
                              np.asarray(x).view(np.uint16))
        col = pap.head_scale(v[1], jnp.int32(2))              # [16, 1] f32
        assert np.array_equal(np.asarray(col)[:, 0],
                              np.asarray(x[1, :, 2], np.float32))


class TestFusedDecodeKernelDirect:
    @pytest.mark.parametrize("t", [1, 3], ids=["step", "verify3"])
    @pytest.mark.parametrize("kind", ["dense-f32", "paged-f32",
                                      "dense-int8", "paged-int8"])
    def test_matches_reference_chunked_read(self, kind, t):
        """fused_decode_attention against the reference _attend_chunked on
        the same caches: live rows agree to f32 reassociation, parked and
        short rows included; a NaN scale in ANOTHER kv head never leaks."""
        from paddle_tpu.ops.decode_attention import (
            _attend_chunked, _q8_quantize)

        rng = np.random.default_rng(11)
        b, hkv, g, d, c, lmax = 3, 2, 2, 16, 8, 32
        paged, quant = kind.startswith("paged"), kind.endswith("int8")
        n = b * lmax // c
        shape = (n, c, hkv, d) if paged else (b, lmax, hkv, d)

        def cache():
            x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
            if not quant:
                return x
            qd, qs = _q8_quantize(x)
            # poison kv head 1's scales on the first position of every
            # block/row: head 0's outputs must not see it
            return qd, qs.at[:, 0, 1].set(jnp.float16(np.nan))

        k, v = cache(), cache()
        table = (jnp.asarray(rng.permutation(n).reshape(b, lmax // c),
                             jnp.int32) if paged else None)
        lengths = jnp.asarray([5, lmax, 17], jnp.int32)   # row 1 is parked
        qg = jnp.asarray(rng.standard_normal(
            (b, hkv, g, t, d)).astype(np.float32))
        q_pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        ref = _attend_chunked(qg, k, v, lengths, q_pos, 0.25, "blhd", None,
                              c, table)
        out = pap.fused_decode_attention(qg, k, v, lengths, 0.25, c,
                                         block_table=table)
        live = [0, 2]
        heads = [0] if quant else [0, 1]
        np.testing.assert_allclose(
            np.asarray(out)[live][:, heads], np.asarray(ref)[live][:, heads],
            rtol=2e-5, atol=2e-5)
        if quant:   # the poisoned head reports its poison, like the reference
            assert np.isnan(np.asarray(out)[live][:, 1]).all()
            assert np.isnan(np.asarray(ref)[live][:, 1]).all()
