"""Fused Pallas chunked-prefill kernel (``prefill_impl="pallas"``),
overlapped row-parallel TP collectives (``tp_overlap=``), and the
declarative ProgramKey registry through the serving stack.

The load-bearing properties:

- **Exact parity**: greedy token streams with the fused
  attention+append kernel are IDENTICAL to the reference chunked
  prefill across the matrix (paged/dense x kv f32/int8) on a workload
  whose prompt lengths sit below / at / at a multiple of / off a
  multiple of the prefill chunk.  The kernel stages the chunk's own
  rows in VMEM with the reference's exact quantize recipe, so the
  caches it leaves behind are bitwise the reference's.
- **Fallback is loud and bitwise**: geometry the kernel does not cover
  (chunk_size=None, non-dividing spans) drops to the reference path
  byte-identically, logged once per process per (call-site, reason) —
  a prefill downgrade is never silenced by an earlier decode one.
- **One registry**: every static program axis (attn_impl,
  prefill_impl, kv_dtype, weight_dtype, tp_overlap and the three
  speculation axes) flows through the
  single frozen ``ProgramKey`` — validated at construction, hashable,
  and carried whole by the engine and the TP program cache.
- **Zero retraces**: a warmed fused-prefill engine serves a larger
  staggered-admission wave without a single new trace.
- **TP byte-identity**: the 4-way-mesh engine with ``tp_overlap`` on
  emits byte-identical tokens to the single-device engine — segmenting
  the row-parallel matmul moves the schedule, not the math.
"""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.analysis import assert_no_retrace
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.ops import paged_attention_pallas as pap
from paddle_tpu.ops.decode_attention import (
    init_kv_pool, slot_prefill_attention)
from paddle_tpu.ops.prefill_attention_pallas import fused_prefill_supported
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.program_key import PROGRAM_AXES, ProgramKey
from tests.test_serving import _run, _tiny_model
from tests.test_serving_tp import _mesh, _tp_model

_RNG = np.random.default_rng(33)
# prompt lengths below / at / at a multiple of / off a multiple of the
# 16-token prefill chunk — every admission shape the chunk walker emits
_PROMPTS = [_RNG.integers(1, 200, size=p) for p in (5, 16, 32, 23)]
_NEW = [7, 5, 6, 4]

_BASE = dict(batch_size=2, max_len=64, decode_chunk=16, prefill_chunk=16)
_PAGED = dict(kv_block=16, max_live_tokens=2 * 64)

_SPEC_BUDGET = 0.25  # draft/verify may flip on reassociated prefill sums


def _outputs(model, **kw):
    done = _run(model, _PROMPTS, _NEW, **kw)
    return {rid: list(r.output_ids) for rid, r in sorted(done.items())}


_MEMO = {}


def _outputs_memo(model, **kw):
    key = tuple(sorted((k, str(v)) for k, v in kw.items()))
    if key not in _MEMO:
        _MEMO[key] = _outputs(model, **_BASE, **kw)
    return _MEMO[key]


def _drift(a, b):
    diff = total = 0
    for rid in a:
        assert len(a[rid]) == len(b[rid])  # scheduling never drifts
        total += len(a[rid])
        diff += sum(x != y for x, y in zip(a[rid], b[rid]))
    return diff / max(total, 1)


# ---------------------------------------------------------------------------
# fused prefill vs reference parity matrix
# ---------------------------------------------------------------------------

class TestFusedPrefillParityMatrix:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["kvf32", "kvint8"])
    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_greedy_parity_is_exact(self, paged, kv_dtype):
        """The acceptance cell: zero token drift on the greedy matrix.
        The fused kernel leaves bitwise-reference caches behind and the
        tiny f32 model's logit margins absorb the online-softmax
        reassociation in the prefill output."""
        model = _tiny_model()
        kw = dict(mode="greedy")
        if paged:
            kw.update(_PAGED)
        if kv_dtype is not None:
            kw["kv_dtype"] = kv_dtype
        ref = _outputs_memo(model, **kw)
        fused = _outputs_memo(model, prefill_impl="pallas", **kw)
        assert _drift(fused, ref) == 0.0

    # two diagonal cells, slow tier: the greedy matrix above is the
    # tier-1 acceptance cross; spec only needs one dense and one paged
    # witness that draft/verify stays inside the reassociation budget
    @pytest.mark.slow
    @pytest.mark.parametrize("paged,kv_dtype",
                             [(False, None), (True, "int8")],
                             ids=["dense-kvf32", "paged-kvint8"])
    def test_spec_tracks_reference(self, paged, kv_dtype):
        model = _tiny_model()
        kw = dict(mode="spec", spec_k=4)
        if paged:
            kw.update(_PAGED)
        if kv_dtype is not None:
            kw["kv_dtype"] = kv_dtype
        ref = _outputs_memo(model, **kw)
        fused = _outputs_memo(model, prefill_impl="pallas", **kw)
        assert _drift(fused, ref) <= _SPEC_BUDGET

    def test_explicit_reference_is_byte_identical_to_default(self):
        """prefill_impl='reference' NAMES the default path, it is not a
        third implementation."""
        model = _tiny_model()
        assert _outputs_memo(model, mode="greedy") == \
            _outputs_memo(model, prefill_impl="reference", mode="greedy")

    @pytest.mark.slow  # the all-in cell compiles a third program family
    def test_fused_composes_with_fused_decode(self):
        """The all-in config: fused prefill + fused decode read + int8
        KV stays exact on greedy (caches are bitwise either way)."""
        model = _tiny_model()
        kw = dict(mode="greedy", kv_dtype="int8", **_PAGED)
        ref = _outputs_memo(model, **kw)
        allin = _outputs_memo(model, prefill_impl="pallas",
                              attn_impl="pallas", **kw)
        assert _drift(allin, ref) <= _SPEC_BUDGET  # decode kernel drifts
        prefill_only = _outputs_memo(model, prefill_impl="pallas", **kw)
        assert _drift(prefill_only, ref) == 0.0


# ---------------------------------------------------------------------------
# fallback selection: unsupported geometry -> reference path, loud once
# ---------------------------------------------------------------------------

class TestPrefillFallback:
    def test_geometry_gate_names_offending_values(self):
        assert fused_prefill_supported(16, 64, 16, True) is None
        assert fused_prefill_supported(16, 64, 32, False) is None
        assert "chunk_size=None" in fused_prefill_supported(
            None, 64, 16, True)
        r = fused_prefill_supported(24, 64, 24, False)
        assert "24" in r and "64" in r and "divide the cache span" in r
        r = fused_prefill_supported(16, 64, 12, True)
        assert "12" in r and "16" in r and "divide" in r
        # dense appends must not run past the slot row
        r = fused_prefill_supported(8, 72, 48, False)
        assert r is not None and "stay in bounds" in r

    def test_unsupported_geometry_is_bitwise_reference(self, caplog,
                                                       monkeypatch):
        """decode_chunk=None has no fused prefill equivalent: the
        'pallas' engine must emit the EXACT bytes of the default path
        and log the downgrade once."""
        monkeypatch.setattr(pap, "_warned", set())
        model = _tiny_model()
        kw = dict(batch_size=2, max_len=64, decode_chunk=None,
                  prefill_chunk=16)
        ref = _outputs(model, **kw)
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.ops.paged_attention_pallas"):
            got = _outputs(model, prefill_impl="pallas", **kw)
        assert got == ref
        msgs = [r.getMessage() for r in caplog.records
                if "prefill_impl='pallas'" in r.getMessage()]
        assert len(msgs) == 1
        assert "chunk_size=None" in msgs[0]
        assert "slot_prefill_attention" in msgs[0]

    def test_prefill_fallback_not_silenced_by_decode_fallback(
            self, caplog, monkeypatch):
        """Satellite contract: the dedup key is (call-site, reason) —
        one engine downgrading BOTH kernels logs two distinct lines."""
        monkeypatch.setattr(pap, "_warned", set())
        model = _tiny_model()
        kw = dict(batch_size=2, max_len=64, decode_chunk=None,
                  prefill_chunk=16)
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.ops.paged_attention_pallas"):
            _outputs(model, prefill_impl="pallas", attn_impl="pallas",
                     **kw)
        msgs = [r.getMessage() for r in caplog.records]
        assert any("prefill_impl='pallas'" in m for m in msgs)
        assert any("attn_impl='pallas'" in m for m in msgs)

    def test_warn_key_is_callsite_and_reason(self, caplog, monkeypatch):
        monkeypatch.setattr(pap, "_warned", set())
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.ops.paged_attention_pallas"):
            pap.warn_fallback("site_a", "reason-1")
            pap.warn_fallback("site_a", "reason-1")   # deduped
            pap.warn_fallback("site_b", "reason-1")   # new call site
            pap.warn_fallback("site_a", "reason-2")   # new reason
        assert len(caplog.records) == 3

    def test_unknown_prefill_impl_raises_at_construction(self):
        with pytest.raises(ValueError, match="unknown prefill_impl"):
            ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                          prefill_impl="triton")


# ---------------------------------------------------------------------------
# paged chunk contract: the divisibility error names the offending values
# ---------------------------------------------------------------------------

class TestPagedChunkContract:
    def test_error_names_chunk_and_block(self):
        k_cache, v_cache = init_kv_pool(4, 16, 2, 8, "float32")
        tbl = jnp.zeros((1, 2), jnp.int32)
        q = jnp.zeros((1, 4, 4, 8), jnp.float32)
        kn = jnp.zeros((1, 4, 2, 8), jnp.float32)
        with pytest.raises(ValueError,
                           match=r"chunk_size=12 with kv_block=16"):
            slot_prefill_attention(q, kn, kn, k_cache, v_cache,
                                   jnp.int32(0), jnp.int32(0),
                                   chunk_size=12, block_table=tbl)
        with pytest.raises(ValueError,
                           match=r"chunk_size=None with kv_block=16"):
            slot_prefill_attention(q, kn, kn, k_cache, v_cache,
                                   jnp.int32(0), jnp.int32(0),
                                   chunk_size=None, block_table=tbl)


# ---------------------------------------------------------------------------
# the ProgramKey registry: one declarative definition of the static axes
# ---------------------------------------------------------------------------

class TestProgramKeyRegistry:
    def test_registry_covers_all_eight_axes_in_order(self):
        assert tuple(ax.name for ax in PROGRAM_AXES) == (
            "attn_impl", "prefill_impl", "kv_dtype", "weight_dtype",
            "tp_overlap", "draft_source", "spec_depth", "spec_tree")

    def test_enum_axis_validation_names_axis_and_allowed(self):
        with pytest.raises(ValueError, match="unknown attn_impl 'flash'"):
            ProgramKey(attn_impl="flash")
        with pytest.raises(ValueError,
                           match="unknown prefill_impl 'triton'"):
            ProgramKey(prefill_impl="triton")
        with pytest.raises(ValueError, match="unknown kv_dtype 'int4'"):
            ProgramKey(kv_dtype="int4")

    def test_segments_axis_validation(self):
        with pytest.raises(ValueError, match="tp_overlap"):
            ProgramKey(tp_overlap=1)
        with pytest.raises(ValueError, match="tp_overlap"):
            ProgramKey(tp_overlap=True)  # bool is not a segment count
        assert ProgramKey(tp_overlap=2).tp_overlap == 2
        assert ProgramKey().tp_overlap is None

    def test_hashable_cache_key_semantics(self):
        a = ProgramKey(prefill_impl="pallas", kv_dtype="int8")
        b = ProgramKey(prefill_impl="pallas", kv_dtype="int8")
        c = a.replace(tp_overlap=2)
        d = {a: 1}
        assert d[b] == 1 and c not in d
        with pytest.raises(ValueError):
            a.replace(tp_overlap=0)  # replace re-validates

    def test_engine_composes_one_key_from_its_knobs(self):
        """The acceptance property: every static knob flows through
        exactly one registry value — the engine's ``_pk`` (the three
        speculation axes stay at their off default on a greedy engine)."""
        eng = ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                            prefill_chunk=16, decode_chunk=16,
                            attn_impl="pallas", prefill_impl="pallas",
                            kv_dtype="int8", weight_dtype="int8",
                            tp_overlap=2)
        assert eng._pk == ProgramKey(
            attn_impl="pallas", prefill_impl="pallas", kv_dtype="int8",
            weight_dtype="int8", tp_overlap=2)
        assert eng._pk.axes() == (
            ("attn_impl", "pallas"), ("prefill_impl", "pallas"),
            ("kv_dtype", "int8"), ("weight_dtype", "int8"),
            ("tp_overlap", 2), ("draft_source", None),
            ("spec_depth", None), ("spec_tree", None))

    def test_engine_rejects_bad_tp_overlap(self):
        with pytest.raises(ValueError, match="tp_overlap"):
            ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                          tp_overlap=1)


# ---------------------------------------------------------------------------
# zero-retrace acceptance: warm fused-prefill engine, staggered admission
# ---------------------------------------------------------------------------

class TestZeroRetracePrefillFused:
    def test_warm_fused_prefill_staggered_wave(self):
        """prefill_impl rides the ProgramKey static: warmup specializes
        the chunked-prefill program once; a second engine serving a
        LARGER staggered wave (every prompt-length-vs-chunk alignment)
        triggers zero retraces."""
        model = _tiny_model()
        rng = np.random.default_rng(5)

        def wave(n):
            return [rng.integers(1, 200, size=int(p))
                    for p in rng.integers(4, 33, size=n)]

        kw = dict(batch_size=2, max_len=64, decode_chunk=16,
                  prefill_chunk=16, prefill_impl="pallas",
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
# tensor parallel: overlapped collectives keep the byte-identity contract
# ---------------------------------------------------------------------------

class TestTPOverlapByteIdentity:
    def test_tp_overlap_byte_identical_to_single_device(self):
        """Segmenting the row-parallel wo/down matmul + psum reorders
        the schedule, never the per-element dot products: the 4-way
        mesh engine with tp_overlap=2 and fused prefill emits the exact
        token bytes of the single-device engine."""
        mesh = _mesh()
        model = _tp_model()
        kw = dict(mode="greedy", batch_size=2, max_len=64,
                  decode_chunk=16, prefill_chunk=16,
                  prefill_impl="pallas", **_PAGED)
        single = _outputs(model, **kw)
        tp = _outputs(model, mesh=mesh, tp_overlap=2, **kw)
        assert tp == single

    def test_overlap_off_matches_overlap_on(self):
        mesh = _mesh()
        model = _tp_model()
        kw = dict(mode="greedy", batch_size=2, max_len=64,
                  decode_chunk=16, prefill_chunk=16, **_PAGED)
        plain = _outputs(model, mesh=mesh, **kw)
        seg = _outputs(model, mesh=mesh, tp_overlap=2, **kw)
        assert plain == seg


# ---------------------------------------------------------------------------
# observability: info gauges, overlap gauge, recorder dispatch detail
# ---------------------------------------------------------------------------

class TestPrefillObservability:
    def test_prefill_kernel_and_overlap_gauges(self):
        reg = MetricsRegistry()
        ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                      prefill_chunk=16, decode_chunk=16, registry=reg,
                      prefill_impl="pallas", tp_overlap=3)
        kern = reg.get("serving_prefill_kernel")
        assert kern.labels(policy="continuous", impl="fused").value == 1
        assert kern.labels(policy="continuous",
                           impl="reference").value == 0
        assert reg.get("serving_tp_overlap_mode").labels(
            policy="continuous").value == 3

    def test_reference_engine_reads_reference_and_zero(self):
        reg = MetricsRegistry()
        ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                      registry=reg)
        kern = reg.get("serving_prefill_kernel")
        assert kern.labels(policy="continuous",
                           impl="reference").value == 1
        assert kern.labels(policy="continuous", impl="fused").value == 0
        assert reg.get("serving_tp_overlap_mode").labels(
            policy="continuous").value == 0

    def test_recorder_dispatch_events_carry_prefill_impl(self):
        eng = ServingEngine(_tiny_model(), batch_size=2, max_len=64,
                            prefill_chunk=16, decode_chunk=16,
                            recorder=True, prefill_impl="pallas")
        eng.submit(Request(_PROMPTS[0], 4))
        eng.run()
        dispatches = [e for e in eng.recorder.events()
                      if e["kind"] == "dispatch"]
        assert dispatches
        assert all(e["prefill_impl"] == "fused" for e in dispatches)


# ---------------------------------------------------------------------------
# kernel-level: the fused attention + append against the reference
# scatter + read on the same caches — the cache it leaves behind is
# BITWISE the reference's (int8 data and f16 scales included), across the
# append geometries (chunk < / = / spanning two cache blocks)
# ---------------------------------------------------------------------------

class TestFusedPrefillKernelDirect:
    @pytest.mark.parametrize("t,c", [(8, 16), (16, 16), (32, 16)],
                             ids=["T<C", "T=C", "T=2C"])
    @pytest.mark.parametrize("kind", ["dense-f32", "paged-f32",
                                      "dense-int8", "paged-int8"])
    def test_chunk_chain_matches_reference(self, kind, t, c):
        from paddle_tpu.ops.decode_attention import init_kv_cache

        rng = np.random.default_rng(5)
        b, hkv, g, d, lmax = 2, 2, 2, 16, 128
        paged = kind.startswith("paged")
        dtype = "int8" if kind.endswith("int8") else jnp.float32
        if paged:
            n = b * lmax // c
            fresh = lambda: init_kv_pool(n, c, hkv, d, dtype)
            table = jnp.asarray(rng.permutation(n).reshape(b, lmax // c),
                                jnp.int32)
        else:
            fresh = lambda: init_kv_cache(b, lmax, hkv, d, dtype)
            table = None
        slot = jnp.int32(1)
        caches = {impl: fresh() for impl in ("reference", "pallas")}
        for step in range(3):                      # offsets 0, T, 2T
            q = jnp.asarray(rng.standard_normal(
                (1, t, hkv * g, d)).astype(np.float32))
            kn = jnp.asarray(rng.standard_normal(
                (1, t, hkv, d)).astype(np.float32))
            vn = jnp.asarray(rng.standard_normal(
                (1, t, hkv, d)).astype(np.float32))
            outs = {}
            for impl in caches:
                kc, vc = caches[impl]
                outs[impl], kc, vc = slot_prefill_attention(
                    q, kn, vn, kc, vc, slot, jnp.int32(step * t),
                    chunk_size=c, block_table=table, prefill_impl=impl)
                caches[impl] = (kc, vc)
            np.testing.assert_allclose(
                np.asarray(outs["pallas"]), np.asarray(outs["reference"]),
                rtol=2e-5, atol=2e-5)
            import jax
            for a, r in zip(jax.tree.leaves(caches["pallas"]),
                            jax.tree.leaves(caches["reference"])):
                assert a.dtype == r.dtype and a.shape == r.shape
                assert np.asarray(a).tobytes() == np.asarray(r).tobytes()
