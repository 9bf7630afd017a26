"""Incremental-decode attention + KV cache + compiled greedy decoding.

Covers VERDICT r4 next-round #6: ops/decode_attention.py,
incubate masked_multihead_attention, and models/llama_decode.decode_greedy
(parity against full-attention recompute / the eager generate loop).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle


def _dense_ref(q_all, k_all, v_all, scale=None):
    """Dense causal attention over the FULL sequence (GQA expanded)."""
    d = q_all.shape[-1]
    if k_all.shape[2] != q_all.shape[2]:
        rep = q_all.shape[2] // k_all.shape[2]
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q_all, k_all, v_all))
    sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    lq, lk = sc.shape[-2], sc.shape[-1]
    sc = jnp.where(jnp.tril(jnp.ones((lq, lk), bool), lk - lq), sc, -1e30)
    p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(q_all.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


class TestDecodeAttention:
    @pytest.mark.parametrize("hkv", [4, 2])  # MHA / GQA
    def test_stepwise_matches_full_recompute(self, hkv):
        """Prefill + N single-token decode steps == dense causal attention
        over the whole sequence."""
        from paddle_tpu.ops.decode_attention import (decode_attention,
                                                     init_kv_cache)

        B, P, N, h, d = 2, 12, 5, 4, 16
        L = P + N
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q_all = jax.random.normal(ks[0], (B, L, h, d), jnp.float32)
        k_all = jax.random.normal(ks[1], (B, L, hkv, d), jnp.float32)
        v_all = jax.random.normal(ks[2], (B, L, hkv, d), jnp.float32)

        kc, vc = init_kv_cache(B, L, hkv, d, "float32")
        lengths = jnp.zeros((B,), jnp.int32)
        outs = []
        out, kc, vc, lengths = decode_attention(
            q_all[:, :P], k_all[:, :P], v_all[:, :P], kc, vc, lengths)
        outs.append(out)
        for t in range(P, L):
            out, kc, vc, lengths = decode_attention(
                q_all[:, t:t + 1], k_all[:, t:t + 1], v_all[:, t:t + 1],
                kc, vc, lengths)
            outs.append(out)
        got = jnp.concatenate(outs, axis=1)
        ref = _dense_ref(q_all, k_all, v_all)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert np.all(np.asarray(lengths) == L)

    def test_ragged_lengths(self):
        """Per-batch lengths: each example attends to its own prefix only."""
        from paddle_tpu.ops.decode_attention import (decode_attention,
                                                     init_kv_cache)

        B, Lmax, h, d = 2, 16, 2, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        k_all = jax.random.normal(ks[1], (B, Lmax, h, d), jnp.float32)
        v_all = jax.random.normal(ks[2], (B, Lmax, h, d), jnp.float32)
        kc, vc = init_kv_cache(B, Lmax, h, d, "float32")
        lens = np.array([5, 9])
        # prime each row's cache with its own prefix (uniform write then
        # per-batch lengths for the probe step)
        for b in range(B):
            kc = kc.at[b, :lens[b]].set(k_all[b, :lens[b]])
            vc = vc.at[b, :lens[b]].set(v_all[b, :lens[b]])
        q = jax.random.normal(ks[0], (B, 1, h, d), jnp.float32)
        knew = k_all[:, 10:11]
        vnew = v_all[:, 10:11]
        out, kc2, vc2, newlen = decode_attention(
            q, knew, vnew, kc, vc, jnp.asarray(lens, jnp.int32))
        assert np.all(np.asarray(newlen) == lens + 1)
        for b in range(B):
            # reference: prefix + the new token
            kk = jnp.concatenate([k_all[b:b + 1, :lens[b]], knew[b:b + 1]], 1)
            vv = jnp.concatenate([v_all[b:b + 1, :lens[b]], vnew[b:b + 1]], 1)
            ref = _dense_ref(q[b:b + 1], kk, vv)
            np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                       np.asarray(ref), rtol=2e-5, atol=2e-5)
            # cache got the new token at position lens[b]
            np.testing.assert_array_equal(np.asarray(kc2[b, lens[b]]),
                                          np.asarray(knew[b, 0]))

    def test_overflow_writes_dropped(self):
        """Writes past Lmax are DROPPED, not clamped onto valid entries."""
        from paddle_tpu.ops.decode_attention import (decode_attention,
                                                     init_kv_cache)

        B, Lmax, h, d = 1, 4, 1, 8
        kc, vc = init_kv_cache(B, Lmax, h, d, "float32")
        k1 = jnp.ones((B, 1, h, d))
        q = jnp.ones((B, 1, h, d))
        _, kc, vc, lengths = decode_attention(
            q, k1, k1, kc, vc, jnp.asarray([Lmax], jnp.int32))
        assert np.all(np.asarray(kc) == 0.0)  # nothing overwritten

    def test_masked_lengths_gates_slot_writes(self):
        """masked_lengths: dead slots' cache writes drop (state preserved
        byte-for-byte), live slots append normally — the serving engine's
        admission/retirement primitive."""
        from paddle_tpu.ops.decode_attention import (decode_attention,
                                                     init_kv_cache,
                                                     masked_lengths)

        B, Lmax, h, d = 3, 8, 1, 4
        rng = np.random.default_rng(0)
        kc, vc = init_kv_cache(B, Lmax, h, d, "float32")
        seeded = jnp.asarray(rng.standard_normal((B, Lmax, h, d)),
                             jnp.float32)
        kc = kc + seeded
        vc = vc + seeded
        live = jnp.asarray([True, False, True])
        lens = masked_lengths(jnp.asarray([2, 5, 7], jnp.int32), live, Lmax)
        np.testing.assert_array_equal(np.asarray(lens), [2, Lmax, 7])
        q = jnp.ones((B, 1, h, d), jnp.float32)
        knew = jnp.full((B, 1, h, d), 9.0, jnp.float32)
        _, kc2, vc2, _ = decode_attention(q, knew, knew, kc, vc, lens)
        # dead slot 1: untouched
        np.testing.assert_array_equal(np.asarray(kc2[1]), np.asarray(kc[1]))
        np.testing.assert_array_equal(np.asarray(vc2[1]), np.asarray(vc[1]))
        # live slots appended at their offsets
        np.testing.assert_array_equal(np.asarray(kc2[0, 2]),
                                      np.asarray(knew[0, 0]))
        np.testing.assert_array_equal(np.asarray(kc2[2, 7]),
                                      np.asarray(knew[2, 0]))
        # admission form: offsets 0 for admitted, Lmax for everyone else
        admit = masked_lengths(jnp.zeros((B,), jnp.int32),
                               jnp.asarray([False, True, False]), Lmax)
        np.testing.assert_array_equal(np.asarray(admit), [Lmax, 0, Lmax])


class TestChunkedDecodeAttention:
    """Parity matrix for the length-adaptive chunked read (chunk_size):
    the online-softmax while_loop must be allclose-identical to the fused
    full-length read on every LIVE row.  Rows parked by masked_lengths
    (offset lmax) are excluded from the trip count BY DESIGN — the full
    path attends over everything while the chunked path reads only the
    chunks live rows need, so parked rows' (documented-garbage, scheduler-
    ignored) outputs differ; the tests assert those stay finite and that
    cache/length updates are byte-equal everywhere."""

    def _pair(self, lens, Lmax, T=1, h=4, hkv=2, d=16, layout="blhd",
              chunk=16, bias=False, seed=0):
        from paddle_tpu.ops.decode_attention import decode_attention

        B = len(lens)
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        q = jax.random.normal(ks[0], (B, T, h, d), jnp.float32)
        kn = jax.random.normal(ks[1], (B, T, hkv, d), jnp.float32)
        vn = jax.random.normal(ks[2], (B, T, hkv, d), jnp.float32)
        shape = (B, Lmax, hkv, d) if layout == "blhd" else (B, hkv, Lmax, d)
        kc = jax.random.normal(ks[3], shape, jnp.float32)
        vc = jax.random.normal(ks[4], shape, jnp.float32)
        ab = (jax.random.normal(ks[5], (B, 1, T, Lmax), jnp.float32)
              if bias else None)
        lengths = jnp.asarray(lens, jnp.int32)
        full = decode_attention(q, kn, vn, kc, vc, lengths, layout=layout,
                                attn_bias=ab)
        chunked = decode_attention(q, kn, vn, kc, vc, lengths, layout=layout,
                                   attn_bias=ab, chunk_size=chunk)
        return full, chunked

    def _assert_parity(self, full, chunked, lens, Lmax):
        fo, fk, fv, fl = full
        co, ck, cv, cl = chunked
        live = np.asarray(lens) < Lmax
        if live.any():
            np.testing.assert_allclose(np.asarray(co)[live],
                                       np.asarray(fo)[live],
                                       rtol=2e-5, atol=2e-5)
        # parked rows: garbage but FINITE (the online-softmax denominator
        # never goes to zero — chunk 0 always runs)
        assert np.isfinite(np.asarray(co)).all()
        # cache and length updates are byte-equal regardless of read path
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(fk))
        np.testing.assert_array_equal(np.asarray(cv), np.asarray(fv))
        np.testing.assert_array_equal(np.asarray(cl), np.asarray(fl))

    @pytest.mark.parametrize("layout", ["blhd", "bhld"])
    def test_ragged_lengths_both_layouts(self, layout):
        lens = [0, 5, 23, 47]
        full, chunked = self._pair(lens, Lmax=48, layout=layout, chunk=16)
        self._assert_parity(full, chunked, lens, 48)

    @pytest.mark.parametrize("layout", ["blhd", "bhld"])
    def test_multi_token_with_bias(self, layout):
        """T>1 (the spec-verify forward) + attn_bias, both layouts."""
        lens = [3, 11, 28]
        full, chunked = self._pair(lens, Lmax=32, T=3, layout=layout,
                                   chunk=8, bias=True, seed=2)
        self._assert_parity(full, chunked, lens, 32)

    def test_non_divisible_lmax_and_odd_chunk(self):
        """lmax % C != 0: the clamped tail chunk re-reads the overlap and
        must mask it out (no double count) — include a full-length row so
        the tail chunk actually runs."""
        for chunk in (16, 7):
            lens = [59, 12, 0]
            full, chunked = self._pair(lens, Lmax=60, chunk=chunk, seed=3)
            self._assert_parity(full, chunked, lens, 60)

    def test_all_retired_batch_stays_finite(self):
        """Every slot parked at offset lmax (masked_lengths): trip count
        clamps to 1, outputs are finite garbage, cache survives untouched
        (writes drop on both paths)."""
        from paddle_tpu.ops.decode_attention import masked_lengths

        Lmax = 32
        lens = np.asarray(masked_lengths(
            jnp.asarray([4, 9, 31], jnp.int32),
            jnp.zeros((3,), bool), Lmax)).tolist()
        full, chunked = self._pair(lens, Lmax=Lmax, chunk=8, seed=4)
        self._assert_parity(full, chunked, lens, Lmax)

    def test_admission_prefill_lengths_zero(self):
        """The serving admission shape: one slot at offset 0 (prefilling),
        the rest parked at lmax — the mix the engine dispatches on every
        admit."""
        lens = [0, 40, 40]
        full, chunked = self._pair(lens, Lmax=40, chunk=16, T=4, seed=5)
        self._assert_parity(full, chunked, lens, 40)

    def test_all_neg_inf_bias_row_stays_finite(self):
        """A -inf attn_bias over every causally visible position of a row
        zeroes the online-softmax denominator; the guarded division must
        return finite garbage (like the full path), never NaN."""
        from paddle_tpu.ops.decode_attention import decode_attention

        B, T, h, hkv, d, Lmax = 2, 1, 4, 2, 16, 32
        ks = jax.random.split(jax.random.PRNGKey(7), 5)
        q = jax.random.normal(ks[0], (B, T, h, d), jnp.float32)
        kn = jax.random.normal(ks[1], (B, T, hkv, d), jnp.float32)
        vn = jax.random.normal(ks[2], (B, T, hkv, d), jnp.float32)
        kc = jax.random.normal(ks[3], (B, Lmax, hkv, d), jnp.float32)
        vc = jax.random.normal(ks[4], (B, Lmax, hkv, d), jnp.float32)
        ab = jnp.zeros((B, 1, T, Lmax), jnp.float32)
        ab = ab.at[0].set(-jnp.inf)  # row 0: every position masked out
        lengths = jnp.asarray([5, 9], jnp.int32)
        out, _, _, _ = decode_attention(q, kn, vn, kc, vc, lengths,
                                        attn_bias=ab, chunk_size=8)
        assert np.isfinite(np.asarray(out)).all()

    def test_chunk_at_least_lmax_falls_back_bitwise(self):
        """chunk_size >= Lmax routes to the fused full read — outputs are
        BITWISE identical, not just allclose."""
        for chunk in (32, 64):
            full, chunked = self._pair([3, 17, 30], Lmax=32, chunk=chunk,
                                       seed=6)
            np.testing.assert_array_equal(np.asarray(chunked[0]),
                                          np.asarray(full[0]))


def _ragged(c, lmax):
    return [0, 1, c - 1, c, c + 1, lmax - 1, 7, 2 * c + 3]


class TestPerSlotRead:
    """The per-slot read of the chunked loop (``_attend_chunked`` with
    ``_slot_block`` engaged): a trip gathers one block of slots in
    descending order of need, and a chunk index gets only as many trips
    as hold a slot that needs it.  Per row the recurrence is the same
    chunks in the same order, and a chunk past a row's length is a no-op
    bit for bit — so every LIVE row is bitwise the batch-wide loop's.
    Parked rows (offset >= lmax) are garbage on either path, finite on
    both."""

    C = 16

    def _operands(self, lens, lmax, t=1, hkv=2, g=2, d=16, seed=0,
                  dtype=jnp.float32):
        b = len(lens)
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        qg = jax.random.normal(ks[0], (b, hkv, g, t, d), jnp.float32)
        kc = jax.random.normal(ks[1], (b, lmax, hkv, d)).astype(dtype)
        vc = jax.random.normal(ks[2], (b, lmax, hkv, d)).astype(dtype)
        lengths = jnp.asarray(lens, jnp.int32)
        q_pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        return qg, kc, vc, lengths, q_pos

    def _chunked(self, ops, monkeypatch, block):
        """One compile of the chunked read with the block rule replaced:
        ``None`` is the batch-wide loop."""
        from paddle_tpu.ops import decode_attention as da

        if block != "rule":
            monkeypatch.setattr(da, "_slot_block", lambda batch: block)
        return np.asarray(jax.jit(
            lambda *a: da._attend_chunked(*a, 0.25, "blhd", None, self.C))(
                *ops))

    CASES = {
        "ragged": dict(lens=_ragged(16, 64), lmax=64),
        "ragged-T5": dict(lens=_ragged(16, 64), lmax=64, t=5),
        "parked-mixed": dict(lens=[64, 3, 64, 40, 64, 64, 17, 64], lmax=64),
        "parked-past-lmax": dict(lens=[70, 3, 64, 40, 99, 64, 17, 16],
                                 lmax=64),
        "tail-chunk": dict(lens=_ragged(16, 60), lmax=60),
        "tail-chunk-T5": dict(lens=_ragged(16, 60), lmax=60, t=5),
        "every-slot-full": dict(lens=[63] * 8, lmax=64),
        "every-slot-short": dict(lens=[2] * 8, lmax=64),
        "block-of-8": dict(lens=list(range(0, 64, 2)), lmax=64, seed=3),
        "block-of-2": dict(lens=_ragged(16, 64), lmax=64, block=2),
        "bf16": dict(lens=_ragged(16, 64), lmax=64, dtype=jnp.bfloat16),
        "gqa-5": dict(lens=_ragged(16, 64), lmax=64, hkv=4, g=5, seed=5),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_live_rows_are_bitwise_the_batch_wide_loops(self, case,
                                                        monkeypatch):
        from paddle_tpu.ops import decode_attention as da

        kw = dict(self.CASES[case])
        lens, lmax = kw.pop("lens"), kw.pop("lmax")
        block = kw.pop("block", "rule")
        assert da._slot_block(len(lens)) is not None
        ops = self._operands(lens, lmax, **kw)
        per_slot = self._chunked(ops, monkeypatch, block)
        wide = self._chunked(ops, monkeypatch, None)
        live = np.asarray(lens) < lmax
        np.testing.assert_array_equal(per_slot[live], wide[live])
        assert np.isfinite(per_slot).all()
        full = np.asarray(da._attend_full(*ops, 0.25, "blhd", None))
        np.testing.assert_allclose(per_slot[live], full[live], rtol=2e-5,
                                   atol=2e-5)

    def test_all_slots_parked_read_nothing(self, monkeypatch):
        """No slot needs a chunk: no trip runs and every row comes back 0
        (the batch-wide loop folds chunk 0 of every slot: garbage)."""
        from paddle_tpu.ops import decode_attention as da

        ops = self._operands([64] * 8, 64)
        assert not self._chunked(ops, monkeypatch, "rule").any()
        assert da.kv_rows_read([64] * 8, 1, self.C, 64) == (0, 0)
        assert da.kv_rows_read([64] * 8, 1, self.C, 64, plain=False) \
            == (8 * self.C, 0)

    @pytest.mark.parametrize("batch", [1, 2, 4, 6])
    def test_a_batch_of_a_block_or_less_is_the_batch_wide_program(
            self, batch, monkeypatch):
        """B = 1 is the prefill chunk's one-slot view: the rule leaves such
        batches (and one the block does not divide) on the batch-wide
        loop, so the slot order is never built."""
        from paddle_tpu.ops import decode_attention as da

        assert da._slot_block(batch) is None

        def never(*a, **k):
            raise AssertionError("the per-slot read engaged")

        monkeypatch.setattr(da, "_slot_order", never)
        lens = [0, 17, 40, 64, 3, 63][:batch]
        ops = self._operands(lens, 64)
        out = self._chunked(ops, monkeypatch, "rule")
        full = np.asarray(da._attend_full(*ops, 0.25, "blhd", None))
        live = np.asarray(lens) < 64
        np.testing.assert_allclose(out[live], full[live], rtol=2e-5,
                                   atol=2e-5)

    @pytest.mark.parametrize("kind", ["paged", "int8", "bhld", "bias"])
    def test_other_geometries_keep_the_batch_wide_loop(self, kind,
                                                       monkeypatch):
        """Paged, int8, ``bhld`` and biased reads at a batch the block
        divides never build the slot order: their loop is the batch-wide
        one, unchanged.  The paged read (batch-wide, through the table) is
        bitwise the dense read of the same rows (per-slot): the pin the
        serving parity matrices rest on."""
        from paddle_tpu.ops import decode_attention as da

        B, lmax, c, hkv, h, d = 8, 64, self.C, 2, 4, 16
        lens = _ragged(c, lmax)
        ks = jax.random.split(jax.random.PRNGKey(11), 6)
        q = jax.random.normal(ks[0], (B, 1, h, d), jnp.float32)
        kn = jax.random.normal(ks[1], (B, 1, hkv, d), jnp.float32)
        vn = jax.random.normal(ks[2], (B, 1, hkv, d), jnp.float32)
        kc = jax.random.normal(ks[3], (B, lmax, hkv, d), jnp.float32)
        vc = jax.random.normal(ks[4], (B, lmax, hkv, d), jnp.float32)
        lengths = jnp.asarray(lens, jnp.int32)
        dense = da.decode_attention(q, kn, vn, kc, vc, lengths,
                                    chunk_size=c)[0]
        built = []
        order = da._slot_order
        monkeypatch.setattr(
            da, "_slot_order",
            lambda *a, **k: built.append(1) or order(*a, **k))
        kw = dict(chunk_size=c)
        if kind == "paged":
            w = lmax // c
            kw["block_table"] = jnp.arange(B * w, dtype=jnp.int32) \
                .reshape(B, w)
            kc, vc = (x.reshape(B * w, c, hkv, d) for x in (kc, vc))
        elif kind == "int8":
            kc, vc = (da._q8_quantize(x) for x in (kc, vc))
        elif kind == "bhld":
            kc, vc = (jnp.swapaxes(x, 1, 2) for x in (kc, vc))
            kw["layout"] = "bhld"
        else:
            kw["attn_bias"] = jnp.zeros((B, 1, 1, lmax), jnp.float32)
        # unjitted, so that the trace runs under the patch
        out = da.decode_attention.__wrapped__(q, kn, vn, kc, vc, lengths,
                                              **kw)[0]
        assert not built
        if kind == "int8":
            np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                       rtol=0.1, atol=0.1)
        else:
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(dense))

    def test_rows_read_follow_the_slots_not_the_longest(self):
        """``kv_rows_read`` by hand: 8 slots, 16-row chunks, blocks of 4.
        Needs (chunks) 1, 1, 1, 2, 2, 4, 1, 3 in descending order 4, 3, 2,
        2 | 1, 1, 1, 1: chunk 0 is needed by 8 slots (2 blocks), chunks 1,
        2 and 3 by 4, 2 and 1 (1 block each): 5 trips x 4 slots x 16 rows.
        The batch-wide rule: 4 chunks x 8 slots x 16 rows."""
        from paddle_tpu.ops.decode_attention import kv_rows_read

        lens = _ragged(16, 64)
        live = sum(n + 1 for n in lens)
        assert kv_rows_read(lens, 1, 16, 64) == (5 * 4 * 16, live)
        assert kv_rows_read(lens, 1, 16, 64, plain=False) \
            == (4 * 8 * 16, live)
        # the full read touches every row
        assert kv_rows_read(lens, 1, None, 64) == (8 * 64, live)


class TestPerSlotReadInTheEngine:
    """The same seeded chat-like stream through ``ServingEngine``: the
    tokens of a request do not depend on which other slots are live, and
    the read's counters say how far it follows the live rows."""

    def _engine(self, **kw):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ServingEngine

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
        model.eval()
        return ServingEngine(model, batch_size=16, max_len=128,
                             prefill_chunk=16, decode_chunk=16, **kw)

    def _stream(self, n):
        """Prompts log-uniform 8-64, outputs 4-16: chat's shape at a
        sixteenth of its lengths."""
        from paddle_tpu.serving import Request

        rng = np.random.default_rng(7)
        out = []
        for _ in range(n):
            p = int(np.exp(rng.uniform(np.log(8), np.log(64))))
            out.append(Request(rng.integers(1, 250, p).astype(np.int32),
                               int(rng.integers(4, 17))))
        return out

    def _served(self, n, **kw):
        eng = self._engine(**kw)
        reqs = self._stream(16)[:n]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [list(r.output_ids) for r in reqs]

    def test_tokens_do_not_depend_on_the_other_slots(self):
        """Every slot live against half the slots empty: the first eight
        requests' token streams are identical."""
        full = self._served(16)
        half = self._served(8)
        assert all(len(t) >= 4 for t in full)
        assert full[:8] == half

    def test_read_ratio_with_half_the_slots_empty(self):
        """A steady stream that keeps 8 of the 16 slots live: read / live
        under 2 where the batch-wide rule, computed in the same run from
        the same host lengths, reads over 3."""
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.ops.decode_attention import kv_rows_read

        reg = MetricsRegistry()
        eng = self._engine(registry=reg)
        seen = {"wide": 0, "read": 0, "live": 0}
        inner = eng._decode_lengths

        def counted(active):
            lens = np.where(active, eng._kv.lengths, 128)
            seen["wide"] += kv_rows_read(lens, 1, 16, 128, plain=False)[0]
            read, live = kv_rows_read(lens, 1, 16, 128)
            seen["read"] += read
            seen["live"] += live
            return inner(active)

        eng._decode_lengths = counted
        waiting, flying = self._stream(24), []
        while waiting or flying:
            flying = [r for r in flying if not r.done]
            while waiting and len(flying) < 8:
                flying.append(waiting.pop(0))
                eng.submit(flying[-1])
            eng.step()
        lbl = dict(policy="continuous")
        read = reg.get("serving_kv_rows_read_total").labels(**lbl).value
        live = reg.get("serving_kv_rows_live_total").labels(**lbl).value
        assert (read, live) == (seen["read"], seen["live"])
        assert read / live < 2 < 3 < seen["wide"] / live


class TestSlotPrefillAttention:
    """The chunked-prefill attention op (ops.slot_prefill_attention):
    chaining [1, P] chunks at offsets 0, P, 2P, ... against one slot of
    the batch cache must reproduce a single monolithic causal pass
    byte-for-byte — each chunk's query i sees exactly the rows written
    before it (previous chunks + intra-chunk causal prefix)."""

    def _chain(self, x_len, P, Lmax=64, B=3, slot=1, h=4, hkv=2, d=16,
               seed=0, chunk_size=None):
        from paddle_tpu.ops.decode_attention import slot_prefill_attention

        # fixed-width source buffers (sliced per chunk) so every P sees
        # the SAME query/key/value values for the real rows
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, Lmax, h, d), jnp.float32)
        kn = jax.random.normal(ks[1], (1, Lmax, hkv, d), jnp.float32)
        vn = jax.random.normal(ks[2], (1, Lmax, hkv, d), jnp.float32)
        kc = jnp.zeros((B, Lmax, hkv, d), jnp.float32)
        vc = jnp.zeros((B, Lmax, hkv, d), jnp.float32)
        outs = []
        for off in range(0, x_len + (-x_len % P), P):
            o, kc, vc = slot_prefill_attention(
                q[:, off:off + P], kn[:, off:off + P], vn[:, off:off + P],
                kc, vc, jnp.int32(slot), jnp.int32(off),
                chunk_size=chunk_size)
            outs.append(np.asarray(o))
        return np.concatenate(outs, axis=1), kc, vc

    @pytest.mark.parametrize("x_len,P", [(5, 16), (16, 16), (32, 8),
                                         (13, 8)])
    def test_chunk_chain_matches_monolithic(self, x_len, P):
        """Prompt lengths <, =, a multiple of, and a non-multiple of the
        chunk width: the chained outputs on the REAL rows equal a single
        full-width pass, and both leave byte-identical cache rows."""
        chained, kc, vc = self._chain(x_len, P)
        mono, kc1, vc1 = self._chain(x_len, x_len)
        np.testing.assert_array_equal(chained[:, :x_len], mono[:, :x_len])
        np.testing.assert_array_equal(np.asarray(kc)[:, :x_len],
                                      np.asarray(kc1)[:, :x_len])
        np.testing.assert_array_equal(np.asarray(vc)[:, :x_len],
                                      np.asarray(vc1)[:, :x_len])

    def test_only_the_slot_row_is_written(self):
        from paddle_tpu.ops.decode_attention import slot_prefill_attention

        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 8, 4, 16), jnp.float32)
        kn = jax.random.normal(ks[1], (1, 8, 2, 16), jnp.float32)
        vn = jax.random.normal(ks[2], (1, 8, 2, 16), jnp.float32)
        kc = jnp.zeros((3, 32, 2, 16), jnp.float32)
        vc = jnp.zeros((3, 32, 2, 16), jnp.float32)
        _, kc, vc = slot_prefill_attention(q, kn, vn, kc, vc,
                                           jnp.int32(2), jnp.int32(0))
        assert not np.asarray(kc)[:2].any() and not np.asarray(vc)[:2].any()
        assert np.asarray(kc)[2, :8].any()
        # rows past the chunk untouched
        assert not np.asarray(kc)[2, 8:].any()

    def test_offset_past_lmax_drops_writes(self):
        """A parked offset (masked_lengths -> lmax) routes every scatter
        out of bounds with mode='drop' — the cache survives bitwise."""
        from paddle_tpu.ops.decode_attention import slot_prefill_attention

        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (1, 8, 4, 16), jnp.float32)
        kn = jax.random.normal(ks[1], (1, 8, 2, 16), jnp.float32)
        vn = jax.random.normal(ks[2], (1, 8, 2, 16), jnp.float32)
        kc = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 2, 16),
                               jnp.float32)
        vc = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 2, 16),
                               jnp.float32)
        out, kc2, vc2 = slot_prefill_attention(q, kn, vn, kc, vc,
                                               jnp.int32(0), jnp.int32(32))
        np.testing.assert_array_equal(np.asarray(kc2), np.asarray(kc))
        np.testing.assert_array_equal(np.asarray(vc2), np.asarray(vc))
        assert np.isfinite(np.asarray(out)).all()


class TestMaskedMultiheadAttention:
    def test_matches_dense_with_mask_and_bias(self):
        import paddle_tpu.incubate.nn.functional as IF

        B, H, D, Lmax, cur = 2, 4, 16, 12, 6
        rng = np.random.default_rng(0)
        x = rng.standard_normal((B, 3 * H * D)).astype("float32")
        bias = rng.standard_normal((3, H, D)).astype("float32")
        cache = np.zeros((2, B, H, Lmax, D), "float32")
        k_prev = rng.standard_normal((B, cur, H, D)).astype("float32")
        v_prev = rng.standard_normal((B, cur, H, D)).astype("float32")
        cache[0, :, :, :cur] = k_prev.transpose(0, 2, 1, 3)
        cache[1, :, :, :cur] = v_prev.transpose(0, 2, 1, 3)
        mask = rng.standard_normal((B, 1, 1, cur + 1)).astype("float32")

        out, cache_out = IF.masked_multihead_attention(
            paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
            bias=paddle.to_tensor(bias), src_mask=paddle.to_tensor(mask))

        xb = x + bias.reshape(-1)
        q, k, v = np.split(xb.reshape(B, 3, H, D), 3, axis=1)
        scale = 1.0 / np.sqrt(D)
        ref_rows = []
        for b in range(B):
            kk = np.concatenate([k_prev[b], k[b]], 0)  # [cur+1, H, D]
            vv = np.concatenate([v_prev[b], v[b]], 0)
            s = np.einsum("ohd,khd->hk", q[b], kk) * scale
            s = s + mask[b, 0, 0][None, :]
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref_rows.append(np.einsum("hk,khd->hd", p, vv).reshape(H * D))
        np.testing.assert_allclose(out.numpy(), np.stack(ref_rows),
                                   rtol=2e-5, atol=2e-5)
        # cache updated at position cur in the reference layout
        co = cache_out.numpy()
        np.testing.assert_allclose(co[0, :, :, cur],
                                   k[:, 0], rtol=1e-6, atol=1e-6)
        assert co.shape == cache.shape

    def test_sequence_lengths_and_unsupported(self):
        import paddle_tpu.incubate.nn.functional as IF

        B, H, D, Lmax = 2, 2, 8, 8
        x = paddle.to_tensor(np.random.randn(B, 3 * H * D).astype("float32"))
        cache = paddle.to_tensor(np.zeros((2, B, H, Lmax, D), "float32"))
        seqlens = paddle.to_tensor(np.array([[0], [3]], dtype="int32"))
        out, cache_out = IF.masked_multihead_attention(
            x, cache_kv=cache, sequence_lengths=seqlens)
        assert list(out.shape) == [B, H * D]
        with pytest.raises(NotImplementedError):
            IF.masked_multihead_attention(
                x, cache_kv=cache, sequence_lengths=seqlens,
                beam_cache_offset=paddle.to_tensor(np.zeros((1,), "int32")))
        with pytest.raises(ValueError):
            IF.masked_multihead_attention(x, cache_kv=cache)


class TestCompiledDecode:
    def test_decode_greedy_matches_eager_generate(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama_decode import decode_greedy

        cfg = LlamaConfig.tiny(dtype="float32")
        model = LlamaForCausalLM(cfg)
        model.eval()
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 256, (2, 7)), dtype="int64")
        eager = model.generate(ids, max_new_tokens=6).numpy()
        compiled = np.asarray(decode_greedy(model, ids, max_new_tokens=6))
        np.testing.assert_array_equal(compiled, eager)

    def test_decode_greedy_tied_embeddings(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama_decode import decode_greedy

        cfg = LlamaConfig.tiny(dtype="float32", tie_word_embeddings=True)
        model = LlamaForCausalLM(cfg)
        model.eval()
        ids = paddle.to_tensor(
            np.random.default_rng(1).integers(0, 256, (1, 5)), dtype="int64")
        eager = model.generate(ids, max_new_tokens=4).numpy()
        compiled = np.asarray(decode_greedy(model, ids, max_new_tokens=4))
        np.testing.assert_array_equal(compiled, eager)


class TestSampledDecode:
    def test_sampling_in_compiled_loop(self):
        """temperature/top-k sampling runs inside the same compiled loop:
        deterministic per seed, different across seeds, tokens restricted
        to plausible ids, and temperature->0 recovers greedy."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama_decode import decode_greedy

        cfg = LlamaConfig.tiny(dtype="float32")
        model = LlamaForCausalLM(cfg)
        model.eval()
        ids = paddle.to_tensor(
            np.random.default_rng(0).integers(0, 256, (2, 6)), dtype="int64")
        a = np.asarray(decode_greedy(model, ids, max_new_tokens=8,
                                     temperature=0.8, top_k=5, seed=1))
        b = np.asarray(decode_greedy(model, ids, max_new_tokens=8,
                                     temperature=0.8, top_k=5, seed=1))
        c = np.asarray(decode_greedy(model, ids, max_new_tokens=8,
                                     temperature=0.8, top_k=5, seed=2))
        np.testing.assert_array_equal(a, b)  # same seed -> same tokens
        assert not np.array_equal(a, c)      # different seed -> different
        assert a.min() >= 0 and a.max() < cfg.vocab_size
        greedy = np.asarray(decode_greedy(model, ids, max_new_tokens=8))
        eager = model.generate(ids, max_new_tokens=8).numpy()
        np.testing.assert_array_equal(greedy, eager)


class TestSpeculativeDecode:
    """decode_speculative (the r5 exceed-the-reference inference item): the
    LOSSLESS property — output byte-identical to plain greedy for ANY
    draft (a bad draft only costs speed, never correctness)."""

    def _make(self, layers, hidden, seed):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(seed)
        cfg = LlamaConfig(
            vocab_size=128, hidden_size=hidden, intermediate_size=hidden * 2,
            num_hidden_layers=layers, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=256,
            dtype="float32")
        return LlamaForCausalLM(cfg)

    def test_lossless_for_any_draft(self):
        from paddle_tpu.models.llama_decode import (decode_greedy,
                                                    decode_speculative)

        target = self._make(3, 64, 0)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, 128, (2, 8)), dtype="int64")
        ref = np.asarray(decode_greedy(target, ids, max_new_tokens=24))

        # random draft: near-zero acceptance -> every round exercises the
        # rejection/rewind path
        bad_draft = self._make(1, 32, 7)
        spec = np.asarray(decode_speculative(target, bad_draft, ids,
                                             max_new_tokens=24, spec_k=3))
        np.testing.assert_array_equal(spec, ref)

        # self-draft: full acceptance -> every round takes the bonus-token
        # (j == k) path; equality also proves cache rollback bookkeeping
        spec_self = np.asarray(decode_speculative(target, target, ids,
                                                  max_new_tokens=24,
                                                  spec_k=3))
        np.testing.assert_array_equal(spec_self, ref)

    def test_spec_k_sweep_and_vocab_guard(self):
        from paddle_tpu.models.llama_decode import (decode_greedy,
                                                    decode_speculative)

        target = self._make(2, 64, 1)
        draft = self._make(1, 64, 2)
        ids = paddle.to_tensor(
            np.random.default_rng(3).integers(0, 128, (1, 5)), dtype="int64")
        ref = np.asarray(decode_greedy(target, ids, max_new_tokens=11))
        for k in (1, 2, 5):
            spec = np.asarray(decode_speculative(target, draft, ids,
                                                 max_new_tokens=11,
                                                 spec_k=k))
            np.testing.assert_array_equal(spec, ref)

        class _V:
            class config:
                vocab_size = 999
        import pytest as _pytest
        with _pytest.raises(ValueError):
            decode_speculative(target, _V(), ids)

    def test_undersized_max_len_rejected(self):
        from paddle_tpu.models.llama_decode import decode_speculative

        target = self._make(2, 64, 1)
        draft = self._make(1, 64, 2)
        ids = paddle.to_tensor(
            np.random.default_rng(4).integers(0, 128, (1, 5)), dtype="int64")
        import pytest as _pytest
        with _pytest.raises(ValueError, match="headroom"):
            # the value that works for decode_greedy (prompt + max_new)
            decode_speculative(target, draft, ids, max_new_tokens=8,
                               max_len=13, spec_k=3)

    def test_ngram_prompt_lookup_lossless(self):
        """draft_model=None: model-free prompt-lookup drafting — lossless
        on random AND repetitive prompts (the lookup-friendly regime where
        acceptance is high and the bonus path runs repeatedly)."""
        from paddle_tpu.models.llama_decode import (decode_greedy,
                                                    decode_speculative)

        target = self._make(3, 64, 0)
        rng = np.random.default_rng(0)
        for prompt in (rng.integers(0, 128, (2, 8)),
                       np.tile(rng.integers(0, 128, (1, 8)), (2, 4))):
            ids = paddle.to_tensor(prompt, dtype="int64")
            ref = np.asarray(decode_greedy(target, ids, max_new_tokens=24))
            spec = np.asarray(decode_speculative(
                target, None, ids, max_new_tokens=24, spec_k=4))
            np.testing.assert_array_equal(spec, ref)

    def test_misuse_errors_are_actionable(self):
        from paddle_tpu.models.llama_decode import decode_speculative

        target = self._make(2, 64, 1)
        ids = paddle.to_tensor(
            np.random.default_rng(5).integers(0, 128, (1, 5)), dtype="int64")
        import pytest as _pytest
        # decode_greedy-style call: ids lands in the draft_model slot
        with _pytest.raises(TypeError, match="draft_model must be"):
            decode_speculative(target, ids)
        with _pytest.raises(ValueError, match="input_ids is required"):
            decode_speculative(target, None)


class TestQkvProjection:
    """``_qkv`` (every serving program's Q/K/V projection) is the three
    plain matmuls reshaped to heads — whatever form it takes to keep the
    compiler from re-laying-out the weights (tests/test_chip_compile.py)."""

    @pytest.mark.parametrize("leaves", ["bf16", "int8", "bf16-mp4"])
    def test_equals_three_plain_matmuls_bitwise(self, leaves):
        from paddle_tpu.models import llama_decode as ld

        hidden, inter, nh, nkv, hd, b, t = 64, 96, 8, 4, 16, 3, 5
        cfg = (nh, nkv, hd, 1e-5)
        ks = iter(jax.random.split(jax.random.PRNGKey(3), 9))

        def w(shape):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * shape[0] ** -0.5).astype(jnp.bfloat16)

        # all seven matmul weights: quantize_decode_weights wants them
        lp = {"ln1": 1 + w((hidden,)), "wq": w((hidden, nh * hd)),
              "wk": w((hidden, nkv * hd)), "wv": w((hidden, nkv * hd)),
              "wo": w((nh * hd, hidden)),
              "gate": w((hidden, inter)), "up": w((hidden, inter)),
              "down": w((inter, hidden))}
        h = jax.random.normal(next(ks), (b, t, hidden),
                              jnp.float32).astype(jnp.bfloat16)
        params = {"layers": [lp]}
        if leaves == "int8":
            params = ld.quantize_decode_weights(params)
            assert params["layers"][0]["wq"].dtype == jnp.int8
        if leaves == "bf16-mp4":
            from jax.sharding import Mesh
            from paddle_tpu.serving.sharding import shard_decode_params
            mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
            params, _ = shard_decode_params(params, mesh)
            assert len(params["layers"][0]["wq"].sharding.device_set) == 4

        def plain(lp, h):
            x = ld._rmsnorm(h, lp["ln1"], cfg[3])
            out = []
            for name, heads in (("wq", nh), ("wk", nkv), ("wv", nkv)):
                y = x @ lp[name].astype(x.dtype)
                if name + "_scale" in lp:
                    y = y * lp[name + "_scale"].astype(x.dtype)
                out.append(y.reshape(b, t, heads, hd))
            return tuple(out)

        got = jax.jit(lambda lp, h: ld._qkv(lp, cfg, h))(
            params["layers"][0], h)
        want = jax.jit(plain)(params["layers"][0], h)
        for g, e in zip(got, want):
            assert g.shape == e.shape and g.dtype == e.dtype
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)),
                np.asarray(e.astype(jnp.float32)))
