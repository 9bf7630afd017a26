"""Fused Pallas paged-attention kernel for the decode hot path.

The reference chunked decode read (ops/decode_attention.py:_attend_chunked)
is a ``lax.while_loop`` of gather -> dequant -> online-softmax stages that
XLA schedules as separate HBM round-trips: each chunk's int8 block is
gathered to HBM-resident f32, re-read by the score einsum, and the partial
softmax state bounces through registers between loop-carried arrays.  This
module fuses the whole read into ONE Pallas kernel per (batch row, kv
head), vLLM-PagedAttention-style:

* **Single VMEM residency per KV chunk.**  Grid ``(B, Hkv, n_chunks)``
  with the chunk axis minor: each program receives one ``[C, D]`` K tile
  and one V tile straight from HBM into VMEM, dequantizes int8 in-place
  (the f32 values never exist in HBM), scores against the resident
  ``[G*T, D]`` query tile and folds the result into the flash-style
  running (max, denominator, accumulator) carried in VMEM scratch across
  the chunk sweep — exactly the streamed layout of
  ops/flash_attention.py's ``_fwd_kernel_streamed``.
* **The block-table gather IS the index map.**  Paged mode prefetches the
  ``[B, W]`` block table as a scalar operand
  (``pltpu.PrefetchScalarGridSpec``): logical chunk ``i`` of row ``b``
  loads pool block ``clip(table[b, i], 0, N-1)`` directly — no gathered
  copy of the chunk is ever materialized.  The clip reproduces the
  reference's ``mode="clip"`` semantics: a sentinel (``>= N``) or stale
  entry reads an arbitrary REAL block whose rows the causal mask zeroes,
  never a NaN-filling OOB default.
* **Reference-exact masking.**  Per row, chunk ``i`` is live for key
  position ``k_idx <= q_pos`` with masked lanes explicitly zeroed after
  the exp (``p = where(live, exp(s - m_new), 0)``) — the same
  fully-masked-chunk pollution guard as the reference.  Slots parked by
  ``masked_lengths`` (offset ``>= lmax``) pass the causal test everywhere
  and come back as finite garbage the scheduler ignores, exactly like the
  reference rows.
* **Per-row adaptive compute.**  ``lengths`` rides the scalar prefetch
  too: a chunk past ``ceil((eff + T) / C)`` for its row (``eff = 0`` for
  parked slots — the reference's trip-count exclusion) skips its compute
  entirely via ``pl.when``, so MXU work tracks each row's real context.
* **CPU = interpret mode.**  ``interpret`` defaults to
  ``jax.default_backend() != "tpu"`` so the parity suite runs the same
  kernel logic on the virtual-device CPU platform; the flag is never the
  literal ``True`` in product code (tpu-lint PTL012 polices exactly that
  — interpret mode silently ships a ~100x slower kernel).  Nothing on
  the chip path leans on the rule: ``chip_smoke.py`` asserts the
  ``tpu_custom_call`` is in the engine's compiled decode program, and
  ``tests/test_chip_compile.py`` hands the TPU compiler the kernel at
  real widths with ``interpret=False``.
* **What the TPU lowering needs** (it compiles for v5e and runs inside
  the engine since PR 21).  Mosaic tiles the last two dims of a block
  (8, 128), so one kv head is never sliced out of the second-to-last
  dim: the cache data goes in as its ``[.., C, Hkv*D]`` view (head ``h``
  = lane block ``h``; ``D`` a multiple of 128) and the int8 caches' f16
  scale leaf as ``scale_view`` — int16 bits, ``[.., Hkv, C]`` (the v5e
  vector unit has no f16; ``C`` a multiple of 128).  Every scalar
  literal in the kernel is typed: the package runs under x64, where a
  Python ``0`` traces as an i64 Mosaic cannot narrow.  Cost, from the
  compiled program: the ``[.., Hkv*D]`` view is NOT the pool's physical
  order on a TPU (it tiles ``(Hkv, D)``), so XLA relayouts each layer's
  whole K and V cache per call — PERF.md has the finding and the view
  that would be free.

Geometry the kernel does NOT cover falls back to the bitwise reference
path: ``fused_decode_supported`` returns the reason and ``warn_fallback``
logs it once per process per (call-site, reason) — a silent fallback
would ship while_loop speed under an ``attn_impl="pallas"`` flag, and a
shared key would let a decode downgrade silence a later prefill one.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fused_decode_attention", "fused_decode_supported",
           "fused_supported", "warn_fallback"]

_NEG_INF = -1e30

_LOG = logging.getLogger(__name__)
# once-per-process fallback log: (where, reason) pairs already warned.
# Serving dispatches thousands of steps through one traced program — the
# fallback decision happens at trace time, but a per-trace log line would
# still spam every warmup; dedup makes the downgrade loud exactly once.
_warned = set()


def fused_decode_supported(layout, attn_bias, chunk_size, lmax):
    """Geometry gate for the fused DECODE kernel: ``None`` when
    supported, else a human-readable reason string (the fallback log
    line).  The prefill kernel has its own gate —
    ops/prefill_attention_pallas.py ``fused_prefill_supported`` — with
    prefill-specific reasons, so a decode downgrade and a prefill
    downgrade are distinct ``warn_fallback`` keys and neither silences
    the other.

    The kernel covers the serving hot path — ``blhd`` caches (dense or
    paged), no additive bias, a chunked read whose chunk divides the
    logical span (uniform Pallas blocks; the reference's clamped-tail
    re-read has no block-uniform equivalent).  Everything else is the
    reference ``lax.while_loop``'s job.
    """
    if layout != "blhd":
        return f"layout {layout!r} (only 'blhd' is fused)"
    if attn_bias is not None:
        return "attn_bias is not fused"
    if chunk_size is None:
        return "chunk_size=None selects the single full-length read"
    if int(chunk_size) > lmax or lmax % int(chunk_size):
        return (f"chunk_size ({int(chunk_size)}) must divide the cache "
                f"span ({lmax}) for uniform kernel blocks")
    return None


#: Back-compat alias (pre-split name); the decode gate is the one this
#: module owns.
fused_supported = fused_decode_supported


def warn_fallback(where, reason, knob="attn_impl"):
    """Log the fused->reference downgrade once per process per
    (call-site, reason) key: a prefill fallback at one call site is
    never silenced by an earlier decode fallback at another."""
    key = (where, reason)
    if key not in _warned:
        _warned.add(key)
        _LOG.warning(
            "%s: %s='pallas' requested but unsupported — %s; "
            "falling back to the reference path (bitwise the "
            "%s=None path, logged once per process)",
            where, knob, reason, knob)


def f16_bits_to_f32(h):
    """f16 -> f32 on the raw bit pattern (``h``: int32 whose low 16 bits
    are the half; upper bits ignored).  The v5e vector unit has no f16
    type — Mosaic refuses an f16 load — so the int8 caches' f16 scale
    leaves enter the kernels as their int16 bit view and widen here,
    exactly (every f16 value, subnormals, inf and NaN included)."""
    i32 = jnp.int32
    sign = (h & i32(0x8000)) << 16
    exp = (h >> 10) & i32(0x1F)
    man = h & i32(0x3FF)
    # normal: rebias 15 -> 127; all-ones exponent (inf/NaN) stays all-ones
    exp32 = jnp.where(exp == 31, i32(255), exp + i32(112))
    normal = jax.lax.bitcast_convert_type(
        sign | (exp32 << 23) | (man << 13), jnp.float32)
    # subnormal: man * 2^-24, exact in f32
    sub = man.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(exp == 0, sub, normal)


def f32_to_f16_bits(x):
    """f32 -> f16 bit pattern (int32, low 16 bits), round-to-nearest-even
    — bit for bit XLA's ``astype(float16)`` — for the kernels that WRITE
    scale leaves (see ``f16_bits_to_f32``)."""
    i32 = jnp.int32
    b = jax.lax.bitcast_convert_type(x, i32)
    sign = (b >> 16) & i32(0x8000)
    a = b & i32(0x7FFFFFFF)
    # normal range: round the 13 dropped mantissa bits to nearest-even
    # (the carry may ripple into the exponent — that is the right answer),
    # rebias 127 -> 15, saturate to inf
    rounded = (a + i32(0xFFF) + ((a >> 13) & i32(1))) >> 13
    normal = jnp.minimum(rounded - i32(112 << 10), i32(0x7C00))
    # below 2^-14 the half is subnormal: an integer count of 2^-24 steps
    # (1024 steps == the smallest normal, whose bits are 0x0400)
    ax = jax.lax.bitcast_convert_type(a, jnp.float32)
    sub = jnp.round(ax * jnp.float32(2.0 ** 24)).astype(i32)
    h = jnp.where(a < i32(0x38800000), sub, normal)
    h = jnp.where(a > i32(0x7F800000), i32(0x7E00), h)     # NaN
    return sign | h


def _vec_transpose(x, to_col):
    """[1, n] <-> [n, 1] for an int32 vector, as a select against the
    identity and a reduce — exact, and cheap at kernel tile sizes (n*n
    selects); Mosaic has no general small-vector transpose."""
    n = x.shape[1] if to_col else x.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # dtype pinned: under x64 an int32 sum widens to int64
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(x, (n, n)), jnp.int32(0)),
                   axis=1 if to_col else 0, keepdims=True, dtype=jnp.int32)


def head_scale(bits, h):
    """Kv head ``h``'s row of an [Hkv, rows] scale tile (int16 f16 bits,
    the ``scale_view`` layout) as the f32 [rows, 1] multiplier of a
    [rows, D] data tile."""
    b32 = bits.astype(jnp.int32)
    mine = jax.lax.broadcasted_iota(jnp.int32, b32.shape, 0) == h
    row = jnp.sum(jnp.where(mine, b32, jnp.int32(0)), axis=0, keepdims=True,
                  dtype=jnp.int32)
    return f16_bits_to_f32(_vec_transpose(row, to_col=True))


def data_view(x):
    """A cache data leaf [.., C, Hkv, D] as the kernels take it:
    [.., C, Hkv*D].  Mosaic tiles the last two dims of a block (8, 128),
    so a [.., 1, D] tile slicing one kv head out of the second-to-last dim
    is refused; in this view head ``h`` is lane block ``h`` of a (C, D)
    tile (D a multiple of 128 on the chip)."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def scale_view(x):
    """The int8 caches' f16 scale leaf [N, C, Hkv] as the kernels take
    it: int16 bits, kv heads second-to-last — [N, Hkv, C].  Mosaic pads
    a 4-wide minor dim to 128 lanes and then refuses any DMA window on
    it; with C minor, a head's scales are a lane-dense row and block /
    DMA windows are whole (Hkv, rows) tiles.  This is also the order the
    TPU stores the leaf in (minor_to_major {1,2,0}: XLA keeps the small
    dim off the lanes), so on the chip the view is a bitcast."""
    return jax.lax.bitcast_convert_type(jnp.swapaxes(x, 1, 2), jnp.int16)


def scale_unview(x, like):
    """Inverse of ``scale_view`` (for kernels that write scale leaves)."""
    return jnp.swapaxes(jax.lax.bitcast_convert_type(x, like.dtype), 1, 2)


def _fused_kernel(*refs, chunk, lmax, t, group, scale, quant, paged):
    """One (batch row, kv head, chunk) step of the fused online softmax.

    refs (scalar-prefetch first, per PrefetchScalarGridSpec): lengths
    [B] (+ the [B, W] block table when paged, consumed by the index maps
    only), then q [1, 1, G*T, D], k/v chunk tiles [1, C, D] — head h's
    lane block of the [.., Hkv*D] view — (+ their [1, Hkv, C] int16
    ``scale_view`` tiles when quant), the output block [1, 1, G*T, D],
    and VMEM scratch acc [G*T, D] / m, l [8, G*T] (sublane-replicated
    running state, the flash_attention idiom).
    """
    if paged:
        len_ref, _tbl_ref, *refs = refs
    else:
        len_ref, *refs = refs
    if quant:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    n_chunks = pl.num_programs(2)
    rows = group * t

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # the reference trip count, per ROW instead of per batch: parked slots
    # (offset >= lmax) contribute eff = 0, so chunks past a row's live
    # span skip their MXU work (chunk 0 always runs: eff + t >= 1)
    eff = jnp.where(length < lmax, length, jnp.int32(0))
    work = i * chunk < eff + t

    @pl.when(work)
    def _compute():
        q = q_ref[0, 0]                                     # [G*T, D] f32
        k = k_ref[0].astype(jnp.float32)                    # [C, D]
        v = v_ref[0].astype(jnp.float32)
        if quant:
            # int8 dequant in VMEM: the f32 chunk never touches HBM
            k = k * head_scale(ks_ref[0], h)
            v = v * head_scale(vs_ref[0], h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [G*T, C]
        # row r of the [G, T] query tile is step token r % t — built as
        # a 3-D iota whose leading dims merge (Mosaic refuses the 2-D ->
        # 1-D cast of a [G, T] iota)
        q_pos = length + jax.lax.broadcasted_iota(
            jnp.int32, (group, t, chunk), 1).reshape(rows, chunk)
        k_idx = i * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, chunk), 1)
        live = k_idx <= q_pos
        s = jnp.where(live, s, jnp.float32(_NEG_INF))
        m = m_ref[0]
        l = l_ref[0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # explicit zero on masked lanes — the online-softmax pollution
        # guard the reference carries (a fully-masked row has
        # s == m_new == _NEG_INF and exp(0) == 1 otherwise)
        p = jnp.where(live, jnp.exp(s - m_new[:, None]), jnp.float32(0.0))
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == n_chunks - 1)
    def _fin():
        l_safe = jnp.maximum(l_ref[0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def fused_decode_attention(qg, k_cache, v_cache, lengths, scale, chunk,
                           block_table=None, interpret=None):
    """Fused drop-in for the reference ``_attend_chunked`` (blhd, no bias).

    qg ``[B, Hkv, G, T, D]`` f32 queries (the reference's grouped layout);
    caches dense ``[B, Lmax, Hkv, D]`` or — with ``block_table [B, W]`` —
    a paged pool ``[N, C, Hkv, D]``; int8 caches are ``(data, scale)``
    pairs dequantized in-kernel.  ``lengths [B]`` are the PRE-append
    lengths (parked slots at ``>= lmax``).  Returns ``[B, Hkv, G, T, D]``
    f32 — same contract as the reference read, numerically equal up to
    dot-product reassociation (the parity matrix pins the drift budget).
    ``interpret=None`` resolves to ``jax.default_backend() != "tpu"``.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, g, t, d = qg.shape
    c = int(chunk)
    quant = isinstance(k_cache, tuple)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    paged = block_table is not None
    if paged:
        n_chunks = int(block_table.shape[1])
        lmax = n_chunks * c
        n_blocks = int((k_cache[0] if quant else k_cache).shape[0])
    else:
        lmax = int((k_cache[0] if quant else k_cache).shape[1])
        n_chunks = lmax // c
    gt = g * t
    q2 = qg.reshape(b, hkv, gt, d).astype(jnp.float32)
    lengths = lengths.astype(jnp.int32)

    # index maps receive (b, h, i, *scalar_refs); constant dims use
    # ``i * 0`` so the index dtype stays i32 under jax_enable_x64 (the
    # flash_attention.py Mosaic idiom)
    if paged:
        scalars = (lengths, block_table.astype(jnp.int32))

        def blk(tbl, bi, ci):
            # the reference gather's mode="clip": sentinel/stale entries
            # read a real pool block, the causal mask discards its rows
            return jnp.clip(tbl[bi, ci], jnp.int32(0), jnp.int32(n_blocks - 1))

        q_idx = lambda bi, hi, ci, ln, tb: (bi, hi, ci * 0, ci * 0)
        k_idx = lambda bi, hi, ci, ln, tb: (blk(tb, bi, ci), ci * 0, hi)
        s_idx = lambda bi, hi, ci, ln, tb: (blk(tb, bi, ci), ci * 0, ci * 0)
    else:
        scalars = (lengths,)
        q_idx = lambda bi, hi, ci, ln: (bi, hi, ci * 0, ci * 0)
        k_idx = lambda bi, hi, ci, ln: (bi, ci, hi)
        s_idx = lambda bi, hi, ci, ln: (bi, ci * 0, ci)

    # data tiles: head h's lane block of ``data_view``; scale tiles: whole
    # (Hkv, C) blocks of ``scale_view``, the kernel picks its head's row
    kv_spec = pl.BlockSpec((1, c, d), k_idx)
    sc_spec = pl.BlockSpec((1, hkv, c), s_idx)
    in_specs = [pl.BlockSpec((1, 1, gt, d), q_idx)]
    args = [q2]
    if quant:
        in_specs += [kv_spec, sc_spec, kv_spec, sc_spec]
        args += [data_view(k_cache[0]), scale_view(k_cache[1]),
                 data_view(v_cache[0]), scale_view(v_cache[1])]
    else:
        in_specs += [kv_spec, kv_spec]
        args += [data_view(k_cache), data_view(v_cache)]

    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, chunk=c, lmax=lmax, t=t, group=g,
            scale=float(scale), quant=quant, paged=paged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, hkv, n_chunks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, gt, d), q_idx),
            scratch_shapes=[
                pltpu.VMEM((gt, d), jnp.float32),
                pltpu.VMEM((8, gt), jnp.float32),
                pltpu.VMEM((8, gt), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gt, d), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, *args)
    return out.reshape(b, hkv, g, t, d)
