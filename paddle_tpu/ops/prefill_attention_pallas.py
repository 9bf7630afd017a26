"""Fused Pallas chunked-prefill kernel: attention + KV append in one pass.

The reference chunked-admission path (ops/decode_attention.py
``slot_prefill_attention``) pays for a prompt chunk twice: a scatter pass
quantizes and writes the chunk's K/V rows into the cache (int8: data plus
f16 scale leaves), then a separate ``lax.while_loop`` re-reads the whole
written prefix — including the rows it just wrote — chunk by chunk
through HBM.  That second pass is exactly the admission-interference tax
the serving bench measures on colocated workers.  This module fuses both
into ONE Pallas kernel per admission chunk, on a ``(kv_head, kv_chunk)``
grid:

* **Quantize-on-append inside the kernel.**  The chunk's new K/V rows are
  staged in VMEM — int8 caches quantize them there with the reference's
  exact absmax-over-head-dim / f16-rounded-scale recipe — and a
  ``pl.when``-guarded async DMA writes them straight into the paged pool
  (or the slot's dense row).  The pool leaves ride in as
  ``memory_space=ANY`` operands aliased to outputs
  (``input_output_aliases``), so the append is in-place: no separate
  scatter pass, no HBM round-trip for the f32 values, and the reference's
  drop semantics hold by construction — an unmapped (sentinel) or
  out-of-span destination block simply never gets a DMA.
* **Exact cross-chunk masking at a device-carried write offset.**  The
  traced ``offset`` scalar rides the scalar prefetch.  The kernel sweeps
  the slot's already-written prefix (blocks with ``j*C < offset``) with a
  double-buffered DMA pipeline — block ``j+1`` streams in while block
  ``j`` folds into the flash-style online softmax — masking ``k_idx <
  offset``; the chunk's own rows fold LAST, from the staged (quantized
  then dequantized, or pool-dtype-cast) VMEM copy, under the intra-chunk
  causal mask.  Attention therefore never depends on the concurrent
  append DMA: the values a query may see are read either from the
  pre-append pool bytes or from the staged registers-resident copy that
  is bitwise what the reference would read back after its scatter.
* **GQA grouping.**  Queries arrive as a resident ``[G*T, D]`` tile per
  kv head — one score matmul per (kv head, chunk), the decode kernel's
  layout.
* **CPU = interpret mode.**  ``interpret`` defaults to
  ``jax.default_backend() != "tpu"`` so the parity suite runs the same
  kernel logic on CPU; never the literal ``True`` in product code
  (tpu-lint PTL012).  On the chip the kernel compiles and runs inside
  the engine (PR 21; ``chip_smoke.py`` asserts the ``tpu_custom_call``
  in the compiled prefill program, ``tests/test_chip_compile.py``
  compiles it at real widths with ``interpret=False``).
* **What the TPU lowering needs.**  The same views as the decode kernel
  (ops/paged_attention_pallas.py): data as ``[.., C, Hkv*D]`` with
  head ``h`` at lane block ``h`` (``D`` a multiple of 128), f16 scale
  leaves as ``scale_view`` int16 bits ``[.., Hkv, C]`` converted with
  integer math (v5e has no f16 vector type; with ``kv_dtype="int8"``
  both ``T`` and ``C`` must be multiples of 128 — a DMA window on the
  lane dim is whole tiles).  A scale leaf keeps every kv head in one
  tile, so heads stage their rows into a shared scratch and the LAST
  head appends them.  DMA window starts carry ``pl.multiple_of`` (the
  alignment contract below, stated so the compiler can use it), and all
  index arithmetic is typed i32 (x64).  Geometry outside this fails in
  the compiler, loudly — there is no interpreter or reference fallback
  on a TPU.

Geometry the kernel does not cover falls back to the bitwise reference
path: ``fused_prefill_supported`` returns the reason and the shared
``warn_fallback`` (ops/paged_attention_pallas.py) logs it once per
process per (call-site, reason) — a prefill downgrade is never silenced
by an earlier decode one.

Alignment contract: the engine's chunked admission walks a prompt in
fixed ``[1, T]`` pieces, so ``offset`` is always a multiple of ``T`` (the
radix prefix match is aligned down to a ``T`` boundary).  The fused
append relies on it — together with the gate's divisibility checks it
makes every write block-aligned.  Callers driving arbitrary offsets must
use the reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.paged_attention_pallas import (
    _vec_transpose, data_view, f16_bits_to_f32, f32_to_f16_bits, head_scale,
    scale_unview, scale_view, warn_fallback,  # noqa: F401 (re-export: the shared fallback logger)
)

__all__ = ["fused_prefill_attention", "fused_prefill_supported",
           "warn_fallback"]

_NEG_INF = -1e30
_Q8_MAX = 127.0


def fused_prefill_supported(chunk_size, lmax, t, paged):
    """Geometry gate for the fused prefill kernel: ``None`` when
    supported, else a human-readable reason string (the fallback log
    line) naming the offending values.

    ``chunk_size`` is the cache-read chunk ``C`` (== the pool block size
    when paged), ``lmax`` the slot's logical span, ``t`` the admission
    chunk width.  The kernel needs uniform read blocks (``C`` divides the
    span), block-aligned appends (``T`` and ``C`` divide one another; a
    chunk otherwise straddles partial blocks the DMA cannot express), and
    — dense only, where writes are not sentinel-guarded — appends that
    cannot run past the row (``T`` divides the span).
    """
    if chunk_size is None:
        return ("chunk_size=None selects the single full-length read "
                "(no uniform blocks for the fused prefill sweep)")
    c = int(chunk_size)
    if c > lmax or lmax % c:
        return (f"chunk_size ({c}) must divide the cache span ({lmax}) "
                "for uniform kernel blocks")
    if t % c and c % t:
        return (f"prefill chunk ({t}) and cache chunk ({c}) must divide "
                "one another for block-aligned fused appends")
    if not paged and lmax % t:
        return (f"prefill chunk ({t}) must divide the cache span "
                f"({lmax}) so dense fused appends stay in bounds")
    return None


def _prefill_kernel(*refs, chunk, t, group, d, scale, quant, paged, nw):
    """One (kv head, kv chunk) step: stage + append at ``j == 0``, fold
    prefix block ``j`` (double-buffered DMA reads), fold the chunk's own
    rows and finalize at the last ``j``.

    refs (scalar-prefetch first): offset [1], ptr ([W] table row when
    paged, [1] slot when dense), q [1, G*T, D], k_new/v_new [T, D] (head
    ``h``'s lane block of the [T, Hkv*D] view), the pool/cache leaves
    (ANY-space, aliased to the pool outputs; data as the [.., C, Hkv*D]
    view, f16 scales as ``scale_view``'s [.., Hkv, C] int16 bits), the
    output tile, and VMEM scratch — running softmax state, staged new
    rows (pool dtype, plus the [Hkv, T] scale bits of EVERY head when
    quant), 2-slot read buffers, and read/write DMA semaphores.

    A DMA window on a scale leaf spans all kv heads (the 4-wide head dim
    is not sliceable): each program stages its head's row into the
    shared [Hkv, T] scratch (the head axis is swept sequentially) and
    the LAST head appends the whole tile; reads fetch whole [Hkv, C]
    tiles and ``head_scale`` picks the row in VMEM.
    """
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        (off_ref, ptr_ref, q_ref, kn_ref, vn_ref,
         kp_ref, ks_ref, vp_ref, vs_ref,
         o_ref, okp_ref, oks_ref, ovp_ref, ovs_ref,
         acc_ref, m_ref, l_ref,
         kwb, ksb, vwb, vsb, kbuf, ksbuf, vbuf, vsbuf,
         rsem, wsem) = refs
    else:
        (off_ref, ptr_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
         o_ref, okp_ref, ovp_ref,
         acc_ref, m_ref, l_ref,
         kwb, vwb, kbuf, vbuf, rsem, wsem) = refs
    i32 = jnp.int32
    f32 = jnp.float32
    h = pl.program_id(0)
    j = pl.program_id(1)
    last_head = h == pl.num_programs(0) - 1
    n_chunks = pl.num_programs(1)
    c = chunk
    rows = group * t
    off = off_ref[0]
    # head h's lane block of a [.., Hkv*D] view
    lanes = pl.ds(pl.multiple_of(h * d, d), d)
    # lax.div/rem, not // and %: the jnp forms trace through a jitted
    # helper whose weak int64 literal Mosaic cannot narrow under x64
    blk0 = jax.lax.div(off, i32(c))
    # the alignment contract (off % t == 0) as facts the compiler can
    # use: a DMA window must start on a tile boundary.  A chunk spanning
    # whole blocks starts at row 0 of its first block.
    off_t = pl.multiple_of(off, t)
    r0 = pl.multiple_of(jax.lax.rem(off, i32(c)), t) if t < c else 0
    # (semaphore indices are i32 scalars for the same reason: a python
    # int index is an i64 Mosaic's memref_slice refuses)

    def write_dmas():
        """The append DMA descriptors (identical at start and wait time):
        (dma, valid) per copy.  Data leaves append per head; scale
        leaves append once, by the last head."""
        pairs = [(kwb, okp_ref, None), (vwb, ovp_ref, None)]
        if quant:
            pairs += [(ksb, oks_ref, last_head), (vsb, ovs_ref, last_head)]
        out = []
        if not paged:
            slot = ptr_ref[0]
            for li, (src, dst, only) in enumerate(pairs):
                dst = dst.at[slot, :, pl.ds(off_t, t)] if only is not None \
                    else dst.at[slot, pl.ds(off_t, t), lanes]
                dma = pltpu.make_async_copy(src, dst, wsem.at[i32(li), i32(0)])
                # always in bounds (gate-checked)
                out.append((dma, off >= 0 if only is None else only))
            return out
        w = ptr_ref.shape[0]
        n_blocks = okp_ref.shape[0]
        rows_m = t if nw == 1 else c
        for mi in range(nw):
            wb = blk0 + i32(mi)
            blk = ptr_ref[jnp.clip(wb, i32(0), i32(w - 1))]
            # the reference scatter's mode="drop": out-of-span or
            # sentinel destinations never get a DMA
            valid = (wb < w) & (blk < n_blocks)
            phys = jnp.clip(blk, i32(0), i32(n_blocks - 1))
            for li, (src, dst, only) in enumerate(pairs):
                if only is not None:
                    src = src.at[:, pl.ds(mi * c, rows_m)]
                    dst = dst.at[phys, :, pl.ds(r0, rows_m)]
                else:
                    src = src.at[pl.ds(mi * c, rows_m)]
                    dst = dst.at[phys, pl.ds(r0, rows_m), lanes]
                dma = pltpu.make_async_copy(src, dst, wsem.at[i32(li), i32(mi)])
                out.append((dma, valid if only is None else valid & only))
        return out

    def read_dmas(ji, sl):
        """Prefix-block read descriptors for chunk ``ji`` into buffer
        slot ``sl`` (identical at start and wait time)."""
        if paged:
            w = ptr_ref.shape[0]
            n_blocks = kp_ref.shape[0]
            # mode="clip": a sentinel entry reads a real block whose rows
            # the offset mask discards, never an OOB default
            blk = jnp.clip(ptr_ref[jnp.clip(ji, i32(0), i32(w - 1))],
                           i32(0), i32(n_blocks - 1))
            data = lambda ref: ref.at[blk, :, lanes]
            sc = lambda ref: ref.at[blk]
        else:
            slot = ptr_ref[0]
            span = pl.ds(pl.multiple_of(ji * c, c), c)
            data = lambda ref: ref.at[slot, span, lanes]
            sc = lambda ref: ref.at[slot, :, span]
        srcs = [(data(kp_ref), kbuf.at[sl]), (data(vp_ref), vbuf.at[sl])]
        if quant:
            srcs += [(sc(ks_ref), ksbuf.at[sl]), (sc(vs_ref), vsbuf.at[sl])]
        return [pltpu.make_async_copy(s, dst, rsem.at[i32(li), sl])
                for li, (s, dst) in enumerate(srcs)]

    def fold(k, v, live):
        """Fold one [*, D] key/value tile into the running online
        softmax; masked lanes are zeroed after the exp (the reference's
        fully-masked-chunk pollution guard).  Returns (acc, l)."""
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale
        s = jnp.where(live, s, f32(_NEG_INF))
        m = m_ref[0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m_new[:, None]), f32(0.0))
        corr = jnp.exp(m - m_new)
        l_new = l_ref[0] * corr + jnp.sum(p, axis=-1)
        acc = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        return acc, l_new

    @pl.when(j == 0)
    def _init_stage_append():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if quant:
            mine = jax.lax.broadcasted_iota(i32, ksb.shape, 0) == h

            # the reference's _q8_quantize, bit for bit: absmax over the
            # head dim, f16-ROUNDED scale as the divisor
            def stage(x_ref, data, bits):
                xf = x_ref[...].astype(f32)                  # [T, D]
                amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
                sc = f32_to_f16_bits(amax / f32(_Q8_MAX))    # [T, 1]
                inv = f32(1.0) / jnp.maximum(f16_bits_to_f32(sc),
                                             f32(1e-8))
                qv = jnp.minimum(jnp.maximum(jnp.round(xf * inv),
                                             f32(-_Q8_MAX)), f32(_Q8_MAX))
                data[...] = qv.astype(jnp.int8)
                # this head's row of the shared [Hkv, T] scale tile
                bits[...] = jnp.where(
                    mine, _vec_transpose(sc, to_col=False),
                    bits[...].astype(i32)).astype(bits.dtype)

            stage(kn_ref, kwb, ksb)
            stage(vn_ref, vwb, vsb)
        else:
            kwb[...] = kn_ref[...].astype(kwb.dtype)
            vwb[...] = vn_ref[...].astype(vwb.dtype)
        for dma, valid in write_dmas():
            @pl.when(valid)
            def _(dma=dma):
                dma.start()
        # kick the read pipeline for prefix block 0

        @pl.when(off > 0)
        def _():
            for dma in read_dmas(j, j):
                dma.start()

    work = j * c < off  # this prefix block holds >= 1 written row

    @pl.when(work)
    def _fold_prefix():
        sl = jax.lax.rem(j, i32(2))
        for dma in read_dmas(j, sl):
            dma.wait()
        nxt = j + 1

        @pl.when(nxt * c < off)
        def _():
            for dma in read_dmas(nxt, jax.lax.rem(nxt, i32(2))):
                dma.start()

        k = kbuf[sl].astype(f32)                            # [C, D]
        v = vbuf[sl].astype(f32)
        if quant:
            k = k * head_scale(ksbuf[sl], h)
            v = v * head_scale(vsbuf[sl], h)
        # every prefix row < offset is causally visible to EVERY query of
        # this chunk (q_pos >= offset); rows at/past the offset in the
        # partially-filled block are exactly the bytes the append DMA may
        # be writing — masked lanes are zeroed after the exp, so a torn
        # or stale read there never reaches the output
        k_live = j * c + jax.lax.broadcasted_iota(i32, (rows, c), 1) < off
        acc, l_new = fold(k, v, k_live)
        acc_ref[...] = acc
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_chunks - 1)
    def _fold_new_fin():
        # the chunk's own rows, exactly as the reference reads them back
        # after its scatter: int8 rows dequantize the staged quantized
        # copy, float rows cast through the pool dtype
        k = kwb[...].astype(f32)
        v = vwb[...].astype(f32)
        if quant:
            k = k * head_scale(ksb[...], h)
            v = v * head_scale(vsb[...], h)
        # row r of the [G, T] query tile is chunk token r % t (a 3-D
        # iota whose leading dims merge — Mosaic refuses the 2-D -> 1-D
        # cast); new key i sits at global position offset + i —
        # intra-chunk causal mask
        q_rel = jax.lax.broadcasted_iota(
            i32, (group, t, t), 1).reshape(rows, t)
        k_rel = jax.lax.broadcasted_iota(i32, (rows, t), 1)
        acc, l_new = fold(k, v, k_rel <= q_rel)
        l_safe = jnp.maximum(l_new, f32(1e-30))
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        for dma, valid in write_dmas():
            @pl.when(valid)
            def _(dma=dma):
                dma.wait()


def fused_prefill_attention(q, k_new, v_new, k_cache, v_cache, slot, offset,
                            scale, chunk, block_table=None, interpret=None):
    """Fused drop-in for ``slot_prefill_attention``'s scatter + attend.

    q ``[1, T, H, D]``; k_new/v_new ``[1, T, Hkv, D]``; caches dense
    ``[B, Lmax, Hkv, D]`` or — with ``block_table [1, W]``, the SLOT'S
    table row — a paged pool ``[N, C, Hkv, D]``; int8 caches are
    ``(data, scale)`` pairs.  ``slot`` / ``offset`` are the traced write
    cursor (``offset`` a multiple of ``T`` — see the module docstring).
    Returns ``(out [1, T, H, D] in q.dtype, k_cache', v_cache')`` with
    the chunk's rows appended in place, numerically equal to the
    reference up to online-softmax fold reassociation (the parity matrix
    pins the drift budget).  ``interpret=None`` resolves to
    ``jax.default_backend() != "tpu"``.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    hkv = k_new.shape[2]
    g = h // hkv
    gt = g * t
    c = int(chunk)
    quant = isinstance(k_cache, tuple)
    paged = block_table is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k_data = k_cache[0] if quant else k_cache
    if paged:
        n_chunks = int(block_table.shape[1])
        ptr = block_table.reshape(-1).astype(jnp.int32)     # [W]
        nw = t // c if t > c else 1
    else:
        n_chunks = int(k_data.shape[1]) // c
        ptr = jnp.reshape(slot, (1,)).astype(jnp.int32)
        nw = 1
    off_arr = jnp.reshape(offset, (1,)).astype(jnp.int32)

    q2 = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3) \
        .reshape(hkv, gt, d).astype(jnp.float32)

    # new rows and cache data go in as ``data_view`` (head h = lane block
    # h), f16 scale leaves as ``scale_view`` — paged_attention_pallas
    # index maps receive (h, j, *scalar_refs); ``j * 0`` keeps the index
    # dtype i32 under jax_enable_x64 (the flash_attention Mosaic idiom)
    q_idx = lambda hi, ji, off, ptr: (hi, ji * 0, ji * 0)
    n_idx = lambda hi, ji, off, ptr: (ji * 0, hi)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    if quant:
        pool_leaves = [data_view(k_cache[0]), scale_view(k_cache[1]),
                       data_view(v_cache[0]), scale_view(v_cache[1])]
    else:
        pool_leaves = [data_view(k_cache), data_view(v_cache)]
    in_specs = [pl.BlockSpec((1, gt, d), q_idx),
                pl.BlockSpec((t, d), n_idx),
                pl.BlockSpec((t, d), n_idx)] + [any_spec] * len(pool_leaves)
    args = [q2, data_view(k_new[0]), data_view(v_new[0]), *pool_leaves]
    # operand index space counts the 2 scalar-prefetch operands
    aliases = {5 + li: 1 + li for li in range(len(pool_leaves))}
    out_specs = [pl.BlockSpec((1, gt, d), q_idx)] \
        + [any_spec] * len(pool_leaves)
    out_shape = [jax.ShapeDtypeStruct((hkv, gt, d), jnp.float32)] \
        + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pool_leaves]

    pool_dtype = k_data.dtype
    stage = [pltpu.VMEM((t, d), pool_dtype)]
    rbuf = [pltpu.VMEM((2, c, d), pool_dtype)]
    if quant:
        stage += [pltpu.VMEM((hkv, t), jnp.int16)]
        rbuf += [pltpu.VMEM((2, hkv, c), jnp.int16)]
    scratch = [
        pltpu.VMEM((gt, d), jnp.float32),
        pltpu.VMEM((8, gt), jnp.float32),
        pltpu.VMEM((8, gt), jnp.float32),
        *stage, *stage,                                     # k then v
        *rbuf, *rbuf,
        pltpu.SemaphoreType.DMA((len(pool_leaves), 2)),
        pltpu.SemaphoreType.DMA((len(pool_leaves), nw)),
    ]

    outs = pl.pallas_call(
        functools.partial(
            _prefill_kernel, chunk=c, t=t, group=g, d=d, scale=float(scale),
            quant=quant, paged=paged, nw=nw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hkv, n_chunks),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the append runs as guarded DMAs the compiler cannot see through
        # — without the side-effect flag it would be dead-code eliminated
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name="prefill_attention_append",
    )(off_arr, ptr, *args)
    out = outs[0].reshape(hkv, g, t, d).transpose(2, 0, 1, 3) \
        .reshape(1, t, h, d).astype(q.dtype)

    if quant:
        return (out,
                (outs[1].reshape(k_data.shape),
                 scale_unview(outs[2], k_cache[1])),
                (outs[3].reshape(k_data.shape),
                 scale_unview(outs[4], v_cache[1])))
    return out, outs[1].reshape(k_data.shape), outs[2].reshape(k_data.shape)
