"""Fused int8-moment AdamW update as ONE Pallas pass (TPU).

The jnp formulation of the 8-bit-Adam update runs as several XLA passes
over HBM: int8→f32 moment decode, the elementwise update, a separate
blockwise-absmax reduce, and the re-quantize (the r5 profile shows
pad_maximum ~29 ms + round/convert ~17 ms + the decode converts on a
0.85B-param step).  This kernel does decode → AdamW → encode for one tile
in VMEM, so every state tensor is read and written exactly once per step.

Layout contract (matches Optimizer._q8_encode): the flat parameter is
viewed as ``[nb, 256]`` — each ROW is one quantization block with one f32
absmax scale.  A kernel tile is ``[rows, 256]`` with the scales as a
``[rows, 1]`` column (broadcasts over lanes natively).

Reference bar: the fused adamw CUDA kernel
(paddle/phi/kernels/gpu/adamw_kernel.cu) — same single-pass idea, plus the
8-bit moment layout the reference does not have.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_Q8_BLOCK = 256


def _kernel(sc_ref, p_ref, g_ref, m_ref, s_ref, v_ref, *outs,
            out_dtype, has_master: bool, chunks: int = 1):
    """sc_ref [1, 16] f32 scalars: b1, b2, eps, lr, c1, c2, wd_factor, _,
    (1-b1), (1-b2), padding...
    p_ref [rows, 256] master f32 (or the raw low-precision param when no
    master exists — cast in-kernel); g_ref [rows, 256] f32|bf16;
    m_ref int8 codes; s_ref [rows, 1] f32 scales; v_ref bf16 moment2.
    outs = ([p32_out,] pw_out, m_out, s_out, v_out).

    ``chunks`` > 1 = NATIVE-shape tiles: refs arrive [br, chunks*256]
    (s_ref [br, chunks]) in the parameter's own 2-D layout, and the
    [rows, 256] quantization-block view happens HERE, in VMEM — the
    flat-layout formulation made XLA retile every state tensor in HBM
    (~13 ms/step on the MoE bench's 8x 16.8M-param experts).  Row-major
    contiguity makes the view exactly the flat path's block order."""
    if has_master:
        p_out, pw_out, m_out, s_out, v_out = outs
    else:
        pw_out, m_out, s_out, v_out = outs
    br = p_ref.shape[0]
    if chunks > 1:
        # native tiles: work in [br, chunks, 256] — every reshape splits or
        # merges MINOR dims only (a [br*chunks, 256] canonical view would
        # cross the sublane dim, which Mosaic refuses for the [br, chunks]
        # scales); the scale of block (r, c) broadcasts over its 256 lanes
        blk = lambda ref: ref[...].reshape(br, chunks, _Q8_BLOCK)
        s_in = s_ref[...][:, :, None]                 # [br, chunks, 1]
        unblk = lambda x: x.reshape(br, chunks * _Q8_BLOCK)
        s_store = lambda s: s.reshape(br, chunks)
        red_axis = 2
    else:
        blk = lambda ref: ref[...]
        s_in = s_ref[...]                             # [rows, 1]
        unblk = lambda x: x
        s_store = lambda s: s
        red_axis = 1
    sc = sc_ref[0]
    b1, b2, eps, lr = sc[0], sc[1], sc[2], sc[3]
    c1, c2, wd_factor = sc[4], sc[5], sc[6]
    # (1-beta) factors are HOST-computed (scalars[8], scalars[9]) so the
    # fused path is bit-identical to the jnp path's python-float constants
    # — an in-kernel f32(1)-f32(0.9) differs by ~2e-7 and can flip int8
    # codes at rounding boundaries (review r5)
    one_m_b1, one_m_b2 = sc[8], sc[9]
    g = blk(g_ref).astype(jnp.float32)
    m = blk(m_ref).astype(jnp.float32) * s_in
    v = blk(v_ref).astype(jnp.float32)
    m_new = b1 * m + one_m_b1 * g
    v_new = b2 * v + one_m_b2 * g * g
    upd = lr * (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    p_new = blk(p_ref).astype(jnp.float32) * wd_factor - upd
    if has_master:
        p_out[...] = unblk(p_new)
    pw_out[...] = unblk(p_new.astype(out_dtype))
    s_new = jnp.max(jnp.abs(m_new), axis=red_axis, keepdims=True) / 127.0
    m_out[...] = unblk(jnp.round(
        m_new / jnp.maximum(s_new, 1e-30)).astype(jnp.int8))
    s_out[...] = s_store(s_new)
    v_out[...] = unblk(v_new.astype(v_ref.dtype))


def fused_adamw_q8(p, g, m_codes, scales, v_bf16, scalars,
                   out_dtype=jnp.bfloat16, has_master=True,
                   interpret=False):
    """Entry: reads the PADDLE_Q8_NATIVE opt-out at CALL time (an env read
    inside the jitted body would be baked in at trace time and silently
    ignored once the shape is cached — review r5)."""
    import os

    native_ok = os.environ.get("PADDLE_Q8_NATIVE", "1") != "0"
    return _fused_adamw_q8(p, g, m_codes, scales, v_bf16, scalars,
                           out_dtype=out_dtype, has_master=has_master,
                           interpret=interpret, native_ok=native_ok)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "has_master", "interpret",
                                    "native_ok"))
def _fused_adamw_q8(p, g, m_codes, scales, v_bf16, scalars,
                    out_dtype=jnp.bfloat16, has_master=True,
                    interpret=False, native_ok=True):
    """One fused update step over a FLAT parameter whose size divides 256.

    p [n]: the f32 master when ``has_master``, else the raw low-precision
    parameter (cast to f32 inside the kernel — no f32 HBM copy is
    materialized); g [n] f32|bf16 grad; m_codes [n] int8; scales [n/256]
    f32; v_bf16 [n] bf16; scalars [16] f32 =
    (beta1, beta2, eps, lr, 1-beta1^t, 1-beta2^t, 1-lr*decay, unused,
    1-beta1, 1-beta2, 6 unused) — slots 8-9 are the HOST-computed
    (1-beta) factors the kernel's moment update reads (zero-padding them
    would silently freeze the moments).  Returns
    ([p32'] p_cast', m_codes', scales', v') — p32' only with a master.
    """
    n = p.size
    nb = n // _Q8_BLOCK
    # NATIVE-2-D path: a [R, C] parameter with a 256-multiple minor dim
    # keeps its own layout end to end (the quantization-block view happens
    # inside the kernel tile) — the flat view below made XLA physically
    # retile every state tensor to the [nb, 256] tiling and back
    if (p.ndim == 2 and p.shape[1] % (8 * _Q8_BLOCK) == 0
            and p.shape[0] % 8 == 0 and p.shape[1] <= 8192
            and native_ok):
        # C % 2048 == 0: the [br, chunks, 256] view tiles cleanly only when
        # chunks is a sublane multiple — chunks=22 ([2048,5632] llama MLP)
        # measured ~8 ms/step SLOWER than the flat path's retiles, while
        # chunks=8/32 (the MoE experts) measured ~8 ms FASTER
        R, C = p.shape
        chunks = C // _Q8_BLOCK
        # row block: ~256KB of f32 per operand tile — HALF the flat path's
        # budget, because the [br, chunks, 256] views materialize extra
        # VMEM intermediates (512KB tiles measured 18.3M scoped > the 16M
        # limit on the [2048, 512] k-proj).  The C <= 8192 gate keeps the
        # 8-row minimum inside budget; wider params (the 32k-vocab lm
        # head) take the flat path below
        br = min(R, (65536 // C) // 8 * 8)
        while R % br:
            br -= 8
        if br >= 8 and R % br == 0:
            grid = (R // br,)
            full = pl.BlockSpec((br, C), lambda i: (i, i * 0))
            col = pl.BlockSpec((br, chunks), lambda i: (i, i * 0))
            args = [
                jnp.asarray(scalars, jnp.float32).reshape(1, 16),
                p, g.reshape(R, C), m_codes.reshape(R, C),
                scales.reshape(R, chunks), v_bf16.reshape(R, C),
            ]
            in_specs = [pl.BlockSpec((1, 16), lambda i: (i * 0, i * 0)),
                        full, full, full, col, full]
            out_specs = [full, full, col, full]
            out_shape = [
                jax.ShapeDtypeStruct((R, C), out_dtype),
                jax.ShapeDtypeStruct((R, C), jnp.int8),
                jax.ShapeDtypeStruct((R, chunks), jnp.float32),
                jax.ShapeDtypeStruct((R, C), v_bf16.dtype),
            ]
            if has_master:
                out_specs = [full] + out_specs
                out_shape = [jax.ShapeDtypeStruct((R, C), jnp.float32)] \
                    + out_shape
            outs = pl.pallas_call(
                functools.partial(_kernel, out_dtype=out_dtype,
                                  has_master=has_master, chunks=chunks),
                grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shape, interpret=interpret,
                name="adamw_q8_update",
            )(*args)
            outs = list(outs)
            s_i = 2 if has_master else 1
            outs[s_i + 1] = outs[s_i + 1].reshape(scales.shape)
            return tuple(
                o if i == s_i + 1 else o.reshape(p.shape)
                for i, o in enumerate(outs))
    # flat path: any shape whose size divides 256
    # tile rows: biggest power-of-two chunk <= 512 that divides nb
    # (terminates at tr == 1: everything divides 1)
    tr = min(512, nb)
    while nb % tr:
        tr //= 2
    grid = (nb // tr,)
    shape2 = (nb, _Q8_BLOCK)
    args = [
        jnp.asarray(scalars, jnp.float32).reshape(1, 16),
        p.reshape(shape2),
        g.reshape(shape2),
        m_codes.reshape(shape2),
        scales.reshape(nb, 1),
        v_bf16.reshape(shape2),
    ]
    full = pl.BlockSpec((tr, _Q8_BLOCK), lambda i: (i, i * 0))
    col = pl.BlockSpec((tr, 1), lambda i: (i, i * 0))
    in_specs = [pl.BlockSpec((1, 16), lambda i: (i * 0, i * 0)),
                full, full, full, col, full]
    out_specs = [full, full, col, full]
    out_shape = [
        jax.ShapeDtypeStruct(shape2, out_dtype),
        jax.ShapeDtypeStruct(shape2, jnp.int8),
        jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        jax.ShapeDtypeStruct(shape2, v_bf16.dtype),
    ]
    if has_master:
        out_specs = [full] + out_specs
        out_shape = [jax.ShapeDtypeStruct(shape2, jnp.float32)] + out_shape
    outs = pl.pallas_call(
        functools.partial(_kernel, out_dtype=out_dtype,
                          has_master=has_master),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        name="adamw_q8_update",
    )(*args)
    if has_master:
        p32_new, p_cast, m_new, s_new, v_new = outs
        return (p32_new.reshape(p.shape), p_cast.reshape(p.shape),
                m_new.reshape(p.shape), s_new.reshape(scales.shape),
                v_new.reshape(p.shape))
    p_cast, m_new, s_new, v_new = outs
    return (p_cast.reshape(p.shape), m_new.reshape(p.shape),
            s_new.reshape(scales.shape), v_new.reshape(p.shape))
