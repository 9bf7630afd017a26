"""Fused rotary position embedding on the PACKED projection layout.

One Pallas pass rotates q and k straight off the attention projections
([B, L, H*D] / [B, L, Hkv*D]) — no [B, L, H, D] intermediates ever reach
HBM.  The XLA lowering of the textbook formulation (split, negate, concat,
two multiplies, add, reshape back to packed) materializes five-plus
full-tensor passes per call and forces non-default layouts whose copies
XLA then has to insert around the flash-attention custom calls; at the
round-5 bench shapes that chain profiled at ~110 ms/step across the 40
per-layer applications (16 fwd + 8 remat + 16 bwd).  Here the rotation is
a single read→rotate→write pass per tensor fused with nothing else to
schedule around, and the backward is THE SAME kernel with the sin table
negated: for the half-rotation R, R^T = R with sin → -sin (R is
orthogonal), so d(raw) = rot(d(rotated), cos, -sin).

Convention matches ``models/llama._apply_rope`` (half-split, llama/HF
style, NOT interleaved):

    rotated = x * cos + rot_half(x) * sin,
    rot_half(x) = concat(-x[d/2:], x[:d/2])

which the kernel evaluates as ``x * cos + swap(x) * sin_signed`` with
``swap(x) = concat(x[d/2:], x[:d/2])`` (a single lane-dim concat) and
``sin_signed = concat(-sin[:d/2], sin[d/2:])`` folded once in the wrapper.

Reference parity: paddle.incubate.nn.functional.fused_rotary_position_embedding
(/root/reference/python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py,
phi fusion kernel paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu) —
same fusion idea, TPU-native layout rationale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.flash_attention import (_on_tpu, _pick_block, _rot_tile,
                                            signed_sin)


def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, oq_ref, ok_ref, *,
                 nh: int, nkv: int, d: int, neg: bool):
    """One (batch, seq-block) program: rotate the q block and the k block.

    q_ref [1, bl, nh*d]; k_ref [1, bl, nkv*d]; cos_ref/sin_ref [bl, d]
    (sin pre-signed by the wrapper; ``neg`` selects the inverse rotation
    for the backward).  The packed->row reshape ([bl, h*d] -> [bl*h, d])
    is contiguous, i.e. free; cos/sin broadcast across the head dimension
    of the row order (row = pos*h + head -> table row pos).
    """
    bl = q_ref.shape[1]
    cos = cos_ref[...]
    sin = sin_ref[...]
    if neg:
        sin = -sin

    def rot(ref, oref, h):
        x = ref[0].reshape(bl * h, d)
        c = jnp.broadcast_to(cos[:, None, :], (bl, h, d)).reshape(bl * h, d)
        s = jnp.broadcast_to(sin[:, None, :], (bl, h, d)).reshape(bl * h, d)
        # shared rotation math (flash_attention._rot_tile) — one source of
        # the swap/sign convention across the standalone and in-kernel ropes
        oref[0] = _rot_tile(x, c, s).reshape(bl, h * d).astype(oref.dtype)

    rot(q_ref, oq_ref, nh)
    rot(k_ref, ok_ref, nkv)


@functools.partial(jax.jit,
                   static_argnames=("nh", "nkv", "neg", "interpret"))
def _rope_pallas(q, k, cos, sin, nh, nkv, neg=False, interpret=False):
    b, l, qd = q.shape
    d = qd // nh
    cos = cos.astype(q.dtype)
    # fold rot_half's sign into the sin table once ([L, D], tiny) — shared
    # convention source: flash_attention.signed_sin
    sin = signed_sin(sin).astype(q.dtype)
    bl = _pick_block(l, 256)
    # index maps use `i * 0` (not the literal 0): a literal traces as i64
    # under the package's jax_enable_x64 and Mosaic rejects the mixed-width
    # index tuple (same convention as flash_attention.py)
    return pl.pallas_call(
        functools.partial(_rope_kernel, nh=nh, nkv=nkv, d=d, neg=neg),
        grid=(b, l // bl),
        in_specs=[
            pl.BlockSpec((1, bl, nh * d), lambda bi, i: (bi, i, i * 0)),
            pl.BlockSpec((1, bl, nkv * d), lambda bi, i: (bi, i, i * 0)),
            pl.BlockSpec((bl, d), lambda bi, i: (i, i * 0)),
            pl.BlockSpec((bl, d), lambda bi, i: (i, i * 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bl, nh * d), lambda bi, i: (bi, i, i * 0)),
            pl.BlockSpec((1, bl, nkv * d), lambda bi, i: (bi, i, i * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
        ],
        interpret=interpret,
        name="fused_rope",
    )(q, k, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_rope(q, k, cos, sin, nh, nkv, interpret=False):
    """Rotate packed q [B, L, nh*D] and k [B, L, nkv*D] by the standard
    (unsigned, half-duplicated) cos/sin tables [L, D].  Returns rotated
    (q, k) in the same packed layout."""
    return _rope_pallas(q, k, cos, sin, nh, nkv, neg=False,
                        interpret=interpret)


def _fused_rope_fwd(q, k, cos, sin, nh, nkv, interpret):
    out = _rope_pallas(q, k, cos, sin, nh, nkv, neg=False,
                       interpret=interpret)
    return out, (cos, sin)


def _fused_rope_bwd(nh, nkv, interpret, res, g):
    cos, sin = res
    dq, dk = g
    dq_raw, dk_raw = _rope_pallas(dq, dk, cos, sin, nh, nkv, neg=True,
                                  interpret=interpret)
    # the tables are position constants: zero cotangent (tiny [L, D])
    return dq_raw, dk_raw, jnp.zeros_like(cos), jnp.zeros_like(sin)


fused_rope.defvjp(_fused_rope_fwd, _fused_rope_bwd)


def available(q_shape, k_shape, nh: int, nkv: int) -> bool:
    """Fast path: TPU, lane-aligned head dim (the in-kernel packed->row
    reshape is only tiling-clean when d is a 128-multiple), sequence a
    128-multiple (dtype-agnostic sublane-tile divisibility for the <= 256
    blocks _pick_block chooses), and blocks that fit scoped VMEM at worst
    case f32.  Anything else — short cached prefills, BERT-shaped d=64,
    CPU — takes the caller's jnp formulation, which was the only path
    before round 5."""
    if not _on_tpu():
        return False
    b, l, qd = q_shape
    d = qd // nh
    if d * nh != qd or k_shape[2] != nkv * d:
        return False
    if d % 128:                    # lane-aligned per-head rows
        return False
    if l % 128 or l < 128:
        return False
    # q/k/cos/sin + two outputs, double-buffered, worst-case f32
    bl = min(256, l)
    if 2 * 4 * bl * (2 * nh * d + 2 * nkv * d + 2 * d) > 12 * 1024 * 1024:
        return False
    return True
