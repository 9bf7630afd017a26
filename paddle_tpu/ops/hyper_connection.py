"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880; built on
Hyper-Connections, arXiv:2409.19606): the residual path of a model whose
token state is ``n`` streams ``X [n, C]`` instead of one.

A hyper-connected sub-layer around a branch ``F`` (attention or FFN, with
its own pre-norm)::

    x~      = RMS_plain(vec(X))                     # [n C], no gain, eps
    H~_pre  = a_pre  (x~ phi_pre)  + b_pre          # [n]
    H~_post = a_post (x~ phi_post) + b_post         # [n]
    H~_res  = a_res mat(x~ phi_res) + b_res         # [n, n]
    H_pre   = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res   = Sinkhorn(clip(H~_res, lo, hi))
    u  = H_pre X;   y = F(u);   X' = H_res X + H_post^T y

``Sinkhorn(M~)``: ``M = exp(M~)``, then ``iters`` times: every column is
divided by (its sum + eps), then every row by (its sum + eps) — doubly
stochastic to within the iteration's error.  ALL ``iters`` steps run: no
early exit, no tolerance.

``phi [n C, n*n + 2 n]`` (columns ``[pre | post | res row-major]``),
``b [n*n + 2 n]`` and ``alpha [3]`` (``a_pre, a_post, a_res``) are float32;
the coefficients are computed in float32 (as ``ops/moe.route`` computes the
router: a ``Precision.HIGHEST`` product of the operands as stored — the
flattened norm is one scalar a row and multiplies the product afterwards)
and the streams are carried in the model's dtype.

**The layout.**  ``X`` is carried as ``[.., n C]``: the equations' ``[n, C]``
state flattened (``vec(X)``), stream ``j`` in columns ``j C .. (j + 1) C``.
A ``[.., n, C]`` array is tiled over ``(n, C)`` on the chip (4 of a tile's
sublanes used), and the coefficient product's ``[rows, n C]`` view of it is
then a copy of the whole stream into another order (chipless compile:
``bf16[8,8,4,3584] copy``); flat, a stream is a lane-aligned slice and the
product reads the state as stored.

**The form the 20 Sinkhorn steps take.**  Written on ``[rows, n, n]`` arrays
with ``sum(axis)`` the iteration is 2 reductions a step over minor dims of
4: the TPU compiler makes 88 small kernels of a sub-layer's coefficients
(chipless compile for a v5e, 64 rows, n = 4, C = 3584: instructions that run
on their own under ``hc.coeff``; 91 at 512 rows).  Here the product comes
out rows-MINOR (``[n*n + 2 n, rows]``), every entry of ``H~`` is its own
``[rows]`` operand of plain elementwise arithmetic, and the iteration
carries the matrix as ``diag(r) K diag(c)`` with ``K = exp(H~_res)`` fixed —
dividing column ``j`` by (its sum + eps) IS ``c_j <- c_j / (c_j sum_i r_i
K_ij + eps)``, and the same for rows —, so a step updates 8 numbers and
not 16 and the compiler fuses each step into ONE kernel: 25 a sub-layer in
the decode program, 28 in a prefill run (``tests/test_chip_compile.py``
holds them).  With each of the 16 entries rescaled in place it is 48: the
fuser stops a multi-output fusion at ~45 operands and results, so no plain
form is one kernel.  On the chip, 16 sub-layers alone (coefficients, read,
write; PERF.md, PR 35): 0.255 ms at 64 rows and 1.33 ms at 512 in this
form, 0.295 / 1.80 in the ``sum(axis)`` form — a kernel of a few hundred
bytes inside a compiled program costs ~0.45 us, not microseconds — and ONE
hand-written (Pallas) kernel for the sigmoids, the clamp and all 20 steps
0.289 / 1.26: nothing in decode, 5% in prefill, so it was not kept (the
residual path is 2.1% of the decode step and 4.1% of a prefill run).

Device scopes (``observability.trace.RESIDUAL_SCOPES``): ``hc.coeff``,
``hc.read``, ``hc.write``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["coefficients", "stacked", "read", "write", "sinkhorn_entries"]


_add = functools.partial(functools.reduce, jnp.add)


def sinkhorn_entries(k, iters, eps):
    """``iters`` Sinkhorn steps on a positive matrix given as its entries
    ``k[i][j]`` (arrays of one shape): every column divided by (its sum +
    eps), then every row, with the matrix carried as ``diag(r) k diag(c)``
    (module docstring).  Returns the entries."""
    n = len(k)
    r = c = [jnp.ones_like(k[0][0])] * n
    for _ in range(iters):
        c = [c[j] / (c[j] * _add([r[i] * k[i][j] for i in range(n)]) + eps)
             for j in range(n)]
        r = [r[i] / (r[i] * _add([k[i][j] * c[j] for j in range(n)]) + eps)
             for i in range(n)]
    return [[r[i] * k[i][j] * c[j] for j in range(n)] for i in range(n)]


def _activate(t, n, iters, eps, clamp):
    """``H~`` as its ``n*n + 2 n`` entries (arrays of one shape) -> the
    coefficients, entry for entry: sigmoid, 2 sigmoid, the clamped
    Sinkhorn.  Plain elementwise arithmetic on separate operands."""
    lo, hi = jnp.float32(clamp[0]), jnp.float32(clamp[1])
    pre = [jax.nn.sigmoid(x) for x in t[:n]]
    post = [2.0 * jax.nn.sigmoid(x) for x in t[n:2 * n]]
    res = sinkhorn_entries(
        [[jnp.exp(jnp.minimum(jnp.maximum(t[2 * n + i * n + j], lo), hi))
          for j in range(n)] for i in range(n)], iters, jnp.float32(eps))
    return pre + post + [x for row in res for x in row]


def coefficients(X, phi, b, alpha, *, n, iters, eps, clamp):
    """``X [.., n C]`` -> the float32 coefficients as ENTRIES ``(pre, post,
    res)``: ``pre[j]``, ``post[j]``, ``res[i][j]`` arrays of shape
    ``X.shape[:-1]`` (module docstring; ``stacked`` makes arrays of them).
    ``clamp = (lo, hi)`` acts on ``H~_res`` before the exponential.  The
    call sits under ``hc.coeff``: what the compiler makes at the inner
    jit's boundary keeps only the call's path."""
    with jax.named_scope("hc.coeff"):
        return _coefficients(X, phi, b, alpha, n=int(n), iters=int(iters),
                             eps=float(eps), clamp=tuple(map(float, clamp)))


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp"))
def _coefficients(X, phi, b, alpha, *, n, iters, eps, clamp):
    # jitted: a model's sub-layers call it at one set of shapes, so the
    # unrolled iteration is traced once a program and not once a sub-layer
    lead = X.shape[:-1]
    xf = X.reshape(-1, X.shape[-1]).astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)          # [rows]
    h = jnp.einsum("rc,ck->kr", xf, phi.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)      # rows minor
    # H~, still one array: each column's learned scale, the row's norm
    a = jnp.repeat(alpha, jnp.array([n, n, n * n]), total_repeat_length=len(b))
    t = h * (a[:, None] * inv[None, :]) + b[:, None]
    out = [x.reshape(lead) for x in _activate(
        [t[k] for k in range(len(b))], n, iters, eps, clamp)]
    return (tuple(out[:n]), tuple(out[n:2 * n]),
            tuple(tuple(out[2 * n + i * n:2 * n + (i + 1) * n])
                  for i in range(n)))


def stacked(pre, post, res):
    """The entries as arrays: ``(pre [.., n], post [.., n], res
    [.., n, n])``."""
    stack = lambda xs: jnp.stack(xs, axis=-1)
    return stack(pre), stack(post), jnp.stack([stack(r) for r in res],
                                              axis=-2)


def _streams(X, n):
    c = X.shape[-1] // n
    return [X[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def read(X, pre):
    """``u = H_pre X``: ``X [.., n C]``, ``pre`` its ``n`` entries ->
    ``[.., C]`` in ``X``'s dtype (float32 sum)."""
    with jax.named_scope("hc.read"):
        return _add([p[..., None] * x for p, x in zip(
            pre, _streams(X, len(pre)))]).astype(X.dtype)


def write(X, res, post, y):
    """``X' = H_res X + H_post^T y``: stream ``i`` receives ``sum_j
    res[i][j] X_j + post[i] y``.  ``y [.., C]``; returns ``[.., n C]`` in
    ``X``'s dtype (float32 sums)."""
    with jax.named_scope("hc.write"):
        xs, yf = _streams(X, len(post)), y.astype(jnp.float32)
        return jnp.concatenate([
            (_add([r[..., None] * x for r, x in zip(row, xs)])
             + p[..., None] * yf).astype(X.dtype)
            for row, p in zip(res, post)], axis=-1)
