"""``ops/hyper_connection.py`` against a NumPy transcription of the mHC
equations (float64 arithmetic on float32 inputs), on the CPU.

Tolerances.  The coefficients are float32 functions of O(1) numbers: the
product over ``n C`` = 192 terms, a sigmoid or an exponential, 20 contracting
Sinkhorn steps.  Against the float64 transcription they agree to a few 1e-7;
``COEFF_TOL`` = 1e-6 is the float32 rounding of numbers in [0, 2] with room,
and far under what a missing step does (one step instead of 20 moves entries
by 1e-2).  The shipped iteration carries the matrix as ``diag(r) K diag(c)``
and the ``sum(axis)`` form rescales all 16 entries: the same arithmetic up to
rounding, held to the same tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import trace
from paddle_tpu.ops import hyper_connection as hc

COEFF_TOL = 1e-6
N, C = 4, 48
KW = dict(n=N, iters=20, eps=1e-6, clamp=(-30.0, 30.0))


def params(seed=0, spread=1.0):
    rng = np.random.default_rng(seed)
    cols = N * N + 2 * N
    phi = (rng.standard_normal((N * C, cols)) * spread
           / np.sqrt(N * C)).astype(np.float32)
    b = np.concatenate([0.3 * rng.standard_normal(2 * N),
                        2.0 * np.eye(N).reshape(-1)]).astype(np.float32)
    alpha = np.asarray([0.7, 1.3, 1.1], np.float32)
    return phi, b, alpha


def state(shape=(3, 5), seed=1, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape + (N * C,)).astype(dtype)


def numpy_coefficients(X, phi, b, alpha, iters=20, eps=1e-6,
                       clamp=(-30.0, 30.0)):
    """The equations as written, float64: X [.., n C]."""
    X, phi, b, alpha = (np.asarray(a, np.float64) for a in (X, phi, b, alpha))
    xt = X / np.sqrt(np.mean(X * X, -1, keepdims=True) + eps)
    t = xt @ phi
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    pre = sig(alpha[0] * t[..., :N] + b[:N])
    post = 2.0 * sig(alpha[1] * t[..., N:2 * N] + b[N:2 * N])
    res = (alpha[2] * t[..., 2 * N:] + b[2 * N:]).reshape(X.shape[:-1]
                                                          + (N, N))
    mat = np.exp(np.clip(res, *clamp))
    for _ in range(iters):
        mat = mat / (mat.sum(-2, keepdims=True) + eps)      # columns
        mat = mat / (mat.sum(-1, keepdims=True) + eps)      # rows
    return pre, post, mat


def shipped(X, phi, b, alpha, **kw):
    return [np.asarray(a) for a in hc.stacked(*hc.coefficients(
        jnp.asarray(X), jnp.asarray(phi), jnp.asarray(b), jnp.asarray(alpha),
        **dict(KW, **kw)))]


def test_coefficients_are_the_equations():
    X, (phi, b, alpha) = state(), params()
    got = shipped(X, phi, b, alpha)
    want = numpy_coefficients(X, phi, b, alpha)
    assert [g.shape for g in got] == [(3, 5, N), (3, 5, N), (3, 5, N, N)]
    assert all(g.dtype == np.float32 for g in got)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=COEFF_TOL, rtol=0)
    # neither the identity nor uniform, so leaving the mixing out shows
    diag = np.diagonal(got[2], axis1=-2, axis2=-1).mean()
    assert 0.4 < diag < 0.9


def test_mixing_matrix_is_doubly_stochastic_after_20_steps():
    """At the spread the benchmark's weights give ``H~_res`` (unit, 2 on
    the diagonal) 20 steps converge: rows sum to 1 to the eps (the last
    division), columns to within 1e-4 for the median token (measured
    1.5e-6) and 1e-2 for every one of 256 (measured 2.9e-3: the iteration's
    error — a token whose matrix is far from balanced would need more
    steps, and gets 20)."""
    X, (phi, b, alpha) = state(shape=(16, 16), seed=2), params(seed=3)
    alpha = np.ones(3, np.float32)
    _, _, res = shipped(X, phi, b, alpha)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    off = np.abs(res.sum(-2) - 1.0).max(-1).ravel()
    assert off.max() < 1e-2 and np.median(off) < 1e-4
    assert (res > 0).all()


def test_every_step_runs():
    """``iters`` is honoured: one step is not twenty, nineteen are not
    twenty at the numbers' own precision where the iteration has not
    converged, and each count is the transcription's."""
    X, (phi, b, alpha) = state(seed=4), params(seed=5, spread=4.0)
    by_iters = {k: shipped(X, phi, b, alpha, iters=k)[2] for k in (1, 2, 20)}
    assert np.abs(by_iters[1] - by_iters[20]).max() > 1e-2
    assert np.abs(by_iters[1] - by_iters[2]).max() > 1e-3
    for k, got in by_iters.items():
        want = numpy_coefficients(X, phi, b, alpha, iters=k)[2]
        np.testing.assert_allclose(got, want, atol=COEFF_TOL, rtol=0)
    # after ONE step the rows sum to 1 (to the eps beside a row sum that
    # the column division left small) and the columns do not yet
    np.testing.assert_allclose(by_iters[1].sum(-1), 1.0, atol=1e-3)
    assert np.abs(by_iters[1].sum(-2) - 1.0).max() > 1e-2


def test_clamp_keeps_the_exponential_finite():
    """Inputs that drive ``H~_res`` to +-1e4: the clamp at +-30 acts before
    the exponential (``exp(30)`` is finite in float32, ``exp(1e4)`` is
    not), and the result is still the transcription's."""
    X, (phi, b, alpha) = state(seed=6), params(seed=7)
    alpha = np.asarray([1.0, 1.0, 1e4], np.float32)
    got = shipped(X, phi, b, alpha)
    assert all(np.isfinite(g).all() for g in got)
    want = numpy_coefficients(X, phi, b, alpha)
    np.testing.assert_allclose(got[2], want[2], atol=COEFF_TOL, rtol=0)
    # without the clamp the same inputs overflow
    wide = shipped(X, phi, b, alpha, clamp=(-1e9, 1e9))
    assert not np.isfinite(wide[2]).all()


def test_fusable_form_is_the_sum_axis_form():
    """The iteration on ``diag(r) K diag(c)`` against the plain one that
    divides ``[.., n, n]`` arrays by ``sum(axis)``, both in float32."""
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.uniform(-6, 6, (7, N, N)), jnp.float32)
    k = [[jnp.exp(logits[:, i, j]) for j in range(N)] for i in range(N)]
    got = jnp.stack([jnp.stack(r, -1) for r in
                     hc.sinkhorn_entries(k, 20, 1e-6)], -2)
    mat = jnp.exp(logits)
    for _ in range(20):
        mat = mat / (mat.sum(-2, keepdims=True) + 1e-6)
        mat = mat / (mat.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(mat),
                               atol=COEFF_TOL, rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_read_and_write_are_the_einsums(dtype):
    rng = np.random.default_rng(9)
    X = jnp.asarray(state(seed=10), dtype)
    y = jnp.asarray(rng.standard_normal((3, 5, C)), dtype)
    pre = rng.uniform(0, 1, (3, 5, N)).astype(np.float32)
    post = rng.uniform(0, 2, (3, 5, N)).astype(np.float32)
    res = rng.uniform(0, 1, (3, 5, N, N)).astype(np.float32)
    entries = lambda a: tuple(jnp.asarray(a[..., j]) for j in range(N))
    X4 = np.asarray(X, np.float32).reshape(3, 5, N, C)
    u = hc.read(X, entries(pre))
    out = hc.write(X, tuple(entries(res[..., i, :]) for i in range(N)),
                   entries(post), y)
    assert u.dtype == X.dtype and out.dtype == X.dtype
    assert u.shape == (3, 5, C) and out.shape == X.shape
    tol = 1e-5 if dtype == "float32" else 4e-2     # bf16: 8 bits of O(4)
    np.testing.assert_allclose(
        np.asarray(u, np.float32), np.einsum("btn,btnc->btc", pre, X4),
        atol=tol)
    want = (np.einsum("btij,btjc->btic", res, X4)
            + post[..., None] * np.asarray(y, np.float32)[:, :, None, :])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               want.reshape(3, 5, N * C), atol=tol)


def test_bf16_state_gives_float32_coefficients():
    X, (phi, b, alpha) = state(seed=11), params(seed=12)
    Xb = jnp.asarray(X, jnp.bfloat16)
    got = shipped(Xb, phi, b, alpha)
    assert all(g.dtype == np.float32 for g in got)
    # exactly the coefficients of the rounded state: the product takes the
    # operands as stored, nothing is rounded on the way
    want = numpy_coefficients(np.asarray(Xb, np.float32), phi, b, alpha)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=COEFF_TOL, rtol=0)


def test_scopes_are_the_vocabularys():
    X, (phi, b, alpha) = state(shape=(2, 3)), params()

    def f(X, y):
        pre, post, res = hc.coefficients(X, phi, b, alpha, **KW)
        return hc.write(X, res, post, hc.read(X, pre) + y)

    text = jax.jit(f).lower(jnp.asarray(X), jnp.zeros((2, 3, C))).as_text(
        debug_info=True)
    assert trace.RESIDUAL_SCOPES == ("hc.coeff", "hc.read", "hc.write")
    for scope in trace.RESIDUAL_SCOPES:
        assert f"{scope}/" in text or f'{scope}"' in text, scope
