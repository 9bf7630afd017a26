"""Fused rotary embedding (ops/fused_rope.py): parity with the textbook
formulation (models/llama._apply_rope) — values AND grads, GQA shapes,
position offsets, bf16 — in Pallas interpret mode on CPU.

Reference parity: paddle.incubate.nn.functional.fused_rotary_position_embedding
(/root/reference/python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py),
reference test test/legacy_test/test_fused_rotary_position_embedding.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.llama import _apply_rope, _rope_cos_sin
from paddle_tpu.ops.fused_rope import available, fused_rope


def _ref(q, k, nh, nkv, theta=10000.0, offset=0):
    b, l, qd = q.shape
    d = qd // nh
    rq, rk = _apply_rope(q.reshape(b, l, nh, d), k.reshape(b, l, nkv, d),
                         theta, position_offset=offset)
    return rq.reshape(q.shape), rk.reshape(k.shape)


def _tables(l, d, dtype, theta=10000.0, offset=0):
    cos, sin = _rope_cos_sin(offset + l, d, theta, dtype)
    return cos[offset:], sin[offset:]


@pytest.mark.parametrize("b,l,nh,nkv,d", [
    (2, 64, 4, 2, 16),     # GQA
    (1, 32, 2, 2, 32),     # MHA
    (2, 48, 8, 1, 16),     # MQA
])
def test_values_and_grads_match_textbook(b, l, nh, nkv, d):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, l, nh * d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv * d)), jnp.float32)
    cos, sin = _tables(l, d, jnp.float32)

    rq_r, rk_r = _ref(q, k, nh, nkv)
    rq_f, rk_f = fused_rope(q, k, cos, sin, nh, nkv, True)
    np.testing.assert_allclose(rq_f, rq_r, atol=1e-6)
    np.testing.assert_allclose(rk_f, rk_r, atol=1e-6)

    # nonlinear downstream so dq depends on the rotated values
    def loss_ref(q, k):
        a, b2 = _ref(q, k, nh, nkv)
        return (a * jnp.sin(a)).sum() + (b2 ** 3).sum()

    def loss_fused(q, k):
        a, b2 = fused_rope(q, k, cos, sin, nh, nkv, True)
        return (a * jnp.sin(a)).sum() + (b2 ** 3).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1))(q, k)
    gf = jax.grad(loss_fused, argnums=(0, 1))(q, k)
    np.testing.assert_allclose(gf[0], gr[0], atol=1e-4)
    np.testing.assert_allclose(gf[1], gr[1], atol=1e-4)


def test_position_offset_cached_prefill():
    b, l, nh, nkv, d, off = 2, 32, 4, 2, 16, 24
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, l, nh * d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv * d)), jnp.float32)
    cos, sin = _tables(l, d, jnp.float32, offset=off)
    rq_r, rk_r = _ref(q, k, nh, nkv, offset=off)
    rq_f, rk_f = fused_rope(q, k, cos, sin, nh, nkv, True)
    np.testing.assert_allclose(rq_f, rq_r, atol=1e-6)
    np.testing.assert_allclose(rk_f, rk_r, atol=1e-6)


def test_bf16_matches_textbook_bf16():
    b, l, nh, nkv, d = 2, 64, 4, 2, 16
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, l, nh * d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, l, nkv * d)), jnp.bfloat16)
    cos, sin = _tables(l, d, jnp.bfloat16)
    rq_r, rk_r = _ref(q, k, nh, nkv)
    rq_f, rk_f = fused_rope(q, k, cos, sin, nh, nkv, True)
    # same ops in the same dtype: bit-identical
    np.testing.assert_array_equal(np.asarray(rq_f), np.asarray(rq_r))
    np.testing.assert_array_equal(np.asarray(rk_f), np.asarray(rk_r))


def test_rotation_is_inverted_by_negated_sin():
    """The vjp identity the backward relies on: R(-theta) == R^{-1}."""
    b, l, nh, nkv, d = 1, 16, 2, 1, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, l, nh * d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, nkv * d)), jnp.float32)
    cos, sin = _tables(l, d, jnp.float32)
    rq, rk = fused_rope(q, k, cos, sin, nh, nkv, True)
    bq, bk = fused_rope(rq, rk, cos, -sin, nh, nkv, True)
    np.testing.assert_allclose(bq, q, atol=1e-5)
    np.testing.assert_allclose(bk, k, atol=1e-5)


def test_available_gating():
    on_tpu = jax.devices()[0].platform == "tpu"
    # well-formed shapes pass exactly when on TPU (the platform gate)
    assert available((2, 256, 512), (2, 256, 128), 4, 1) == on_tpu
    # malformed head split
    assert not available((2, 256, 500), (2, 256, 128), 4, 1)
    # sub-128 head dim (BERT-shaped): packed->row reshape not lane-clean
    assert not available((2, 256, 4 * 64), (2, 256, 64), 4, 1)
    # short cached prefill (l not a 128-multiple): jnp fallback
    assert not available((2, 24, 4 * 128), (2, 24, 128), 4, 1)
    # the bench shapes pass exactly when on TPU
    assert available((16, 2048, 16 * 128), (16, 2048, 4 * 128), 16, 4) \
        == on_tpu


def test_incubate_api_routes_onto_kernel(monkeypatch):
    """incubate.nn.functional.fused_rotary_position_embedding's common case
    (neox style, q+k, batch-major) rides the Pallas kernel; kernel-vs-jnp
    parity through the public API."""
    import functools

    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.ops import fused_rope as FR

    b, l, nh, nkv, d = 2, 32, 4, 2, 16
    rng = np.random.default_rng(5)
    q = paddle.to_tensor(rng.standard_normal((b, l, nh, d)).astype("float32"))
    k = paddle.to_tensor(rng.standard_normal((b, l, nkv, d)).astype("float32"))

    ref_q, ref_k, _ = IF.fused_rotary_position_embedding(q, k)

    calls = []
    real = FR.fused_rope
    monkeypatch.setattr(FR, "available", lambda *a, **kw: True)
    monkeypatch.setattr(
        FR, "fused_rope",
        lambda *a, **kw: calls.append(1) or real(*a[:6], True))
    fast_q, fast_k, _ = IF.fused_rotary_position_embedding(q, k)
    assert calls, "fast path was not taken"
    np.testing.assert_allclose(np.asarray(fast_q.numpy()),
                               np.asarray(ref_q.numpy()), atol=1e-6)
    np.testing.assert_allclose(np.asarray(fast_k.numpy()),
                               np.asarray(ref_k.numpy()), atol=1e-6)


def test_incubate_api_dtype_contract():
    """Reference contract: outputs carry q's dtype even when user sin/cos
    are wider (review r5) — on both the jnp fallback and the fast path."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as IF

    b, l, nh, d = 1, 16, 2, 8
    rng = np.random.default_rng(6)
    q = paddle.to_tensor(
        rng.standard_normal((b, l, nh, d)).astype(np.float32)).astype(
        "bfloat16")
    k = paddle.to_tensor(
        rng.standard_normal((b, l, nh, d)).astype(np.float32)).astype(
        "bfloat16")
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2, np.float32) / d))
    freqs = np.outer(np.arange(l, dtype=np.float32), inv)
    emb = np.concatenate([freqs, freqs], -1)
    sin = paddle.to_tensor(np.sin(emb).astype(np.float32))
    cos = paddle.to_tensor(np.cos(emb).astype(np.float32))
    oq, ok, _ = IF.fused_rotary_position_embedding(q, k, sin=sin, cos=cos)
    assert str(oq.dtype).endswith("bfloat16"), oq.dtype
    assert str(ok.dtype).endswith("bfloat16"), ok.dtype


def test_flash_attention_packed_rope_parity():
    """Rope fused INTO the flash kernels (q/k rotate on VMEM tiles, bwd
    re-rotates from raw residuals and inverse-rotates dq/dk in-kernel):
    values + grads match rotate-then-attend.  Not routed by the model at
    bench shapes (measured slower there, round 5); parity keeps
    the op usable where the tradeoff inverts."""
    from paddle_tpu.ops.flash_attention import (flash_attention_packed,
                                                flash_attention_packed_rope)

    B, L, NH, NKV, D = 2, 256, 4, 2, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, L, NH * D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, NKV * D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, NKV * D)), jnp.float32)
    cos, sin = _rope_cos_sin(L, D, 10000.0, jnp.float32)

    def ref(q, k, v):
        rq, rk = _apply_rope(q.reshape(B, L, NH, D),
                             k.reshape(B, L, NKV, D), 10000.0)
        return flash_attention_packed(rq.reshape(B, L, -1),
                                      rk.reshape(B, L, -1), v,
                                      NH, NKV, True, None, True)

    def fused(q, k, v):
        return flash_attention_packed_rope(q, k, v, cos, sin, NH, NKV,
                                           True, None, True)

    np.testing.assert_allclose(fused(q, k, v), ref(q, k, v), atol=1e-5)

    def loss(f):
        return lambda *a: (f(*a) * jnp.sin(f(*a))).sum()

    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=1e-4)
