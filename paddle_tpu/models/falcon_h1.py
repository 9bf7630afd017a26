"""Falcon-H1 (``model_type: falcon_h1``): a hybrid decoder whose every block
runs a Mamba-2 mixer and a GQA attention IN PARALLEL on the same normed
input, sums both into the residual, then a SwiGLU MLP; muP multipliers sit
on nearly every edge.

Equations (config keys in backticks; ``RMS(x; w)`` = RMSNorm):

- ``h0 = E[tok] * embedding_multiplier``.
- Block: ``u = RMS(h; input_layernorm)``.
  - Attention: ``a = u * attention_in_multiplier``; ``q = a Wq``,
    ``k = (a Wk) * key_multiplier``, ``v = a Wv``; rotate-half RoPE on q and
    k; causal ``softmax(q k^T / sqrt(head_dim)) v``;
    ``A = (. Wo) * attention_out_multiplier``.
  - SSM: ``s = u * ssm_in_multiplier``; ``p = (s W_in) * m``, ``m`` scaling
    the segments ``[z | x | B | C | dt]`` by ``ssm_multipliers``;
    ``xBC = silu(causal depthwise conv([x|B|C]))``;
    ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
    ``y_t = S_t C_t + D x_t`` (``ops/ssm.py``); gated grouped norm
    ``y = RMS_grouped(y * silu(z); w)``; ``M = (y W_out) *
    ssm_out_multiplier``.
  - ``h = h + A + M``.
  - MLP: ``v = RMS(h; pre_ff_layernorm)``; ``h = h + down(up(v) *
    silu(gate(v) * mlp_multipliers[0])) * mlp_multipliers[1]``.
- ``logits = lm_head(RMS(h; final_layernorm)) * lm_head_multiplier``.

The block's math is a set of pure functions over a plain dict of weights
(``lp``), shared by the whole-sequence forward here and by the serving
programs (``models/falcon_h1_decode.py``), under the device scopes of
``observability.trace.SCOPES`` (``ssm.in_proj``, ``ssm.conv``, ``ssm.scan``,
``ssm.norm_gate``, ``ssm.out`` beside the ``attn.*`` names).

Parameters are BORN in the configured dtype (``_Born``): drawn under jit
straight into it, the large tables block by block, never float32 first —
34 B parameters in float32 fit no chip this model is cut for.  A fresh
model draws each projection with std ``1 / (its multipliers x
sqrt(fan_in))`` so that every branch contributes O(1) with the published
multipliers in place, and the recurrence by Mamba-2's own initialisation.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from paddle_tpu.autograd.engine import apply
from paddle_tpu.models.llama import _apply_rope
from paddle_tpu.models.llama_decode import _rmsnorm as rmsnorm
from paddle_tpu.nn.initializer import Initializer
from paddle_tpu.nn.layer.container import LayerList
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.ops.ssm import causal_conv1d, ssd_chunked
from paddle_tpu.tensor.random import _key

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "FalconH1Statics"]


@dataclass
class FalconH1Config:
    """The published ``config.json`` keys (defaults: Falcon-H1-34B-Instruct)."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must be a multiple of mamba_n_groups")
        if self.tie_word_embeddings:
            raise ValueError("Falcon-H1's head is untied")
        self.ssm_multipliers = tuple(float(x) for x in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(x) for x in self.mlp_multipliers)

    # tiny preset used by the tests
    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, mamba_d_ssm=64,
                    mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=2,
                    mamba_d_state=16, mamba_chunk_size=8,
                    max_position_embeddings=128, dtype="float32")
        base.update(kw)
        return FalconH1Config(**base)


class FalconH1Statics(NamedTuple):
    """What the compiled programs close over of a configuration (hashable:
    a jit static, the serving programs' ``cfg``)."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    groups: int
    d_state: int
    d_conv: int
    chunk: int
    embed_mult: float
    attn_in: float
    attn_out: float
    key_mult: float
    head_mult: float
    ssm_in: float
    ssm_out: float
    ssm_mults: tuple
    mlp_mults: tuple

    @property
    def d_ssm(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self):
        return self.d_ssm + 2 * self.groups * self.d_state

    @property
    def segments(self):
        """Widths of ``w_in``'s output segments ``[z | x | B | C | dt]``."""
        gn = self.groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)


def statics_of(c: FalconH1Config) -> FalconH1Statics:
    return FalconH1Statics(
        c.num_attention_heads, c.num_key_value_heads, c.head_dim,
        float(c.rms_norm_eps), c.mamba_n_heads, c.mamba_d_head,
        c.mamba_n_groups, c.mamba_d_state, c.mamba_d_conv,
        c.mamba_chunk_size, float(c.embedding_multiplier),
        float(c.attention_in_multiplier), float(c.attention_out_multiplier),
        float(c.key_multiplier), float(c.lm_head_multiplier),
        float(c.ssm_in_multiplier), float(c.ssm_out_multiplier),
        c.ssm_multipliers, c.mlp_multipliers)


# ---------------------------------------------------------------- block math
# pure functions over ``lp``, a dict of one block's weights: ln1, ln2, wq, wk,
# wv, wo, w_in, conv_w [K, C], conv_b, dt_bias, a_log, d, norm_w, w_out,
# gate, up, down (projections [in, out])
def scaled_mm(x, w, scale):
    """``(x @ w) * scale`` with the multiplier on the float32 product: a muP
    multiplier costs no second rounding (``scale`` a float or a vector over
    the output features)."""
    return (jnp.matmul(x, w, preferred_element_type=jnp.float32)
            * scale).astype(x.dtype)


def attn_qkv(lp, cfg, u):
    """u [B, T, hidden] (normed) -> q [B, T, H, D], k, v [B, T, Hkv, D],
    before RoPE; ``key_multiplier`` already on k."""
    b, t, _ = u.shape
    with jax.named_scope("attn.qkv"):
        a = u * jnp.asarray(cfg.attn_in, u.dtype)
        # the barrier keeps the products 2-D past the dots, as in
        # llama_decode._qkv: with the head reshape directly behind a dot
        # the TPU compiler re-lays-out the weight on every run
        q, k, v = jax.lax.optimization_barrier(
            (a @ lp["wq"], scaled_mm(a, lp["wk"], cfg.key_mult),
             a @ lp["wv"]))
        return (q.reshape(b, t, cfg.heads, cfg.head_dim),
                k.reshape(b, t, cfg.kv_heads, cfg.head_dim),
                v.reshape(b, t, cfg.kv_heads, cfg.head_dim))


def embed(table, ids, cfg):
    """``E[ids] * embedding_multiplier``."""
    return (table[ids].astype(jnp.float32) * cfg.embed_mult).astype(
        table.dtype)


def attn_out(lp, cfg, out):
    """Attention output [B, T, H, D] -> the branch's residual term."""
    b, t = out.shape[:2]
    with jax.named_scope("attn.out"):
        return scaled_mm(out.reshape(b, t, cfg.heads * cfg.head_dim),
                         lp["wo"], cfg.attn_out)


def ssm_in(lp, cfg, u):
    """u [B, T, hidden] (normed) -> (z [B, T, d_ssm], xBC [B, T, C] before
    the convolution, dt [B, T, H] float32 after softplus)."""
    with jax.named_scope("ssm.in_proj"):
        mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                               zip(cfg.segments, cfg.ssm_mults)])
        p = scaled_mm(u * jnp.asarray(cfg.ssm_in, u.dtype), lp["w_in"], mup)
        z = p[..., :cfg.d_ssm]
        xbc = p[..., cfg.d_ssm:cfg.d_ssm + cfg.conv_channels]
        dt = p[..., cfg.d_ssm + cfg.conv_channels:].astype(jnp.float32)
        return z, xbc, jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))


def ssm_split(cfg, xbc):
    """The convolved ``[x | B | C]`` [.., C] -> x [.., G, E, P], B, C
    [.., G, N] (heads keep their group structure: ``ops/ssm.py``)."""
    lead = xbc.shape[:-1]
    g, n = cfg.groups, cfg.d_state
    x = xbc[..., :cfg.d_ssm].reshape(
        *lead, g, cfg.ssm_heads // g, cfg.ssm_head_dim)
    bm = xbc[..., cfg.d_ssm:cfg.d_ssm + g * n].reshape(*lead, g, n)
    cm = xbc[..., cfg.d_ssm + g * n:].reshape(*lead, g, n)
    return x, bm, cm


def ssm_head_params(lp, cfg):
    """(A [G, E] negative, D [G, E]) float32."""
    shape = (cfg.groups, cfg.ssm_heads // cfg.groups)
    return (-jnp.exp(lp["a_log"].astype(jnp.float32)).reshape(shape),
            lp["d"].astype(jnp.float32).reshape(shape))


def ssm_out(lp, cfg, y, z):
    """y [B, T, G, E, P] float32 (the scan's output), z [B, T, d_ssm] ->
    the branch's residual term: gate, grouped RMSNorm, output projection."""
    b, t = z.shape[:2]
    with jax.named_scope("ssm.norm_gate"):
        v = y.reshape(b, t, cfg.groups, -1) \
            * jax.nn.silu(z.astype(jnp.float32)).reshape(
                b, t, cfg.groups, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + cfg.eps)
        v = v.reshape(b, t, cfg.d_ssm).astype(z.dtype) * lp["norm_w"]
    with jax.named_scope("ssm.out"):
        return scaled_mm(v, lp["w_out"], cfg.ssm_out)


def mlp(lp, cfg, h):
    """h + the SwiGLU MLP of its ``pre_ff_layernorm``."""
    with jax.named_scope("norm"):
        x = rmsnorm(h, lp["ln2"], cfg.eps)
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(scaled_mm(x, lp["gate"], cfg.mlp_mults[0]))
        return h + scaled_mm((x @ lp["up"]) * gate, lp["down"],
                             cfg.mlp_mults[1])


def block_forward(lp, cfg, h, theta):
    """One block over whole sequences h [B, L, hidden] from position 0: no
    cache, zero initial state (training and the plain model forward)."""
    b, L, _ = h.shape
    with jax.named_scope("norm"):
        u = rmsnorm(h, lp["ln1"], cfg.eps)
    q, k, v = attn_qkv(lp, cfg, u)
    with jax.named_scope("attn.rope"):
        q, k = _apply_rope(q, k, theta)
    with jax.named_scope("attn.core"):
        g = cfg.heads // cfg.kv_heads
        qg = q.reshape(b, L, cfg.kv_heads, g, cfg.head_dim)
        s = jnp.einsum("blkgd,bmkd->bkglm", qg, k).astype(jnp.float32) \
            * cfg.head_dim ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
        out = jnp.einsum("bkglm,bmkd->blkgd",
                         jax.nn.softmax(s, -1).astype(v.dtype), v)
    a = attn_out(lp, cfg, out.reshape(b, L, cfg.heads, cfg.head_dim))

    z, xbc, dt = ssm_in(lp, cfg, u)
    xbc, _ = causal_conv1d(
        xbc, jnp.zeros((b, cfg.d_conv - 1, cfg.conv_channels), xbc.dtype),
        lp["conv_w"], lp["conv_b"])
    x, bm, cm = ssm_split(cfg, xbc)
    a_neg, d = ssm_head_params(lp, cfg)
    # pad to whole SSD chunks: dt = 0 makes a padded position a no-op
    pad = -L % cfg.chunk
    padded = lambda arr: jnp.pad(arr, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (arr.ndim - 2))
    s0 = jnp.zeros((cfg.groups, cfg.ssm_heads // cfg.groups,
                    cfg.ssm_head_dim, cfg.d_state), jnp.float32)
    y = jax.vmap(lambda xr, dtr, br, cr: ssd_chunked(
        xr, dtr, a_neg, br, cr, d, s0, cfg.chunk)[0])(
        padded(x), padded(dt.reshape(b, L, *a_neg.shape)), padded(bm),
        padded(cm))[:, :L]
    m = ssm_out(lp, cfg, y, z)
    return mlp(lp, cfg, h + a + m)


# -------------------------------------------------------------- the Layer
@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype",
                                             "row_blocks"))
def _draw(key, shape, std, dtype, row_blocks):
    def block(k, rows):
        return (jax.random.normal(k, (rows,) + shape[1:], jnp.float32)
                * std).astype(dtype)

    if row_blocks == 1:
        return block(key, shape[0])
    rows = shape[0] // row_blocks
    return jax.lax.map(
        lambda i: block(jax.random.fold_in(key, i), rows),
        jnp.arange(row_blocks, dtype=jnp.uint32)).reshape(shape)


class _Born(Initializer):
    """N(0, std^2) drawn under jit straight into the parameter's dtype; a
    table of 2^27 elements or more in 8 blocks of rows, so that its
    float32 bits are never resident at once."""

    def __init__(self, std):
        self.std = float(std)

    def __call__(self, param, block=None):
        shape = tuple(int(s) for s in param.shape)
        big = math.prod(shape) >= 2 ** 27 and shape[0] % 8 == 0
        param._data = _draw(_key(), shape, self.std,
                            jnp.dtype(param.data.dtype).name, 8 if big else 1)


class _BornSegments(Initializer):
    """Column segments of one matrix, each N(0, its own std^2)."""

    def __init__(self, widths, stds):
        self.widths, self.stds = tuple(widths), tuple(stds)

    def __call__(self, param, block=None):
        rows, dtype = int(param.shape[0]), jnp.dtype(param.data.dtype).name
        param._data = jnp.concatenate(
            [_draw(_key(), (rows, w), float(s), dtype, 1)
             for w, s in zip(self.widths, self.stds)], axis=1)


class _Fixed(Initializer):
    """A given array, cast to the parameter's dtype."""

    def __init__(self, make):
        self.make = make

    def __call__(self, param, block=None):
        param._data = jnp.asarray(
            self.make(tuple(int(s) for s in param.shape)),
            param.data.dtype)


class _Weight(Layer):
    """One named ``weight`` (and an optional ``bias``) born in ``dtype``."""

    def __init__(self, shape, dtype, init, bias_init=None):
        super().__init__()
        self.weight = self.create_parameter(
            list(shape), dtype=dtype, default_initializer=init)
        if bias_init is not None:
            self.bias = self.create_parameter(
                [shape[-1]], dtype=dtype, default_initializer=bias_init)


_ones = _Fixed(jnp.ones)


class FalconH1Attention(Layer):
    def __init__(self, c):
        super().__init__()
        h, qd = c.hidden_size, c.num_attention_heads * c.head_dim
        kvd = c.num_key_value_heads * c.head_dim
        rt = h ** -0.5 / c.attention_in_multiplier
        self.q_proj = _Weight((h, qd), c.dtype, _Born(rt))
        self.k_proj = _Weight((h, kvd), c.dtype,
                              _Born(rt / c.key_multiplier))
        self.v_proj = _Weight((h, kvd), c.dtype, _Born(rt))
        self.o_proj = _Weight(
            (qd, h), c.dtype,
            _Born(qd ** -0.5 / c.attention_out_multiplier))


class FalconH1Mixer(Layer):
    """The Mamba-2 branch's weights.  ``in_proj`` [hidden, z|x|B|C|dt],
    ``conv1d.weight`` [kernel, channels] (``weight[kernel-1]`` multiplies
    the current input), ``dt_bias``, ``A_log``, ``D`` per head."""

    def __init__(self, c):
        super().__init__()
        s = statics_of(c)
        h, nh = c.hidden_size, c.mamba_n_heads
        # one std a segment: each of z, x, B, C, dt comes out O(1)
        self.in_proj = _Weight(
            (h, sum(s.segments)), c.dtype, _BornSegments(
                s.segments, [h ** -0.5 / (c.ssm_in_multiplier * m)
                             for m in c.ssm_multipliers]))
        self.conv1d = _Weight((c.mamba_d_conv, s.conv_channels), c.dtype,
                              _Born(0.5), bias_init=_Born(0.25))
        # Mamba-2's own initialisation: dt log-uniform in [1e-3, 1e-1]
        # (dt_bias its inverse softplus), A uniform in [1, 16], D = 1
        def dt_bias(shape):
            dt = jnp.exp(jax.random.uniform(
                _key(), shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))

        self.dt_bias = self.create_parameter(
            [nh], dtype=c.dtype, default_initializer=_Fixed(dt_bias))
        self.A_log = self.create_parameter(
            [nh], dtype=c.dtype, default_initializer=_Fixed(
                lambda shape: jnp.log(jax.random.uniform(
                    _key(), shape, jnp.float32, 1.0, 16.0))))
        self.D = self.create_parameter(
            [nh], dtype=c.dtype, default_initializer=_ones)
        self.norm = _Weight((c.mamba_d_ssm,), c.dtype, _ones)
        self.out_proj = _Weight(
            (c.mamba_d_ssm, h), c.dtype,
            _Born(c.mamba_d_ssm ** -0.5 / c.ssm_out_multiplier))


class FalconH1MLP(Layer):
    def __init__(self, c):
        super().__init__()
        h, i = c.hidden_size, c.intermediate_size
        self.gate_proj = _Weight((h, i), c.dtype,
                                 _Born(h ** -0.5 / c.mlp_multipliers[0]))
        self.up_proj = _Weight((h, i), c.dtype, _Born(h ** -0.5))
        self.down_proj = _Weight((i, h), c.dtype,
                                 _Born(i ** -0.5 / c.mlp_multipliers[1]))


class FalconH1DecoderLayer(Layer):
    def __init__(self, c):
        super().__init__()
        self.input_layernorm = _Weight((c.hidden_size,), c.dtype, _ones)
        self.self_attn = FalconH1Attention(c)
        self.mamba = FalconH1Mixer(c)
        self.pre_ff_layernorm = _Weight((c.hidden_size,), c.dtype, _ones)
        self.feed_forward = FalconH1MLP(c)

    def weights(self):
        """The block's weights under the names the pure functions read
        (``lp``), as parameters."""
        a, m, f = self.self_attn, self.mamba, self.feed_forward
        return {
            "ln1": self.input_layernorm.weight,
            "ln2": self.pre_ff_layernorm.weight,
            "wq": a.q_proj.weight, "wk": a.k_proj.weight,
            "wv": a.v_proj.weight, "wo": a.o_proj.weight,
            "w_in": m.in_proj.weight, "conv_w": m.conv1d.weight,
            "conv_b": m.conv1d.bias, "dt_bias": m.dt_bias,
            "a_log": m.A_log, "d": m.D, "norm_w": m.norm.weight,
            "w_out": m.out_proj.weight, "gate": f.gate_proj.weight,
            "up": f.up_proj.weight, "down": f.down_proj.weight,
        }


class FalconH1Model(Layer):
    def __init__(self, c):
        super().__init__()
        self.embed_tokens = _Weight(
            (c.vocab_size, c.hidden_size), c.dtype,
            _Born(1.0 / c.embedding_multiplier))
        self.layers = LayerList(
            [FalconH1DecoderLayer(c) for _ in range(c.num_hidden_layers)])
        self.final_layernorm = _Weight((c.hidden_size,), c.dtype, _ones)


class FalconH1ForCausalLM(Layer):
    """``forward(input_ids [B, L]) -> logits [B, L, vocab]`` float32: the
    whole sequence from position 0.  Served through
    ``paddle_tpu.serving.ServingEngine`` like any other model (the family
    of ``serving_family()``: ``models/falcon_h1_decode.py``)."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.model = FalconH1Model(config)
        self.lm_head = _Weight(
            (config.hidden_size, config.vocab_size), config.dtype,
            _Born(config.hidden_size ** -0.5 / config.lm_head_multiplier))

    def serving_family(self):
        from paddle_tpu.models.falcon_h1_decode import FALCON_H1_FAMILY

        return FALCON_H1_FAMILY

    def forward(self, input_ids):
        cfg, theta = statics_of(self.config), float(self.config.rope_theta)
        with jax.named_scope("embed"):
            h = apply("falcon_h1_embed",
                      lambda e, ids: embed(e, ids, cfg),
                      self.model.embed_tokens.weight, input_ids)
        for layer in self.model.layers:
            names, params = zip(*layer.weights().items())
            h = apply("falcon_h1_block",
                      lambda x, *ws: block_forward(
                          dict(zip(names, ws)), cfg, x, theta), h, *params)

        def head(x, norm, w):
            with jax.named_scope("norm"):
                x = rmsnorm(x, norm, cfg.eps)
            with jax.named_scope("lm_head"):
                return (x @ w).astype(jnp.float32) * cfg.head_mult

        return apply("falcon_h1_head", head, h,
                     self.model.final_layernorm.weight, self.lm_head.weight)
