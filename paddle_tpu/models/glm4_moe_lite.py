"""GLM-4.7-Flash (``model_type: glm4_moe_lite``): a decoder whose attention
is multi-head LATENT attention (MLA) in every layer and whose FFN, after
``first_k_dense_replace`` dense SwiGLU layers, is a mixture of
``n_routed_experts`` routed SwiGLU experts (``num_experts_per_tok`` a token)
plus ``n_shared_experts`` shared ones.

TWO published models run through this file and its serving programs
(``models/glm4_moe_lite_decode.py``): GLM-4.7-Flash, and Xing4.0-29B-A4B
(``models/xing4.py``: the same attention and expert FFN at other widths).
Two facts of ``Glm4MoeLiteStatics`` tell them apart — the residual path
(``hc``: one stream and ``h + branch``, or ``hc_mult`` streams under a
hyper-connection, ``ops/hyper_connection.py``) and the softmax scale
(``scale_mult``: YaRN's ``mscale_all_dim`` factor) — and one of the
parameters: the rope table (``rope_tables``: plain, or YaRN's blended
frequencies).

Equations (config keys in backticks; ``RMS`` = RMSNorm, eps ``rms_norm_eps``;
pre-norm residual blocks ``h += attn(RMS(h)); h += ffn(RMS(h))`` — with
``hc_mult`` > 1 each ``+=`` is the hyper-connected sub-layer of
``ops/hyper_connection.py``'s docstring instead, the stream starts as
``hc_mult`` copies of the embedding and ends as their sum):

- MLA.  ``c_q = RMS(x W_dq)`` (``q_lora_rank``); per head
  ``[q_nope | q_rope] = c_q W_uq`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``).  ``[c | k_r] = x W_dkv`` (``kv_lora_rank`` | rope);
  ``c = RMS(c)``; ``k_r = rope(k_r)`` — ONE rope key a token, shared by all
  heads —; ``q_rope = rope(q_rope)`` (rotate-half pairing).
  *Expanded* (the published form, ``block_forward``): per head
  ``k_nope = c W_uk``, ``v = c W_uv``, ``s = (q_nope . k_nope + q_rope . k_r)
  / sqrt(nope + rope)``, causal softmax, ``o = p v``, ``out = W_o [o_1 ..]``.
  *Absorbed* (the same numbers, for a cache that holds only ``[c | k_r]``;
  the serving programs, ``models/glm4_moe_lite_decode.py``):
  ``q~ = W_uk^T q_nope`` (``kv_lora_rank``), ``s = (q~ . c + q_rope . k_r)
  / sqrt(nope + rope)``, ``o = W_uv (sum_j p_j c_j)``.
  With ``rope_scaling`` (``type: yarn``; the DeepSeek-V3-shaped modelling
  code's forms): the rotary frequencies ``inv_j = theta^(-2j/rope)`` are
  blended with their interpolated values, ``inv'_j = inv_j (1 - r_j) +
  (inv_j / factor) r_j``, ``r_j = clip((j - lo) / (hi - lo), 0, 1)``, ``lo =
  floor(dim(beta_fast))``, ``hi = ceil(dim(beta_slow))``, ``dim(b) = rope
  ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta)``; cos / sin
  are multiplied by ``m(mscale) / m(mscale_all_dim)`` and the softmax scale
  by ``m(mscale_all_dim)^2``, ``m(a) = 0.1 a ln(factor) + 1``.
- Router and experts: ``ops/moe.py`` (sigmoid scores, the selection bias
  ``e_score_correction_bias`` chooses, ``norm_topk_prob``,
  ``routed_scaling_factor``; ``n_group = topk_group = 1``).
  ``y = sum_{e chosen} g_e E_e(x) + E_shared(x)``,
  ``E(x) = W_down (silu(W_gate x) * W_up x)``.

The published ``kv_b_proj`` is stored as its two halves ``w_uk [heads, nope,
kv_lora_rank]`` and ``w_uv [heads, kv_lora_rank, v_head_dim]`` (heads
leading: the batch dimension of both forms' products, so no form reshapes a
weight).  The multi-token-prediction module (``num_nextn_predict_layers``)
is not built: the main model's logits do not depend on it.

Parameters are BORN in the configured dtype (``falcon_h1._Born``): 9 GB of
bf16 weights are 18 GB in float32.  A fresh model draws every projection
N(0, 1 / fan_in).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import math

import jax
import jax.numpy as jnp

from paddle_tpu.autograd.engine import apply
from paddle_tpu.models.falcon_h1 import _Born, _Fixed, _Weight
from paddle_tpu.models.llama_decode import _rmsnorm as rmsnorm
from paddle_tpu.models.llama_decode import _rope_at, _rope_tables
from paddle_tpu.nn.layer.container import LayerList
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.ops.moe import expert_ffn, route

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteForCausalLM",
           "Glm4MoeLiteStatics", "rope_tables"]


@dataclass
class Glm4MoeLiteConfig:
    """The published ``config.json`` keys (defaults: GLM-4.7-Flash)."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 202752
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # None, or the published YaRN dict (``type``, ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``mscale``, ``mscale_all_dim``)
    rope_scaling: dict | None = None
    # residual streams: 1 = the plain ``h + branch``; above 1 every sub-layer
    # is hyper-connected (ops/hyper_connection.py) and the four keys below
    # are read
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    def __post_init__(self):
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("norm_topk_prob", True),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"glm4_moe_lite: {key}={getattr(self, key)!r} has no "
                    f"path (implemented: {want!r})")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotate-half)")
        if self.rope_scaling is not None \
                and self.rope_scaling.get("type") != "yarn":
            raise ValueError(
                f"glm4_moe_lite: rope_scaling type "
                f"{self.rope_scaling.get('type')!r} has no path "
                "(implemented: None or 'yarn')")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult={self.hc_mult}: a model has at least "
                             "one residual stream")

    # tiny preset used by the tests
    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=48, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128,
                    dtype="float32")
        base.update(kw)
        return Glm4MoeLiteConfig(**base)


class Glm4MoeLiteStatics(NamedTuple):
    """What the compiled programs close over of a configuration (hashable:
    a jit static, the serving programs' ``cfg``)."""
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    eps: float
    top_k: int
    route_scale: float
    # the residual path: streams (1 = plain), and the hyper-connection's
    # Sinkhorn steps, eps and (lo, hi) clamp
    hc: int = 1
    hc_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: tuple = (0.0, 0.0)
    # YaRN's factor on the softmax scale (1 without rope_scaling)
    scale_mult: float = 1.0

    @property
    def row(self):
        """Width of one cached latent row ``[c | k_r]``."""
        return self.kv_rank + self.rope

    @property
    def row_stored(self):
        """The row's width in the cache: whole 128-lane tiles.  The chip
        stores 128 lanes at a time, so a 576-wide row occupies 640 either
        way; stored unpadded, the TPU compiler makes POSITIONS the leaf's
        minor dimension to avoid the padding and copies the whole leaf
        into and out of the row-minor order on every run (chipless
        compile, PR 33)."""
        return -(-self.row // 128) * 128

    @property
    def scale(self):
        return float(self.nope + self.rope) ** -0.5 * self.scale_mult


def _yarn_mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def statics_of(c: Glm4MoeLiteConfig) -> Glm4MoeLiteStatics:
    rs = c.rope_scaling
    # a one-stream model's statics do not vary with keys it does not read
    iters, eps, clamp = (c.hc_sinkhorn_iters, float(c.hc_eps), (
        float(c.mhc_h_res_clamp_min), float(c.mhc_h_res_clamp_max))
    ) if c.hc_mult > 1 else (0, 0.0, (0.0, 0.0))
    return Glm4MoeLiteStatics(
        c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
        c.v_head_dim, c.kv_lora_rank, float(c.rms_norm_eps),
        c.num_experts_per_tok, float(c.routed_scaling_factor),
        hc=c.hc_mult, hc_iters=iters, hc_eps=eps, hc_clamp=clamp,
        scale_mult=1.0 if rs is None else _yarn_mscale(
            rs["factor"], rs.get("mscale_all_dim", 0)) ** 2)


def rope_tables(c: Glm4MoeLiteConfig, lmax, dtype):
    """cos / sin ``[lmax, rope]`` by the configuration's rule: the plain
    ``theta^(-2j/rope)`` frequencies, or YaRN's (module docstring; float32
    frequencies, the same two leaves)."""
    d, theta, rs = c.qk_rope_head_dim, float(c.rope_theta), c.rope_scaling
    if rs is None:
        return _rope_tables(lmax, d, theta, dtype)
    dim = lambda beta: d * math.log(rs["original_max_position_embeddings"]
                                    / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = max(math.floor(dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    j = jnp.arange(d // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = 1.0 / theta ** (2.0 * j / d)
    inv = inv * (1.0 - ramp) + inv / rs["factor"] * ramp
    freqs = jnp.outer(jnp.arange(lmax, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = _yarn_mscale(rs["factor"], rs.get("mscale", 1)) \
        / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    return (jnp.cos(emb) * m).astype(dtype), (jnp.sin(emb) * m).astype(dtype)


# ---------------------------------------------------------------- block math
# pure functions over ``lp``, a dict of one layer's weights (projections
# [in, out]): ln1, ln2, w_dq, q_norm, w_uq, w_dkv, kv_norm, w_uk [H, nope,
# rank], w_uv [H, rank, v], wo; a dense layer gate, up, down; an expert
# layer router [h, E], router_bias [E] float32, e_gate, e_up [E, h, f],
# e_down [E, f, h], s_gate, s_up, s_down (the shared expert); with hc > 1
# hc1_* / hc2_* (phi, b, alpha: the attention and the FFN sub-layer's
# hyper-connection, float32)
def stream_in(cfg, h):
    """The embedding ``[B, T, hidden]`` as the layers' state: itself, or
    ``hc`` copies of it side by side ``[B, T, hc * hidden]`` (the layout
    ``ops/hyper_connection.py`` argues)."""
    # (a concatenate, not jnp.tile: that is a broadcast to [.., hc, hidden]
    # and a reshape, which the TPU compiler makes a copy of the whole state)
    return h if cfg.hc == 1 else jnp.concatenate([h] * cfg.hc, axis=-1)


def stream_out(cfg, h):
    """The state behind the last layer as the head's input: itself, or the
    sum of its streams."""
    if cfg.hc == 1:
        return h
    with jax.named_scope("norm"):
        streams = h.reshape(h.shape[:-1] + (cfg.hc, -1))
        return jnp.sum(streams.astype(jnp.float32), axis=-2).astype(h.dtype)


def sublayer(lp, cfg, which, h, branch):
    """One residual sub-layer (``which``: 1 attention, 2 FFN) around
    ``branch(u [B, T, hidden]) -> (y, aux)``, the branch with its own
    pre-norm: ``h + y``, or with ``hc`` streams ``h [B, T, hc * hidden]``
    the hyper-connected read, branch and write.  Returns ``(h', aux)``."""
    if cfg.hc == 1:
        y, aux = branch(h)
        return h + y, aux
    # imported here: a model with one stream loads and traces none of it
    from paddle_tpu.ops import hyper_connection as hc

    pre, post, res = hc.coefficients(
        h, lp[f"hc{which}_phi"], lp[f"hc{which}_b"], lp[f"hc{which}_alpha"],
        n=cfg.hc, iters=cfg.hc_iters, eps=cfg.hc_eps, clamp=cfg.hc_clamp)
    y, aux = branch(hc.read(h, pre))
    return hc.write(h, res, post, y), aux


def mla_project(lp, cfg, u):
    """u [B, T, hidden] (normed) -> (q_nope [B, T, H, nope], q_rope
    [B, T, H, rope], c [B, T, rank] normed, k_r [B, T, 1, rope]), before
    RoPE."""
    b, t, _ = u.shape
    with jax.named_scope("attn.qkv"):
        c_q = rmsnorm(u @ lp["w_dq"], lp["q_norm"], cfg.eps)
        # the barrier keeps the products 2-D past the dots, as in
        # llama_decode._qkv: with the head reshape directly behind a dot
        # the TPU compiler re-lays-out the weight on every run
        q, ckv = jax.lax.optimization_barrier(
            (c_q @ lp["w_uq"], u @ lp["w_dkv"]))
        q = q.reshape(b, t, cfg.heads, cfg.nope + cfg.rope)
        c = rmsnorm(ckv[..., :cfg.kv_rank], lp["kv_norm"], cfg.eps)
        return (q[..., :cfg.nope], q[..., cfg.nope:], c,
                ckv[..., None, cfg.kv_rank:])


def attn_out(lp, cfg, o):
    """Per-head outputs [B, T, H, v] -> the branch's residual term."""
    b, t = o.shape[:2]
    with jax.named_scope("attn.out"):
        return o.reshape(b, t, cfg.heads * cfg.v_dim) @ lp["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def ffn(lp, cfg, h, live):
    """The FFN branch ``FFN(RMS(h))`` (the caller adds it): the dense
    SwiGLU, or the routed experts plus the shared one.  h [.., hidden];
    ``live`` [..] bool: rows that route (``ops/moe.py``).  Returns ``(y,
    experts int32 [.., k] or None)``."""
    with jax.named_scope("norm"):
        x = rmsnorm(h, lp["ln2"], cfg.eps)
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return swiglu(x, lp["gate"], lp["up"], lp["down"]), None
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    experts, gates = route(flat, lp["router"], lp["router_bias"],
                           cfg.top_k, cfg.route_scale)
    y = expert_ffn(flat, experts, gates, live.reshape(-1), lp["e_gate"],
                   lp["e_up"], lp["e_down"])
    with jax.named_scope("moe.shared"):
        y = y + swiglu(flat, lp["s_gate"], lp["s_up"], lp["s_down"])
    return y.reshape(h.shape), experts.reshape(*lead, cfg.top_k)


def block_forward(lp, cfg, h, cos_t, sin_t):
    """One layer over whole sequences from position 0, no cache: the
    EXPANDED attention (the plain model forward).  h [B, L, hidden], or
    [B, L, hc * hidden]; ``cos_t``, ``sin_t``: ``rope_tables``."""
    b, L = h.shape[:2]

    def attn(u):
        with jax.named_scope("norm"):
            u = rmsnorm(u, lp["ln1"], cfg.eps)
        q_nope, q_rope, c, k_r = mla_project(lp, cfg, u)
        with jax.named_scope("attn.rope"):
            q_rope, k_r = _rope_at(q_rope, k_r, cos_t, sin_t,
                                   jnp.arange(L, dtype=jnp.int32)[None])
        with jax.named_scope("mla.absorb"):
            k_nope = jnp.einsum("blc,hdc->blhd", c, lp["w_uk"])
            v = jnp.einsum("blc,hcd->blhd", c, lp["w_uv"])
        with jax.named_scope("attn.core"):
            s = (jnp.einsum("blhd,bmhd->bhlm", q_nope, k_nope)
                 + jnp.einsum("blhd,bmd->bhlm", q_rope, k_r[:, :, 0])
                 ).astype(jnp.float32) * cfg.scale
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
            o = jnp.einsum("bhlm,bmhd->blhd",
                           jax.nn.softmax(s, -1).astype(v.dtype), v)
        return attn_out(lp, cfg, o), None

    h, _ = sublayer(lp, cfg, 1, h, attn)
    live = jnp.ones((b, L), bool)
    return sublayer(lp, cfg, 2, h, lambda u: ffn(lp, cfg, u, live))[0]


# -------------------------------------------------------------- the Layer
_ones = _Fixed(jnp.ones)


def _proj(shape, dtype, fan_in):
    return _Weight(shape, dtype, _Born(fan_in ** -0.5))


class Glm4MoeLiteAttention(Layer):
    def __init__(self, c):
        super().__init__()
        h, heads = c.hidden_size, c.num_attention_heads
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_a_proj = _proj((h, c.q_lora_rank), c.dtype, h)
        self.q_a_layernorm = _Weight((c.q_lora_rank,), c.dtype, _ones)
        self.q_b_proj = _proj((c.q_lora_rank, heads * qk), c.dtype,
                              c.q_lora_rank)
        self.kv_a_proj_with_mqa = _proj(
            (h, c.kv_lora_rank + c.qk_rope_head_dim), c.dtype, h)
        self.kv_a_layernorm = _Weight((c.kv_lora_rank,), c.dtype, _ones)
        # kv_b_proj's two halves, heads leading
        self.k_b_proj = _proj((heads, c.qk_nope_head_dim, c.kv_lora_rank),
                              c.dtype, c.kv_lora_rank)
        self.v_b_proj = _proj((heads, c.kv_lora_rank, c.v_head_dim),
                              c.dtype, c.kv_lora_rank)
        self.o_proj = _proj((heads * c.v_head_dim, h), c.dtype,
                            heads * c.v_head_dim)


class Glm4MoeLiteMLP(Layer):
    def __init__(self, c, width):
        super().__init__()
        h = c.hidden_size
        self.gate_proj = _proj((h, width), c.dtype, h)
        self.up_proj = _proj((h, width), c.dtype, h)
        self.down_proj = _proj((width, h), c.dtype, width)


class Glm4MoeLiteGate(Layer):
    """``weight`` [hidden, E] in the model's dtype and the float32
    selection bias ``e_score_correction_bias`` [E]."""

    def __init__(self, c):
        super().__init__()
        h, e = c.hidden_size, c.n_routed_experts
        self.weight = self.create_parameter(
            [h, e], dtype=c.dtype, default_initializer=_Born(h ** -0.5))
        self.e_score_correction_bias = self.create_parameter(
            [e], dtype="float32", default_initializer=_Fixed(jnp.zeros))


class Glm4MoeLiteMoE(Layer):
    """Router (``gate.weight`` [hidden, E], ``gate.e_score_correction_bias``
    float32), the routed experts stacked ``[E, ..]`` and the shared one."""

    def __init__(self, c):
        super().__init__()
        h, e, f = c.hidden_size, c.n_routed_experts, c.moe_intermediate_size
        self.gate = Glm4MoeLiteGate(c)
        self.experts_gate = _proj((e, h, f), c.dtype, h)
        self.experts_up = _proj((e, h, f), c.dtype, h)
        self.experts_down = _proj((e, f, h), c.dtype, f)
        self.shared_experts = Glm4MoeLiteMLP(c, f * c.n_shared_experts)


class Glm4MoeLiteHyperConnection(Layer):
    """One sub-layer's mixing parameters (``ops/hyper_connection.py``), born
    in float32 whatever the model's dtype: ``phi [hc C, hc^2 + 2 hc]``
    N(0, 1 / (hc C)), ``alpha`` ones, ``b`` zero but for 2 on the diagonal
    of its ``res`` part (a fresh model keeps most of each stream where it
    is)."""

    def __init__(self, c):
        super().__init__()
        n, wide = c.hc_mult, c.hc_mult * c.hidden_size
        cols = n * n + 2 * n
        self.phi = self.create_parameter(
            [wide, cols], dtype="float32",
            default_initializer=_Born(wide ** -0.5))
        self.b = self.create_parameter(
            [cols], dtype="float32", default_initializer=_Fixed(
                lambda shape: jnp.concatenate(
                    [jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])))
        self.alpha = self.create_parameter(
            [3], dtype="float32", default_initializer=_Fixed(jnp.ones))


class Glm4MoeLiteDecoderLayer(Layer):
    def __init__(self, c, dense):
        super().__init__()
        self.input_layernorm = _Weight((c.hidden_size,), c.dtype, _ones)
        self.self_attn = Glm4MoeLiteAttention(c)
        self.post_attention_layernorm = _Weight((c.hidden_size,), c.dtype,
                                                _ones)
        self.mlp = (Glm4MoeLiteMLP(c, c.intermediate_size) if dense
                    else Glm4MoeLiteMoE(c))
        if c.hc_mult > 1:
            self.attn_hc = Glm4MoeLiteHyperConnection(c)
            self.mlp_hc = Glm4MoeLiteHyperConnection(c)

    def weights(self):
        """The layer's weights under the names the pure functions read
        (``lp``), as parameters."""
        a, m = self.self_attn, self.mlp
        lp = {
            "ln1": self.input_layernorm.weight,
            "ln2": self.post_attention_layernorm.weight,
            "w_dq": a.q_a_proj.weight, "q_norm": a.q_a_layernorm.weight,
            "w_uq": a.q_b_proj.weight,
            "w_dkv": a.kv_a_proj_with_mqa.weight,
            "kv_norm": a.kv_a_layernorm.weight,
            "w_uk": a.k_b_proj.weight, "w_uv": a.v_b_proj.weight,
            "wo": a.o_proj.weight,
        }
        if isinstance(m, Glm4MoeLiteMLP):
            lp.update(gate=m.gate_proj.weight, up=m.up_proj.weight,
                      down=m.down_proj.weight)
        else:
            s = m.shared_experts
            lp.update(router=m.gate.weight,
                      router_bias=m.gate.e_score_correction_bias,
                      e_gate=m.experts_gate.weight,
                      e_up=m.experts_up.weight,
                      e_down=m.experts_down.weight,
                      s_gate=s.gate_proj.weight, s_up=s.up_proj.weight,
                      s_down=s.down_proj.weight)
        for which, name in ((1, "attn_hc"), (2, "mlp_hc")):
            hc = getattr(self, name, None)
            if hc is not None:
                lp.update({f"hc{which}_phi": hc.phi, f"hc{which}_b": hc.b,
                           f"hc{which}_alpha": hc.alpha})
        return lp


class Glm4MoeLiteModel(Layer):
    def __init__(self, c):
        super().__init__()
        self.embed_tokens = _Weight((c.vocab_size, c.hidden_size), c.dtype,
                                    _Born(1.0))
        self.layers = LayerList([
            Glm4MoeLiteDecoderLayer(c, dense=i < c.first_k_dense_replace)
            for i in range(c.num_hidden_layers)])
        self.norm = _Weight((c.hidden_size,), c.dtype, _ones)


class Glm4MoeLiteForCausalLM(Layer):
    """``forward(input_ids [B, L]) -> logits [B, L, vocab]`` float32: the
    whole sequence from position 0 (expanded attention).  Served through
    ``paddle_tpu.serving.ServingEngine`` like any other model (the family
    of ``serving_family()``: ``models/glm4_moe_lite_decode.py``)."""

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        self.config = config
        self.model = Glm4MoeLiteModel(config)
        self.lm_head = _proj((config.hidden_size, config.vocab_size),
                             config.dtype, config.hidden_size)

    def serving_family(self):
        from paddle_tpu.models.glm4_moe_lite_decode import (
            GLM4_MOE_LITE_FAMILY)

        return GLM4_MOE_LITE_FAMILY

    def forward(self, input_ids):
        cfg = statics_of(self.config)
        cos_t, sin_t = rope_tables(self.config, input_ids.shape[1],
                                   self.model.embed_tokens.weight.data.dtype)
        with jax.named_scope("embed"):
            h = apply("glm4_moe_lite_embed",
                      lambda e, ids: stream_in(cfg, e[ids]),
                      self.model.embed_tokens.weight, input_ids)
        for layer in self.model.layers:
            names, params = zip(*layer.weights().items())
            h = apply("glm4_moe_lite_block",
                      lambda x, *ws: block_forward(
                          dict(zip(names, ws)), cfg, x, cos_t, sin_t),
                      h, *params)

        def head(x, norm, w):
            x = stream_out(cfg, x)
            with jax.named_scope("norm"):
                x = rmsnorm(x, norm, cfg.eps)
            with jax.named_scope("lm_head"):
                return (x @ w).astype(jnp.float32)

        return apply("glm4_moe_lite_head", head, h, self.model.norm.weight,
                     self.lm_head.weight)
