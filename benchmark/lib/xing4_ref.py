"""The plain reference of Xing4.0-29B-A4B (``model_type: xing4_0``): a token's
state is ``n = hc_mult`` residual streams under manifold-constrained
hyper-connections (mHC, arXiv:2512.24880), every sub-layer's branch is
GLM-4.7-Flash's (multi-head latent attention in its EXPANDED form over the
whole sequence with no cache; a sigmoid router with a selection bias, routed
experts in a plain loop over ALL experts, a shared expert), and positions are
YaRN-scaled.  Straightforward ``jax.numpy`` in float32 at matmul precision
"highest" — no kernels, no cache, no grouped product, nothing imported from
the program.  Weights come from ``lib/xing4_weights.py`` (the seed), one
layer at a time, an expert's float32 copy one at a time, the head over the
vocabulary in blocks.

The equations (config keys in backticks; C = ``hidden_size``; ``RMS`` =
RMSNorm with ``rms_norm_eps``; sigma = the logistic function):

- The stream.  ``X`` in R^{n x C}; ``X_0`` = the token's embedding in every
  row; after the last layer ``h = sum_i X_i``, then the final RMS and the
  head.
- A hyper-connected sub-layer (two a layer: attention, then FFN; ``F`` the
  branch with its pre-norm; each has its own ``phi``, ``b``, ``alpha``):
  ``x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``;
  ``H~ = alpha_g (x~ phi) + b`` (columns ``[pre | post | res row-major]``,
  ``alpha_g`` the group's scalar);
  ``H_pre = sigma(H~_pre)``, ``H_post = 2 sigma(H~_post)``,
  ``H_res = Sinkhorn(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))``:
  ``M = exp(.)``, then ``hc_sinkhorn_iters`` times every column divided by
  (its sum + ``hc_eps``), then every row by (its sum + ``hc_eps``);
  ``u = H_pre X``, ``y = F(u)``, ``X' = H_res X + H_post^T y``.
- MLA: GLM's equations (``lib/glm4_moe_lite_ref.py``) with two changes from
  YaRN (``rope_scaling``; the DeepSeek-V3-shaped modelling code's forms).
  The rotary frequencies ``inv_j = theta^(-2j/rope)`` become ``inv_j (1 -
  r_j) + (inv_j / factor) r_j``, ``r_j = clip((j - lo) / (hi - lo), 0, 1)``,
  ``lo = floor(dim(beta_fast))``, ``hi = ceil(dim(beta_slow))``, ``dim(b) =
  rope ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta)``;
  cos / sin are multiplied by ``m(mscale) / m(mscale_all_dim)`` and the
  softmax scale is ``(nope + rope)^(-1/2) m(mscale_all_dim)^2``, ``m(a) = 0.1
  a ln(factor) + 1``.
- Router, experts, dense FFN, logits: ``lib/glm4_moe_lite_ref.py``'s
  ``choose``, ``moe``, ``swiglu`` as they are (the same equations).

Departures from the published description, each also under ``assumed`` in
the configuration file: the hyper-connection wraps the attention and the FFN
sub-layer separately, streams start as copies of the embedding and end as
their sum (the Hyper-Connections paper's arrangement); the clamp acts on
``H~_res`` before the exponential; ``hc_eps`` sits in the Sinkhorn
denominators and in the flattened norm, which has no learned gain; the rope
pairing is rotate-half; the multi-token-prediction module is not built.

``routes=`` / ``stats=`` / ``chosen=`` and ``quant="fp8"`` (the control: every
projection's operands through float8_e4m3; the router AND the
hyper-connection's coefficients stay float32, as the configuration computes
them) are ``lib/glm4_moe_lite_ref.py``'s, unchanged in meaning.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import xing4_weights as W
from benchmark.lib.glm4_moe_lite_ref import (VOCAB_BLOCKS, _head_block, f32,
                                             moe, swiglu)
from benchmark.lib.llama_ref import HI, mm, rmsnorm

def mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_rule(m):
    """(the rotary frequencies ``[rope / 2]`` as a tuple, the factor on cos
    and sin, the softmax scale) from the published keys."""
    d, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    inv = [theta ** (-2.0 * j / d) for j in range(d // 2)]
    plain = float(m["qk_nope_head_dim"] + d) ** -0.5
    rs = m.get("rope_scaling")
    if rs is None:
        return tuple(inv), 1.0, plain
    if rs["type"] != "yarn":
        raise ValueError(f"no reference for rope_scaling {rs['type']!r}")
    dim = lambda beta: d * math.log(
        rs["original_max_position_embeddings"] / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = max(math.floor(dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    ramp = [min(max((j - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
            for j in range(d // 2)]
    inv = [f * (1 - r) + f / rs["factor"] * r for f, r in zip(inv, ramp)]
    m_all = mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    return (tuple(inv), mscale(rs["factor"], rs.get("mscale", 1)) / m_all,
            plain * m_all ** 2)


def rope(x, inv, mult):
    """x [L, H, D], positions 0..L-1, rotate-half, frequencies ``inv``."""
    f = jnp.outer(jnp.arange(x.shape[0], dtype=jnp.float32),
                  jnp.asarray(inv, jnp.float32))
    cos = (jnp.cos(jnp.concatenate([f, f], -1)) * mult)[:, None, :]
    sin = (jnp.sin(jnp.concatenate([f, f], -1)) * mult)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mla(p, u, d, eps, rule, quant=None):
    """Expanded latent attention over one sequence: u [L, hidden]
    (normed)."""
    inv, mult, scale = rule
    L = u.shape[0]
    c_q = rmsnorm(mm(u, p["w_dq"], quant), p["q_norm"], eps)
    q = mm(c_q, p["w_uq"], quant).reshape(L, d.heads, d.nope + d.rope)
    ckv = mm(u, p["w_dkv"], quant)
    c = rmsnorm(ckv[:, :d.kv_rank], p["kv_norm"], eps)
    k_r = rope(ckv[:, None, d.kv_rank:], inv, mult)[:, 0]       # [L, rope]
    q_r = rope(q[..., d.nope:], inv, mult)
    mask = jnp.tril(jnp.ones((L, L), bool))

    def head(args):
        qn, qr, w_uk, w_uv = args
        k_nope, v = mm(c, w_uk.T, quant), mm(c, w_uv, quant)
        s = (jnp.einsum("ld,md->lm", qn, k_nope, precision=HI)
             + jnp.einsum("ld,md->lm", qr, k_r, precision=HI)) * scale
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("lm,md->ld", pr, v, precision=HI)

    o = jax.lax.map(head, (q[..., :d.nope].transpose(1, 0, 2),
                           q_r.transpose(1, 0, 2), p["w_uk"], p["w_uv"]))
    return mm(o.transpose(1, 0, 2).reshape(L, d.heads * d.v_dim), p["wo"],
              quant)


def sinkhorn(logits, iters, eps):
    """``[.., n, n]`` -> doubly stochastic to within the iteration's
    error."""
    mat = jnp.exp(logits)
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, axis=-2, keepdims=True) + eps)   # columns
        mat = mat / (jnp.sum(mat, axis=-1, keepdims=True) + eps)   # rows
    return mat


def hyper(p, which, X, hc):
    """One sub-layer's coefficients from the state X [L, n, C]: (H_pre
    [L, n], H_post [L, n], H_res [L, n, n]).  ``hc`` = (iters, eps, lo,
    hi)."""
    iters, eps, lo, hi = hc
    L, n, _ = X.shape
    x = X.reshape(L, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    a, b = p[f"hc{which}_alpha"], p[f"hc{which}_b"]
    t = jnp.matmul(x, p[f"hc{which}_phi"], precision=HI)
    pre = jax.nn.sigmoid(a[0] * t[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * t[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * t[:, 2 * n:] + b[2 * n:]).reshape(L, n, n)
    return pre, post, sinkhorn(jnp.clip(res, lo, hi), iters, eps)


def sublayer(p, which, X, hc, branch):
    """``X' = H_res X + H_post^T F(H_pre X)`` over one sequence X
    [L, n, C]; ``branch(u [L, C]) -> (y, aux)``."""
    pre, post, res = hyper(p, which, X, hc)
    y, aux = branch(jnp.einsum("ln,lnc->lc", pre, X, precision=HI))
    return (jnp.einsum("lij,ljc->lic", res, X, precision=HI)
            + post[:, :, None] * y[:, None, :]), aux


def layer(p, X, routes, d, eps, rule, hc, margin, quant=None):
    """One layer over one sequence X [L, n, C] (float32)."""
    X, _ = sublayer(p, 1, X, hc, lambda u: (
        mla(p, rmsnorm(u, p["ln1"], eps), d, eps, rule, quant), None))

    def ffn(u):
        x = rmsnorm(u, p["ln2"], eps)
        if "router" not in p:
            return swiglu(x, p["gate"], p["up"], p["down"], quant), {}
        return moe(p, x, d, routes, margin, quant)

    return sublayer(p, 2, X, hc, ffn)


@functools.partial(jax.jit, static_argnames=("d", "eps", "rule", "hc",
                                             "margin", "quant"))
def _layer_rows(p, Xs, routes, d, eps, rule, hc, margin, quant):
    # an expert's float32 copy is made inside the loop over experts
    pf = f32(p, skip=("e_gate", "e_up", "e_down"))
    return jax.lax.map(
        lambda a: layer(pf, a[0], a[1], d, eps, rule, hc, margin, quant),
        (Xs, routes))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_rows(norm, Xs, rows, eps):
    h = jnp.sum(jnp.take_along_axis(Xs, rows[:, :, None, None], axis=1),
                axis=2)
    return rmsnorm(h, norm.astype(jnp.float32), eps)


def serve_logits(m, seed, dtype, tokens, rows, quants=(None,), routes=None,
                 route_margin=0.0, stats=None, chosen=None):
    """Full-forward logits of padded sequences:
    ``lib/glm4_moe_lite_ref.serve_logits``'s contract, argument for
    argument (``tokens`` [R, L] right-padded, ``rows`` [R, K], ``routes``
    [R, L, L_moe, k] followed within ``route_margin``, ``stats``,
    ``chosen``); one ``[R, K, vocab]`` float32 numpy array per entry of
    ``quants``."""
    d = W.dims_of(m)
    gd = W.shared_dims(d)
    eps, rule = float(m["rms_norm_eps"]), rope_rule(m)
    hc = (int(m["hc_sinkhorn_iters"]), float(m["hc_eps"]),
          float(m["mhc_h_res_clamp_min"]), float(m["mhc_h_res_clamp_max"]))
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    R, L = tokens.shape
    none = jnp.full((R, L, d.top_k), -1, jnp.int32)
    embed = W.top_leaf(seed, gd, dtype, "embed")
    X0 = jnp.repeat(embed[tokens].astype(jnp.float32)[:, :, None, :],
                    d.streams, axis=2)
    del embed
    Xs = [X0 for _ in quants]
    totals, used, li = {}, [[] for _ in quants], 0
    for i in range(m["num_hidden_layers"]):
        p = W.layer_weights(seed, i, d, dtype)
        dense = W.is_dense(d, i)
        for j, q in enumerate(quants):
            rec = none if routes is None or q is not None or dense \
                else jnp.asarray(routes[:, :, li], jnp.int32)
            Xs[j], st = _layer_rows(p, Xs[j], rec, d=gd, eps=eps, rule=rule,
                                    hc=hc, margin=float(route_margin),
                                    quant=q)
            if st:
                used[j].append(np.asarray(st.pop("chosen")))
            if q is None:
                for k, v in st.items():
                    totals.setdefault(k, []).append(np.asarray(v))
        li += not dense
    if chosen is not None:
        chosen.extend(np.stack(u, axis=2) for u in used)
    if stats is not None and totals:
        short = np.concatenate([s.ravel() for s in totals.pop("short")])
        stats.update({k: int(np.sum(v)) for k, v in totals.items()})
        stats["short"] = short[short >= 0]
    norm = W.top_leaf(seed, gd, dtype, "norm")
    hs = [_final_rows(norm, X, rows, eps=eps) for X in Xs]
    lm_head = W.top_leaf(seed, gd, dtype, "lm_head")
    n = VOCAB_BLOCKS if d.vocab % VOCAB_BLOCKS == 0 else 1
    return [np.concatenate(
        [np.asarray(_head_block(lm_head, h, jnp.int32(i), n=n, quant=q))
         for i in range(n)], axis=-1)
        for h, q in zip(hs, quants)]
