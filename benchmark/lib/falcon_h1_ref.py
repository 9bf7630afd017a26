"""The plain reference of Falcon-H1 (``model_type: falcon_h1``): every block
runs a Mamba-2 mixer and a GQA attention in parallel on the same normed
input, sums both into the residual, then a SwiGLU MLP; muP multipliers sit on
nearly every edge.  Straightforward ``jax.numpy`` in float32 at matmul
precision "highest" — no kernels, no cache, no chunked scan, and nothing
imported from the program.  Weights come from ``lib/falcon_h1_weights.py``
(the seed), one block at a time, and the head runs over the vocabulary in
blocks, so a float32 copy of one block is all that is ever resident.

The equations (config keys in backticks; ``RMS(x; w)`` = RMSNorm with
``rms_norm_eps``):

- ``h0 = E[tok] * embedding_multiplier``.
- Block: ``u = RMS(h; input_layernorm)``.
  - Attention: ``a = u * attention_in_multiplier``; ``q = a Wq``,
    ``k = (a Wk) * key_multiplier``, ``v = a Wv``; rotate-half RoPE over the
    whole head dim on q and k; causal ``softmax(q k^T / sqrt(head_dim)) v``;
    ``A = (. Wo) * attention_out_multiplier``.  No biases.
  - SSM: ``s = u * ssm_in_multiplier``; ``p = (s W_in) * m`` with ``m``
    scaling the segments ``[z | x | B | C | dt]`` by ``ssm_multipliers``;
    ``xBC = silu(causal depthwise conv([x|B|C], kernel mamba_d_conv, bias))``;
    ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head ``hd`` of
    group ``g = hd // (heads / groups)``:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_{t,g}``,
    ``y_t = S_t C_{t,g} + D x_t``; gated norm (``mamba_rms_norm`` true,
    ``mamba_norm_before_gate`` false): ``y = RMS_grouped(y * silu(z); w)``
    over ``mamba_n_groups`` groups; ``M = (y W_out) * ssm_out_multiplier``.
  - ``h = h + A + M``.
  - MLP: ``v = RMS(h; pre_ff_layernorm)``;
    ``h = h + down(up(v) * silu(gate(v) * mlp_multipliers[0]))
    * mlp_multipliers[1]``.
- ``logits = lm_head(RMS(h; final_layernorm)) * lm_head_multiplier``.

Departures from the published implementation, none of which the catalog
row's ``config`` settles (they are the configuration file's ``assumed``):
the SSM recurrence is the token-by-token one under ``lax.scan`` (the
published code runs the chunked form of the same recurrence); ``dt`` is not
clamped (the published ``time_step_limit`` is ``(0, inf)``); the gated
norm's groups are ``mamba_n_groups`` contiguous slices of ``mamba_d_ssm``.

``quant="fp8"`` is the control, not the reference: every projection's
operands (weights per output channel, activations per token) are rounded
through float8_e4m3 — the precision below the configuration's bfloat16.  The
recurrence itself stays float32 in the control.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import falcon_h1_weights as W
from benchmark.lib.llama_ref import HI, attention, f32, mm, rmsnorm, rope

VOCAB_BLOCKS = 8


def conv1d_causal(x, w, b):
    """Depthwise causal convolution over time: x [L, C], w [K, C] (``w[K-1]``
    multiplies the current input), b [C]; zeros before the sequence."""
    k, L = w.shape[0], x.shape[0]
    xx = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    return b + sum(xx[i:i + L] * w[i] for i in range(k))


def ssm_recurrence(x, dt, a, bm, cm, d):
    """The state-space recurrence, one token at a time.  x [L, H, P],
    dt [L, H], a [H], bm/cm [L, G, N], d [H] -> y [L, H, P]."""
    H, P = x.shape[1:]
    G, N = bm.shape[1:]

    def step(S, inp):
        xt, dtt, bt, ct = inp
        bh, ch = jnp.repeat(bt, H // G, 0), jnp.repeat(ct, H // G, 0)
        S = (jnp.exp(dtt * a)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        y = jnp.einsum("hpn,hn->hp", S, ch, precision=HI) + d[:, None] * xt
        return S, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, bm, cm))
    return y


def ssm_mixer(p, u, d, eps, quant=None):
    """The Mamba-2 branch over one sequence: u [L, hidden] (normed)."""
    L = u.shape[0]
    segs = W.in_proj_segments(d)
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32)
                           for w, m in zip(segs, d.ssm_mults)])
    proj = mm(u * d.ssm_in, p["w_in"], quant) * mup
    cuts = np.cumsum(segs)[:-1]
    z, x, bm, cm, dt = jnp.split(proj, cuts, axis=-1)
    xbc = jax.nn.silu(conv1d_causal(jnp.concatenate([x, bm, cm], -1),
                                    p["conv_w"], p["conv_b"]))
    x, bm, cm = jnp.split(xbc, cuts[1:3] - segs[0], axis=-1)
    H, P = d.ssm_heads, d.d_ssm // d.ssm_heads
    y = ssm_recurrence(
        x.reshape(L, H, P), jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["a_log"]), bm.reshape(L, d.groups, d.d_state),
        cm.reshape(L, d.groups, d.d_state), p["d"]).reshape(L, d.d_ssm)
    y = (y * jax.nn.silu(z)).reshape(L, d.groups, d.d_ssm // d.groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return mm(y.reshape(L, d.d_ssm) * p["norm_w"], p["w_out"], quant) \
        * d.ssm_out


def layer(p, h, d, eps, theta, quant=None):
    """One block over one sequence h [L, hidden] (float32)."""
    L = h.shape[0]
    u = rmsnorm(h, p["ln1"], eps)
    a = u * d.attn_in
    q = rope(mm(a, p["wq"], quant).reshape(L, d.heads, d.head_dim), theta)
    k = rope((mm(a, p["wk"], quant) * d.key_mult).reshape(
        L, d.kv_heads, d.head_dim), theta)
    v = mm(a, p["wv"], quant).reshape(L, d.kv_heads, d.head_dim)
    attn = mm(attention(q, k, v), p["wo"], quant) * d.attn_out
    h = h + attn + ssm_mixer(p, u, d, eps, quant)
    x = rmsnorm(h, p["ln2"], eps)
    return h + mm(mm(x, p["up"], quant)
                  * jax.nn.silu(mm(x, p["gate"], quant) * d.mlp_mults[0]),
                  p["down"], quant) * d.mlp_mults[1]


@functools.partial(jax.jit, static_argnames=("d", "eps", "theta", "quant"))
def _layer_rows(p, hs, d, eps, theta, quant):
    pf = f32(p)
    return jax.lax.map(lambda h: layer(pf, h, d, eps, theta, quant), hs)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_rows(norm, hs, rows, eps):
    h = jnp.take_along_axis(hs, rows[:, :, None], axis=1)
    return rmsnorm(h, norm.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("n", "mult", "quant"))
def _head_block(lm_head, h, i, n, mult, quant):
    """Logits of vocabulary block ``i`` of ``n`` (columns of ``lm_head``;
    the control's scales are per output channel and per token, so a block
    of columns reads what the whole matrix would).  ``i`` is traced: one
    compiled program for every block."""
    width = lm_head.shape[1] // n
    w = jax.lax.dynamic_slice_in_dim(lm_head, i * width, width, axis=1)
    return mm(h, w.astype(jnp.float32), quant) * mult


def serve_logits(m, seed, dtype, tokens, rows, quants=(None,)):
    """Full-forward logits of padded sequences.

    ``tokens`` [R, L] int32 (right-padded: a pad is causally invisible to
    every real position, in the attention, the convolution and the
    recurrence alike), ``rows`` [R, K] the positions whose next-token
    logits are wanted.  Returns one ``[R, K, vocab]`` float32 numpy array
    per entry of ``quants`` (None = the reference, "fp8" = the control)."""
    d = W.dims_of(m)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    embed = W.top_leaf(seed, d, dtype, "embed")
    h0 = embed[tokens].astype(jnp.float32) * d.embed_mult
    del embed
    hs = [h0 for _ in quants]
    for i in range(m["num_hidden_layers"]):
        p = W.layer_weights(seed, i, d, dtype)
        hs = [_layer_rows(p, h, d=d, eps=eps, theta=theta, quant=q)
              for h, q in zip(hs, quants)]
    norm = W.top_leaf(seed, d, dtype, "norm")
    hs = [_final_rows(norm, h, rows, eps=eps) for h in hs]
    lm_head = W.top_leaf(seed, d, dtype, "lm_head")
    n = VOCAB_BLOCKS if d.vocab % VOCAB_BLOCKS == 0 else 1
    return [np.concatenate(
        [np.asarray(_head_block(lm_head, h, jnp.int32(i), n=n,
                                mult=d.head_mult, quant=q))
         for i in range(n)], axis=-1)
        for h, q in zip(hs, quants)]
