"""The plain reference: a Llama-shaped decoder (Mistral-7B's published
equations) in straightforward ``jax.numpy`` and float32 at matmul precision
"highest" — no kernels, no cache, no batching tricks, and nothing imported
from the program.  Weights come from ``lib/weights.py`` (the seed), one
layer at a time, so the float32 copy of a layer is all that is ever
resident.

``quant="fp8"`` is the control, not the reference: every projection's
inputs (weights per output channel, activations per token) are rounded
through float8_e4m3 — the precision below the configuration's bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights as W

HI = jax.lax.Precision.HIGHEST


def _fp8(x, axis):
    """x rounded through float8_e4m3 with one scale per slice along
    ``axis`` (absmax -> 448, the format's largest value)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.maximum(s, 1e-30)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm_fp8(x, w):
    """x [.., K] @ w [K, N] with both operands in fp8 — activations scaled
    per token, weights per output channel.  The backward is in fp8 too: the
    two products of the cotangent, with the cotangent scaled per token."""
    return jnp.matmul(_fp8(x, -1), _fp8(w, 0), precision=HI)


def _mm_fp8_fwd(x, w):
    return _mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, g):
    x, w = res
    gq = _fp8(g, -1)
    dx = jnp.matmul(gq, _fp8(w, 0).T, precision=HI)
    dw = jnp.matmul(_fp8(x, -1).reshape(-1, x.shape[-1]).T,
                    gq.reshape(-1, g.shape[-1]), precision=HI)
    return dx, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(x, w, quant=None):
    if quant == "fp8":
        return _mm_fp8(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [.., L, H, D], positions 0..L-1, half-rotation (rotate_half)."""
    L, d = x.shape[-3], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = jnp.outer(jnp.arange(L, dtype=jnp.float32), inv)
    cos = jnp.cos(jnp.concatenate([f, f], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([f, f], -1))[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v):
    """Causal softmax attention, one sequence.  q [L, H, D], k/v
    [L, Hkv, D]; query head h reads kv head h // (H / Hkv).  One kv group
    at a time so the [G, L, L] scores are all that is live."""
    L, H, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(L, Hkv, H // Hkv, D).transpose(1, 2, 0, 3)   # [Hkv,G,L,D]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [Hkv,L,D]
    mask = jnp.tril(jnp.ones((L, L), bool))

    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("gld,md->glm", qh, kh, precision=HI) * D ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("glm,md->gld", p, vh, precision=HI)

    o = jax.lax.map(one, (qg, kg, vg))                          # [Hkv,G,L,D]
    return o.transpose(2, 0, 1, 3).reshape(L, H * D)


def layer(p, h, dims, eps, theta, quant=None):
    """One decoder layer over one sequence h [L, hidden] (float32)."""
    _, _, nh, nkv, d, _ = dims
    L = h.shape[0]
    x = rmsnorm(h, p["ln1"], eps)
    q = rope(mm(x, p["wq"], quant).reshape(L, nh, d), theta)
    k = rope(mm(x, p["wk"], quant).reshape(L, nkv, d), theta)
    v = mm(x, p["wv"], quant).reshape(L, nkv, d)
    h = h + mm(attention(q, k, v), p["wo"], quant)
    x = rmsnorm(h, p["ln2"], eps)
    return h + mm(jax.nn.silu(mm(x, p["gate"], quant)) * mm(x, p["up"], quant),
                  p["down"], quant)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "quant"))
def _layer_rows(p, hs, dims, eps, theta, quant):
    pf = f32(p)
    return jax.lax.map(lambda h: layer(pf, h, dims, eps, theta, quant), hs)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_rows(top, hs, rows, eps, quant):
    """Logits [R, K, vocab] of the ``rows`` [R, K] positions of hs."""
    top = f32(top)
    h = jnp.take_along_axis(hs, rows[:, :, None], axis=1)
    return mm(rmsnorm(h, top["norm"], eps), top["lm_head"], quant)


def serve_logits(m, seed, dtype, tokens, rows, quants=(None,)):
    """Full-forward logits of padded sequences.

    ``tokens`` [R, L] int32 (right-padded: a pad is causally invisible to
    every real position), ``rows`` [R, K] the positions whose next-token
    logits are wanted.  Returns one ``[R, K, vocab]`` float32 numpy array
    per entry of ``quants`` (None = the reference, "fp8" = the control).
    Each layer's weights are made from the seed, used and dropped."""
    dims = W.dims_of(m)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    top = W.top_weights(seed, dims, dtype)
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    hs = [top["embed"].astype(jnp.float32)[tokens] for _ in quants]
    for i in range(m["num_hidden_layers"]):
        p = W.layer_weights(seed, i, dims, dtype)
        hs = [_layer_rows(p, h, dims=dims, eps=eps, theta=theta, quant=q)
              for h, q in zip(hs, quants)]
    return [np.asarray(_head_rows(top, h, rows, eps=eps, quant=q))
            for h, q in zip(hs, quants)]


# ----------------------------------------------------------------- training
def _head_loss(top, h, labels, eps, quant):
    """Summed next-token cross-entropy of one row: h [L, hidden] (the last
    layer's output), labels [L] (position t is scored against token t+1)."""
    logits = mm(rmsnorm(h[:-1], top["norm"], eps), top["lm_head"], quant)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[1:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "quant"))
def _layer_fwd(p, h, dims, eps, theta, quant):
    return layer(f32(p), h, dims, eps, theta, quant)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "quant"))
def _layer_bwd(p, h, g, dims, eps, theta, quant):
    """(d loss / d layer weights, d loss / d layer input) of one row."""
    _, vjp = jax.vjp(lambda pp, hh: layer(pp, hh, dims, eps, theta, quant),
                     f32(p), h)
    return vjp(g)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_bwd(top, h, labels, eps, quant):
    """Row loss (sum), d/d(norm, lm_head), d/d h."""
    t = {"norm": top["norm"].astype(jnp.float32),
         "lm_head": top["lm_head"].astype(jnp.float32)}
    loss, (gt, gh) = jax.value_and_grad(
        lambda tt, hh: _head_loss(tt, hh, labels, eps, quant), (0, 1))(t, h)
    return loss, gt, gh


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_fwd(top, h, labels, eps, quant):
    return _head_loss(f32({"norm": top["norm"], "lm_head": top["lm_head"]}),
                      h, labels, eps, quant)


@jax.jit
def _embed_bwd(embed, ids, g):
    return jnp.zeros(embed.shape, jnp.float32).at[ids].add(g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("opt",))
def _adamw(p, grads, opt):
    """AdamW after ``t = len(grads)`` steps, the moments rebuilt from every
    gradient so far (m_t = (1-b1) sum b1^(t-k) g_k): no moment is stored.
    Parameters are kept in their storage type, as the configuration states
    (bf16, no float32 master copy): the update is computed in float32 and
    rounded once."""
    lr, b1, b2, eps, wd = opt
    t = len(grads)
    m = sum((1 - b1) * b1 ** (t - k) * g for k, g in enumerate(grads, 1))
    v = sum((1 - b2) * b2 ** (t - k) * g * g for k, g in enumerate(grads, 1))
    mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    new = p.astype(jnp.float32) * (1 - lr * wd) - lr * mhat / (
        jnp.sqrt(vhat) + eps)
    return new.astype(p.dtype)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))


def leaf_names(n_layers):
    return [("top", k) for k in ("embed", "norm", "lm_head")] + [
        (i, k) for i in range(n_layers) for k in W.LAYER_LEAVES]


def train_reference(m, seed, dtype, batches, opt, updates=2, quant=None,
                    rows=None):
    """Follow the first steps of training in float32.

    ``batches``: the token batches ``[B, L]`` in the order the program got
    them; step ``k`` trains on ``batches[k]``.  ``opt`` = (lr, beta1, beta2,
    eps, weight_decay).  Makes ``updates`` optimizer steps and one more
    forward, so it returns ``updates + 1`` losses, the per-leaf norm of the
    FIRST gradient and the per-leaf norm of the parameters' change after
    the ``updates`` steps.  One batch row and one layer at a time.
    ``rows`` (a fault for the tests): train on these rows of each batch
    only, the mean taken over them."""
    dims = W.dims_of(m)
    kw = dict(dims=dims, eps=float(m["rms_norm_eps"]),
              theta=float(m["rope_theta"]), quant=quant)
    hk = dict(eps=kw["eps"], quant=quant)
    n_layers = m["num_hidden_layers"]
    params = {"top": dict(W.top_weights(seed, dims, dtype))}
    for i in range(n_layers):
        params[i] = dict(W.layer_weights(seed, i, dims, dtype))
    history = {name: [] for name in leaf_names(n_layers)}
    losses, grad_norms = [], {}
    for step in range(updates + 1):
        ids = np.asarray(batches[step], np.int32)
        if rows is not None:
            ids = ids[list(rows)]
        B, L = ids.shape
        scale = 1.0 / (B * (L - 1))
        last = step == updates
        # forward, a row at a time, keeping every layer's input
        acts = []
        for b in range(B):
            hs = [params["top"]["embed"].astype(jnp.float32)[ids[b]]]
            for i in range(n_layers):
                hs.append(_layer_fwd(params[i], hs[-1], **kw))
            acts.append(hs if not last else hs[-1:])
        if last:
            losses.append(scale * float(sum(
                _head_fwd(params["top"], acts[b][-1], ids[b], **hk)
                for b in range(B))))
            break
        # backward: the head for every row, then layer by layer (every row
        # through a layer before the next), so each leaf is updated — and
        # its gradient dropped — as soon as it is complete
        first = step == 0

        def settle(name, g):
            if first:
                grad_norms[name] = float(_norm(g))
            group, leaf = name
            params[group][leaf] = _adamw(params[group][leaf],
                                         tuple(history[name]) + (g,), opt=opt)
            if step + 1 < updates:
                # a later update needs it: kept on the host, so that the
                # device holds one gradient of one layer at a time
                history[name].append(np.asarray(g))
            else:
                history[name] = None

        total, g_top, ghs = 0.0, None, []
        for b in range(B):
            loss, gt, gh = _head_bwd(params["top"], acts[b][-1], ids[b], **hk)
            total += float(loss)
            ghs.append(gh * scale)
            g_top = gt if g_top is None else _acc(g_top, gt)
        losses.append(scale * total)
        for leaf in ("norm", "lm_head"):
            settle(("top", leaf), g_top[leaf] * scale)
        del g_top
        for i in reversed(range(n_layers)):
            g_layer = None
            for b in range(B):
                gp, ghs[b] = _layer_bwd(params[i], acts[b][i], ghs[b], **kw)
                g_layer = gp if g_layer is None else _acc(g_layer, gp)
                acts[b][i + 1] = None
            for leaf in W.LAYER_LEAVES:
                settle((i, leaf), g_layer[leaf])
            del g_layer, gp
        g_embed = None
        for b in range(B):
            ge = _embed_bwd(params["top"]["embed"], ids[b], ghs[b])
            g_embed = ge if g_embed is None else _acc(g_embed, ge)
        settle(("top", "embed"), g_embed)
        del g_embed, ghs, acts
    history = None
    change = {}
    for group in ["top"] + list(range(n_layers)):
        init = (W.top_weights(seed, dims, dtype) if group == "top"
                else W.layer_weights(seed, group, dims, dtype))
        for leaf, p in params[group].items():
            change[(group, leaf)] = float(_diff_norm(p, init[leaf]))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
