"""The plain reference of GLM-4.7-Flash (``model_type: glm4_moe_lite``):
multi-head latent attention in its EXPANDED (published) form over the whole
sequence with no cache, a sigmoid router with a selection bias, the routed
experts in a plain loop over ALL experts (each computes every token and is
weighted by a gate that is 0 where it was not chosen), a shared expert.
Straightforward ``jax.numpy`` in float32 at matmul precision "highest" — no
kernels, no cache, no grouped product, nothing imported from the program.
Weights come from ``lib/glm4_moe_lite_weights.py`` (the seed), one layer at a
time, an expert's float32 copy one at a time, the head over the vocabulary in
blocks.

The equations (config keys in backticks; ``RMS`` = RMSNorm with
``rms_norm_eps``; pre-norm residual blocks):

- MLA: ``c_q = RMS(x W_dq)``; per head ``[q_nope | q_rope] = c_q W_uq``;
  ``[c | k_r] = x W_dkv``; ``c = RMS(c)``; rotate-half RoPE on ``q_rope`` and
  on the ONE ``k_r`` a token; per head ``k_nope = c W_uk``, ``v = c W_uv``,
  ``s = (q_nope . k_nope + q_rope . k_r) / sqrt(qk_nope + qk_rope)``, causal
  softmax, ``o = p v``; ``out = [o_1 .. o_H] W_o``.
- Router (float32 in every precision): ``s = sigmoid(x W_r)``; the
  ``num_experts_per_tok`` experts are the top of ``s + b`` (``b`` =
  ``e_score_correction_bias``); ``g_e = routed_scaling_factor x s_e / (sum of
  the chosen s + 1e-20)``.
- FFN: dense layers (the first ``first_k_dense_replace``)
  ``down(silu(gate x) * up x)`` at ``intermediate_size``; the others
  ``sum_e g_e E_e(x) + E_shared(x)`` at ``moe_intermediate_size``.
- ``logits = lm_head(RMS(h))``.

``routes=`` (recorded routes, "return routed experts"): the served program
routes from bf16 hidden states and this reference from float32 ones, so a
few (position, layer) pairs in a hundred choose another 4th expert — no
rounding error downstream, and not a fault.  Where a recorded set is given
for a (position, layer), the reference USES it if every recorded expert's
biased score lies within ``route_margin`` of the reference's own 4th-ranked
biased score there (the choice is legitimate by the reference's own
scores), weighing it by its own unbiased scores; otherwise it keeps its own
choice and counts a refusal.  ``stats`` reports what happened.

``quant="fp8"`` is the control, not the reference: every projection's
operands (weights per output channel, activations per token) are rounded
through float8_e4m3 — the precision below the configuration's bfloat16.  The
router stays float32 (over the control's own hidden states) and the control
follows no recorded routes: it CHOOSES, as a program computed in that
precision would.  ``chosen=`` hands every pass's sets back, so that the
control can stand in the program's place: its sets are what such a program
would have recorded, and the float32 reference follows THEM within the same
margin (``drivers/serve_routed.py::control_in_place``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import glm4_moe_lite_weights as W
from benchmark.lib.llama_ref import HI, mm, rmsnorm, rope

VOCAB_BLOCKS = 8


def f32(tree, skip=()):
    return {k: v if k in skip else v.astype(jnp.float32)
            for k, v in tree.items()}


def mla(p, u, d, eps, theta, quant=None):
    """Expanded latent attention over one sequence: u [L, hidden]
    (normed)."""
    L = u.shape[0]
    c_q = rmsnorm(mm(u, p["w_dq"], quant), p["q_norm"], eps)
    q = mm(c_q, p["w_uq"], quant).reshape(L, d.heads, d.nope + d.rope)
    ckv = mm(u, p["w_dkv"], quant)
    c = rmsnorm(ckv[:, :d.kv_rank], p["kv_norm"], eps)
    k_r = rope(ckv[:, None, d.kv_rank:], theta)[:, 0]           # [L, rope]
    q_r = rope(q[..., d.nope:], theta)
    mask = jnp.tril(jnp.ones((L, L), bool))
    scale = float(d.nope + d.rope) ** -0.5

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


def choose(p, x, d, routes, margin):
    """The router over one sequence x [L, hidden]: (chosen [L, k], gates
    [L, k], stats).  ``routes`` [L, k] int32, ``-1`` where nothing was
    recorded."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HI))
    biased = s + p["router_bias"]
    top, own = jax.lax.top_k(biased, d.top_k)
    has = routes[:, 0] >= 0
    rec = jnp.clip(routes, 0)
    # how far each recorded expert's biased score lies under the
    # reference's own 4th: <= 0 for an expert of the reference's own set
    short = top[:, -1:] - jnp.take_along_axis(biased, rec, -1)
    worst = jnp.max(short, -1)
    differs = has & (worst > 0)
    legit = has & (worst <= margin)
    chosen = jnp.where(legit[:, None], rec, own)
    sc = jnp.take_along_axis(s, chosen, -1)
    gates = d.route_scale * sc / (jnp.sum(sc, -1, keepdims=True) + 1e-20)
    stats = {"recorded": jnp.sum(has), "differ": jnp.sum(differs),
             "refused": jnp.sum(has & ~legit),
             "followed": jnp.sum(differs & legit),
             "short": jnp.where(differs, worst, -1.0), "chosen": chosen}
    return chosen, gates, stats


def swiglu(x, gate, up, down, quant=None):
    return mm(jax.nn.silu(mm(x, gate, quant)) * mm(x, up, quant), down,
              quant)


def moe(p, x, d, routes, margin, quant=None):
    """Routed experts + the shared one over one sequence x [L, hidden]:
    every expert computes every token, weighted by its gate (0 where it was
    not chosen) — experts x tokens of work, the plain form."""
    chosen, gates, stats = choose(p, x, d, routes, margin)
    L = x.shape[0]
    weight = jnp.zeros((L, d.experts), jnp.float32).at[
        jnp.arange(L)[:, None], chosen].add(gates)

    def expert(y, args):
        w_gate, w_up, w_down, g = args
        out = swiglu(x, w_gate.astype(jnp.float32),
                     w_up.astype(jnp.float32), w_down.astype(jnp.float32),
                     quant)
        return y + out * g[:, None], None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["e_gate"], p["e_up"], p["e_down"], weight.T))
    return y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"], quant), stats


def layer(p, h, routes, d, eps, theta, margin, quant=None):
    """One layer over one sequence h [L, hidden] (float32)."""
    h = h + mla(p, rmsnorm(h, p["ln1"], eps), d, eps, theta, quant)
    x = rmsnorm(h, p["ln2"], eps)
    if "router" not in p:
        return h + swiglu(x, p["gate"], p["up"], p["down"], quant), {}
    y, stats = moe(p, x, d, routes, margin, quant)
    return h + y, stats


@functools.partial(jax.jit, static_argnames=("d", "eps", "theta", "margin",
                                             "quant"))
def _layer_rows(p, hs, routes, d, eps, theta, margin, quant):
    # an expert's float32 copy is made inside the loop over experts
    pf = f32(p, skip=("e_gate", "e_up", "e_down"))
    return jax.lax.map(
        lambda a: layer(pf, a[0], a[1], d, eps, theta, margin, quant),
        (hs, routes))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_rows(norm, hs, rows, eps):
    h = jnp.take_along_axis(hs, rows[:, :, None], axis=1)
    return rmsnorm(h, norm.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("n", "quant"))
def _head_block(lm_head, h, i, n, quant):
    """Logits of vocabulary block ``i`` of ``n`` (``i`` traced: one
    compiled program for every block)."""
    width = lm_head.shape[1] // n
    w = jax.lax.dynamic_slice_in_dim(lm_head, i * width, width, axis=1)
    return mm(h, w.astype(jnp.float32), quant)


def serve_logits(m, seed, dtype, tokens, rows, quants=(None,), routes=None,
                 route_margin=0.0, stats=None, chosen=None):
    """Full-forward logits of padded sequences.

    ``tokens`` [R, L] int32 (right-padded: a pad is causally invisible to
    every real position), ``rows`` [R, K] the positions whose next-token
    logits are wanted.  ``routes`` [R, L, L_moe, k] (``-1`` = nothing
    recorded): followed by the reference (quant None) where legitimate
    within ``route_margin`` (module docstring); ``stats`` (a dict) is then
    filled with ``recorded`` / ``differ`` / ``refused`` / ``followed``
    counts over all (position, expert layer) pairs and ``short``, the
    margins of the pairs that differ.  ``chosen`` (a list) is filled with
    the sets each pass USED, one ``[R, L, L_moe, k]`` int32 array per entry
    of ``quants``.  Returns one ``[R, K, vocab]`` float32 numpy array per
    entry of ``quants``."""
    d = W.dims_of(m)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    tokens, rows = jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32)
    R, L = tokens.shape
    none = jnp.full((R, L, d.top_k), -1, jnp.int32)
    embed = W.top_leaf(seed, d, dtype, "embed")
    h0 = embed[tokens].astype(jnp.float32)
    del embed
    hs = [h0 for _ in quants]
    totals, used, li = {}, [[] for _ in quants], 0
    for i in range(m["num_hidden_layers"]):
        p = W.layer_weights(seed, i, d, dtype)
        dense = W.is_dense(d, i)
        for j, q in enumerate(quants):
            rec = none if routes is None or q is not None or dense \
                else jnp.asarray(routes[:, :, li], jnp.int32)
            hs[j], st = _layer_rows(p, hs[j], rec, d=d, eps=eps, theta=theta,
                                    margin=float(route_margin), quant=q)
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
    norm = W.top_leaf(seed, d, dtype, "norm")
    hs = [_final_rows(norm, h, rows, eps=eps) for h in hs]
    lm_head = W.top_leaf(seed, d, dtype, "lm_head")
    n = VOCAB_BLOCKS if d.vocab % VOCAB_BLOCKS == 0 else 1
    return [np.concatenate(
        [np.asarray(_head_block(lm_head, h, jnp.int32(i), n=n, quant=q))
         for i in range(n)], axis=-1)
        for h, q in zip(hs, quants)]
