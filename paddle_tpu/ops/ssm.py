"""State-space (Mamba-2 / SSD) primitives with carried state.

Three pieces, plain ``jax.numpy`` that XLA fuses (no Pallas): a causal
depthwise convolution that carries its last ``K - 1`` inputs (the *tail*),
the chunked SSD scan for many tokens of one sequence (prefill, and the
whole-sequence model forward), and the one-token state update for a batch of
slots (decode).

Per head ``h`` of group ``g`` the recurrence is

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (outer) B_{t,g}       S in R^{P x N}
    y_t = S_t C_{t,g} + D_h x_t

The chunked form (Dao & Gu, "Transformers are SSMs", 2024) splits the
sequence into chunks of ``chunk`` tokens: inside a chunk the outputs are one
masked matmul against the decays' lower triangle, and the state is
materialised only at chunk boundaries — one ``[P, N]`` update a chunk and
head, not one a token.  The state is float32 throughout: it accumulates over
every token of a sequence.

Heads keep their group structure (``[G, E]`` with ``E = H / G`` heads a
group) so ``B`` and ``C`` are never repeated per head.

The device scopes (``ssm.conv``, ``ssm.scan``, ``ssm.state_update``) are
opened here, like ``attn.core`` inside ``ops/decode_attention.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d", "conv_tail_after", "ssd_chunked",
           "ssm_state_update"]


def causal_conv1d(x, tail, w, b):
    """Depthwise causal convolution over time, then SiLU.

    ``x [B, T, C]`` the new inputs, ``tail [B, K-1, C]`` the ``K - 1``
    inputs before them (zeros at the start of a sequence), ``w [K, C]``
    (``w[K-1]`` multiplies the current input), ``b [C]``.  Returns
    ``(silu(conv) [B, T, C] in x's dtype, xx [B, K-1+T, C])`` — ``xx`` is
    the tail followed by the inputs, what ``conv_tail_after`` cuts the next
    tail from.  Accumulates in float32."""
    with jax.named_scope("ssm.conv"):
        k, t = w.shape[0], x.shape[1]
        xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        wf = w.astype(jnp.float32)
        acc = b.astype(jnp.float32)
        for i in range(k):
            acc = acc + xx[:, i:i + t].astype(jnp.float32) * wf[i]
        return jax.nn.silu(acc).astype(x.dtype), xx


def conv_tail_after(xx, n_valid, k):
    """The tail after ``n_valid`` of the new inputs: rows ``n_valid ..
    n_valid + K - 2`` of ``xx [B, K-1+T, C]`` — the last ``K - 1`` inputs
    that were real (``n_valid`` a traced scalar: the padded end of a
    prompt's last chunk advances nothing)."""
    with jax.named_scope("ssm.conv"):
        return jax.lax.dynamic_slice_in_dim(xx, n_valid, k - 1, axis=1)


def ssd_chunked(x, dt, a, bm, cm, d, state, chunk):
    """Chunked SSD scan of ONE sequence.

    ``x [T, G, E, P]``, ``dt [T, G, E]`` (after softplus; 0 at a position
    makes it a no-op for the state), ``a [G, E]`` (negative), ``bm``/``cm``
    ``[T, G, N]``, ``d [G, E]``, ``state [G, E, P, N]`` float32 (the state
    before the first token).  ``T`` must be a multiple of ``chunk``.
    Returns ``(y [T, G, E, P] float32, state' float32)``."""
    with jax.named_scope("ssm.scan"):
        t, g, e, p = x.shape
        n = bm.shape[-1]
        c = t // chunk
        if c * chunk != t:
            raise ValueError(f"sequence {t} is not a multiple of the SSD "
                             f"chunk {chunk}")
        f32 = jnp.float32
        x = x.astype(f32).reshape(c, chunk, g, e, p)
        dt = dt.astype(f32).reshape(c, chunk, g, e)
        bm = bm.astype(f32).reshape(c, chunk, g, n)
        cm = cm.astype(f32).reshape(c, chunk, g, n)
        # log-decay from the chunk's start up to and including token i
        cum = jnp.cumsum(dt * a.astype(f32), axis=1)            # [c,Q,g,e]
        cum_t = jnp.moveaxis(cum, 1, -1)                        # [c,g,e,Q]
        # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i-cum_j) dt_j x_j
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            tri, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
        cb = jnp.einsum("cign,cjgn->cgij", cm, bm)
        m = cb[:, :, None] * decay * jnp.moveaxis(dt, 1, -1)[..., None, :]
        y = jnp.einsum("cgeij,cjgep->cigep", m, x)
        # what each chunk adds to the state at its end
        to_end = jnp.exp(cum[:, -1:] - cum) * dt                # [c,Q,g,e]
        s_add = jnp.einsum("cjge,cjgep,cjgn->cgepn", to_end, x, bm)
        through = jnp.exp(cum[:, -1])                           # [c,g,e]

        # the state at chunk boundaries: the only sequential part
        def boundary(s, xs):
            add, thr = xs
            return s * thr[..., None, None] + add, s

        state, s_in = jax.lax.scan(boundary, state.astype(f32),
                                   (s_add, through))
        y = y + jnp.einsum("cign,cgepn->cigep", cm, s_in) \
            * jnp.exp(cum)[..., None]
        y = y + x * d.astype(f32)[..., None]
        return y.reshape(t, g, e, p), state


def ssm_state_update(x, dt, a, bm, cm, d, state, live):
    """One token for every slot of a batch.

    ``x [B, G, E, P]``, ``dt [B, G, E]``, ``a``/``d [G, E]``, ``bm``/``cm``
    ``[B, G, N]``, ``state [B, G, E, P, N]`` float32, ``live [B]`` bool:
    a slot that is not live keeps its state bit for bit (its ``y`` is
    garbage the scheduler ignores).  Returns ``(y [B, G, E, P] float32,
    state')``.  Elementwise over the state: read once, written once."""
    with jax.named_scope("ssm.state_update"):
        f32 = jnp.float32
        x, dt = x.astype(f32), dt.astype(f32)
        keep = jnp.exp(dt * a.astype(f32))[..., None, None]     # [B,g,e,1,1]
        add = (dt[..., None] * x)[..., None] \
            * bm.astype(f32)[:, :, None, None, :]               # [B,g,e,P,N]
        new = state * keep + add
        y = jnp.sum(new * cm.astype(f32)[:, :, None, None, :], axis=-1)
        y = y + x * d.astype(f32)[..., None]
        return y, jnp.where(live[:, None, None, None, None], new, state)
