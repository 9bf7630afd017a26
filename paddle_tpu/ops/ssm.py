"""State-space (Mamba-2 / SSD) primitives with carried state.

Three pieces: a causal depthwise convolution that carries its last
``K - 1`` inputs (the *tail*) and the chunked SSD scan for many tokens of
one sequence (prefill, and the whole-sequence model forward), plain
``jax.numpy`` that XLA fuses; and the one-token state update for the live
slots of a batch (decode), one Pallas kernel that reads and writes only
the live slots' state.

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

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["causal_conv1d", "conv_tail_after", "ssd_chunked",
           "ssm_state_update"]

# heads of the decode update's state tile: [16, 128, 256] float32 is 2 MB
_HEAD_BLOCK = 16


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


def _update_kernel(order_ref, n_ref, x_ref, dt_ref, a_ref, d_ref, b_ref,
                   c_ref, s_ref, y_ref, o_ref, *, hb, e):
    """One (head block, place) of the decode update: the live slot at this
    place, ``hb`` heads.  Blocks: ``x``/``y`` ``[1, hb, P]``, ``dt``
    ``[1, hb, 1]``, ``a``/``d`` ``[hb, 1]``, ``B``/``C`` ``[1, G, N]``
    (every group: a ``[1, 1, N]`` block is off the (8, 128) tiling; the
    block's groups are picked here), the state in and out ``[1, hb, P, N]``.
    Places past ``n_live`` revisit the last live slot's blocks (no DMA in,
    no write-back) and compute nothing; with no live slot at all, place 0
    copies its block through, so nothing uninitialised is written back."""
    j = pl.program_id(0)                # read outside pl.when: the CPU
    i = pl.program_id(1)                # interpreter has no rule inside
    n = n_ref[0]

    @pl.when(i < n)
    def _update():
        x = x_ref[0]
        dt = dt_ref[0]
        keep = jnp.exp(dt * a_ref[...])                     # [hb, 1]
        dtx = dt * x                                        # [hb, P]
        per = min(hb, e)                # heads of one group in the block
        g0 = jax.lax.div(j * jnp.int32(hb), jnp.int32(e))
        for k in range(hb // per):
            g = g0 + jnp.int32(k)
            heads = slice(k * per, (k + 1) * per)
            new = s_ref[0, heads] * keep[heads][:, :, None] \
                + dtx[heads][:, :, None] * b_ref[0, pl.ds(g, 1), :][None]
            o_ref[0, heads] = new
            y_ref[0, heads] = jnp.sum(
                new * c_ref[0, pl.ds(g, 1), :][None], axis=-1) \
                + d_ref[heads] * x[heads]

    @pl.when((i == jnp.int32(0)) & (n == jnp.int32(0)))
    def _copy_through():
        o_ref[...] = s_ref[...]


def ssm_state_update(x, dt, a, bm, cm, d, state, live, interpret=None):
    """One token for every live slot of a batch.

    ``x [B, G, E, P]``, ``dt [B, G, E]``, ``a``/``d [G, E]``, ``bm``/``cm``
    ``[B, G, N]``, ``state [B, G, E, P, N]`` float32, ``live [B]`` bool:
    a slot that is not live keeps its state bit for bit and its ``y`` is
    zero.  Returns ``(y [B, G, E, P] float32, state')``.

    ONE Pallas kernel over grid ``(head blocks, B places)``: a stable sort
    puts the live slots first, and place ``i`` reads and writes the state
    of slot ``order[min(i, n_live - 1)]`` in place (aliased), so only the
    live slots' state moves — a parked slot's is never touched.  The head
    blocks are the outer axis: a block index that changed inside the dead
    tail would write back a buffer nothing computed.  ``interpret=None``
    is ``jax.default_backend() != "tpu"``; the kernel's tests hand
    ``pltpu.InterpretParams()``, the TPU interpreter, which models the
    pipeline's buffers (the plain one re-reads a block's last contents
    where the chip writes back whatever its buffer holds)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.named_scope("ssm.state_update"):
        ft = state.dtype                # float32 (a test may hand float64)
        b, g, e, p, n = state.shape
        h = g * e
        hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 \
            and (e % _HEAD_BLOCK == 0 or _HEAD_BLOCK % e == 0) else h
        order = jnp.argsort(~live, stable=True).astype(jnp.int32)
        n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)

        def slot(i, order_ref, n_ref):
            last = jax.lax.max(n_ref[0] - jnp.int32(1), jnp.int32(0))
            return order_ref[jax.lax.min(i, last)]

        tile = lambda j, i, o, m: (slot(i, o, m), j, i * 0, i * 0)
        rows = lambda j, i, o, m: (slot(i, o, m), j, i * 0)
        groups = lambda j, i, o, m: (slot(i, o, m), i * 0, i * 0)
        heads = lambda j, i, o, m: (j, i * 0)
        block = hb * p * n * jnp.dtype(ft).itemsize
        y, new = pl.pallas_call(
            functools.partial(_update_kernel, hb=hb, e=e),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(h // hb, b),
                in_specs=[pl.BlockSpec((1, hb, p), rows),
                          pl.BlockSpec((1, hb, 1), rows),
                          pl.BlockSpec((hb, 1), heads),
                          pl.BlockSpec((hb, 1), heads),
                          pl.BlockSpec((1, g, n), groups),
                          pl.BlockSpec((1, g, n), groups),
                          pl.BlockSpec((1, hb, p, n), tile)],
                out_specs=[pl.BlockSpec((1, hb, p), rows),
                           pl.BlockSpec((1, hb, p, n), tile)]),
            out_shape=[jax.ShapeDtypeStruct((b, h, p), ft),
                       jax.ShapeDtypeStruct((b, h, p, n), ft)],
            # operand 8 (the 2 scalar-prefetch operands counted) is the state
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                # the state's tiles in and out, double-buffered, the
                # kernel's two tile-sized temporaries, and room
                vmem_limit_bytes=max(8 * block, 16 << 20)),
            interpret=interpret,
            name="ssm_state_update",
        )(order, n_live, x.astype(ft).reshape(b, h, p),
          dt.astype(ft).reshape(b, h, 1), a.astype(ft).reshape(h, 1),
          d.astype(ft).reshape(h, 1), bm.astype(ft), cm.astype(ft),
          state.reshape(b, h, p, n))
        y = jnp.where(live[:, None, None], y, 0.0)
        return y.reshape(b, g, e, p), new.reshape(state.shape)
