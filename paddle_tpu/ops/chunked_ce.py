"""Remat-chunked token cross-entropy (the big-vocab loss pattern).

One shared implementation of the chunk-by-chunk LM loss used by the llama
(next-token) and BERT (masked-LM) heads: the vocab-head matmul + fp32
log-softmax run on ``chunk_size`` tokens at a time inside a ``lax.scan``
with per-chunk remat, so the [B*L, V] logits tensor (gigabytes at bench
shapes) never materializes; the backward rescans and recomputes each
chunk's matmul.  Reference baseline: the fused softmax-with-CE kernels the
reference reaches through paddle.nn.functional.cross_entropy
(paddle/phi/kernels/gpu/cross_entropy_kernel.cu) — on TPU the chunked scan
is the memory-shape that fits HBM (r3/r5 profiles put 90-160 ms/step in
full-vocab softmax fusions before chunking).

Labels < 0 are ignored (this covers both llama's -1 scan padding and the
reference's ignore_index=-100); the mean is over valid labels only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunked_token_ce_fn"]


def chunked_token_ce_fn(chunk_size: int, vh_weight: bool = False,
                        pad_label: int = -1):
    """Build ``f(h, labels, w) -> scalar`` mean CE over valid tokens.

    h [B, L, H]; labels [B, L] int (negative = ignored); w is the vocab
    projection — [H, V] when ``vh_weight`` is False (llama lm_head), [V, H]
    when True (BERT's tied embedding matrix, consumed without a transpose).
    ``pad_label`` tags the scan-padding tail (any negative value works; it
    is masked exactly like user-provided ignore labels)."""

    def f(h, lab, w):
        # the loss's name in a device trace (observability.trace.SCOPES);
        # the vocabulary matmul inside it is ``lm_head``
        with jax.named_scope("loss"):
            return chunked(h, lab, w)

    def chunked(h, lab, w):
        B, L, H = h.shape
        n = B * L
        if n == 0:  # seq_len == 1 next-token case: no targets exist
            return jnp.zeros((), jnp.float32)
        h2 = h.reshape(n, H)
        lab2 = lab.reshape(n).astype(jnp.int32)
        c = min(chunk_size, n)
        pad = (-n) % c
        if pad:  # pad with an ignored label → masked out of the mean
            h2 = jnp.concatenate([h2, jnp.zeros((pad, H), h2.dtype)], 0)
            lab2 = jnp.concatenate(
                [lab2, jnp.full((pad,), pad_label, jnp.int32)], 0)
        hc = h2.reshape(-1, c, H)
        lc = lab2.reshape(-1, c)

        def chunk_loss(hx, lx):
            with jax.named_scope("lm_head"):
                if vh_weight:
                    logits = jnp.einsum(
                        "ch,vh->cv", hx, w.astype(hx.dtype),
                        preferred_element_type=jnp.float32)
                else:
                    logits = jnp.dot(hx, w,
                                     preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(lx, 0)[:, None], axis=-1)[:, 0]
            valid = (lx >= 0).astype(jnp.float32)
            return ((lse - gold) * valid).sum(), valid.sum()

        chunk_loss = jax.checkpoint(chunk_loss)

        # NOTE: stay on lax.scan.  An unrolled python loop over the chunks
        # was A/B-tested on v5e (r5): it LOSES ~70 ms/step — XLA schedules
        # the scan's chunk matmuls better than the unrolled graph, and the
        # backward's dynamic-update-slice stack (~31 ms) comes back cheaper
        # than the unrolled version's concatenated cotangents.
        def body(acc, xs):
            s, k = chunk_loss(*xs)
            return (acc[0] + s, acc[1] + k), None

        (total, count), _ = jax.lax.scan(body, (0.0, 0.0), (hc, lc))
        return total / jnp.maximum(count, 1.0)

    return f
