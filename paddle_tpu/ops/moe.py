"""A routed expert FFN for serving: static shapes under uneven routing.
Two published models run through it (GLM-4.7-Flash's experts of ``[2048,
1536]``, Xing4.0-29B-A4B's of ``[3584, 1024]``): nothing here knows which
but the grouped product's tile, which is a function of the product's shape
(``_tile``).

``models/moe_llm.py``'s training FFN lets every expert compute every token
(experts x tokens of work).  Here the cost follows the (token, expert) PAIRS
that exist: ``route`` picks each token's experts, ``expert_ffn`` orders the
pairs by expert, runs three grouped matrix products over the experts'
contiguous row groups (``_grouped``: group sizes are a traced ``[E]``
operand, never a shape) and combines by the inverse permutation.

- **No capacity factor, no dropped token**: a group may hold no row or every
  row; both are exact.
- **Rows that are not live route nowhere**: a parked decode slot or the
  padded end of a prefill chunk (``live`` false) joins no group — its pairs
  sort behind every expert's, read no expert's weights and come back 0.
- The router runs in float32 (as the published implementations compute it);
  everything else in the model's dtype with float32 accumulation.

Device scopes (``observability.trace.EXPERT_SCOPES``): ``moe.router``,
``moe.dispatch`` (ordering and gathering the pairs), ``moe.experts`` (the
grouped products), ``moe.combine``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

__all__ = ["route", "expert_ffn", "live_routes"]


def route(x, w_r, bias, k, scale):
    """Sigmoid router with a selection bias (``topk_method: noaux_tc``,
    one group): ``s = sigmoid(x W_r)``; the ``k`` experts are the top-k of
    ``s + bias`` — the bias chooses, the unbiased score weighs —;
    ``gates = scale * s_e / (sum of the chosen s + 1e-20)``
    (``norm_topk_prob``).  x [T, h]; w_r [h, E]; bias [E].  Returns
    ``(experts int32 [T, k], gates float32 [T, k])``."""
    with jax.named_scope("moe.router"):
        # float32 products of the operands as stored: bf16 x bf16 is exact
        # in float32, so HIGHEST costs one pass for a bf16 model
        s = jax.nn.sigmoid(jnp.matmul(
            x, w_r, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
        _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        chosen = jnp.take_along_axis(s, experts, axis=-1)
        gates = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), gates


def live_routes(experts, live):
    """What a serving program hands back beside a token: the experts that
    served the row as int8, ``-1`` for a row that is not live."""
    return jnp.where(live[:, None], experts, -1).astype(jnp.int8)


# rows, contraction and output columns of one tile of the grouped product,
# by the product's ``(K, N)``, each found alone on a v5e (ms a product at
# 200 live pairs over 60 experts / 1,024 / 2,048 pairs over 64; beside them
# the touched experts' weight bytes at the HBM peak):
# - [2048, 1536] and [1536, 2048] (PR 33; again PR 35): 0.55 / 0.64 / 0.68
#   and 0.55 / 0.65 / 0.71 against 0.46 / 0.49; ``jax.lax.ragged_dot`` 0.68 /
#   1.29, the default 128-cubed tiles 3.1 / 4.6;
# - [3584, 1024] (PR 35): the WHOLE contraction in one tile, 0.64 / 0.87 /
#   0.91 against 0.54 / 0.57 — at GLM's 2,048 the second tile is a quarter
#   mask, 0.92 / 1.05 / 1.17; (128, 2048, 1024) 0.71 / 0.81 / 0.91 is the
#   better at 1,024 pairs and the worse in decode; (128, 3584, 1024) does
#   not fit the kernel's 16 MB;
# - [1024, 3584] (PR 35): half the output columns a tile, 0.64 / 0.74 / 0.81;
#   512 columns 0.65 / 0.79 / 0.87; all 3,584 do not fit.
# PERF.md section 6 (PR 35) has the whole table.
_TILES = {(3584, 1024): (128, 3584, 512), (1024, 3584): (128, 1024, 1792)}
_TILE = (128, 2048, 512)


def _tile(k, n):
    return _TILES.get((k, n), _TILE)


def _grouped(x, w, sizes, interpret=None):
    """``x [M, K]`` rows in contiguous groups of ``sizes [E]`` against
    ``w [E, K, N]`` -> float32 ``[M, N]``: the Pallas grouped matrix
    product of ``jax.experimental.pallas.ops.tpu.megablox`` (a tile visits
    only the groups its rows belong to, so weights of experts with no row
    are never read).  Rows behind the last group hold whatever the kernel
    left there: the caller masks them.  ``interpret=None`` resolves to
    ``jax.default_backend() != "tpu"``, as the repo's other kernels do."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    tm, tk, tn = _tile(k, w.shape[2])
    # the package runs with x64 on, under which the library's tile count
    # (a ``sum`` of int32) is an int64 scalar operand the TPU compiler
    # refuses: trace the call with 32-bit defaults
    with jax.enable_x64(False):
        return gmm(x, w, sizes, preferred_element_type=jnp.float32,
                   tiling=(tm if m % tm == 0 else m, min(tk, k),
                           min(tn, w.shape[2])), interpret=interpret)


def expert_ffn(x, experts, gates, live, w_gate, w_up, w_down):
    """``_expert_ffn`` (below: the contract), jitted: an expert model's
    layers call it at one set of shapes, so the ordering, the three
    grouped products and the combine are traced and lowered once a
    program and not once a layer — a serving engine traces one prefill
    program a run width in its set-up (PERF.md, PR 34).  The call sits
    under ``moe.dispatch``: what the compiler makes at the call's boundary
    (the pairs' coming and going) keeps only the call's own path, and the
    scopes inside still name everything else."""
    with jax.named_scope("moe.dispatch"):
        return _expert_ffn(x, experts, gates, live, w_gate, w_up, w_down)


@jax.jit
def _expert_ffn(x, experts, gates, live, w_gate, w_up, w_down):
    """``sum_e gates_e * down_e(silu(gate_e x) * up_e x)`` over each live
    row's chosen experts.  x [T, h]; experts, gates [T, k]; live [T] bool;
    w_gate, w_up [E, h, f]; w_down [E, f, h].  Every shape is static in
    ``T``; the routing's imbalance moves only the group sizes."""
    t, k = experts.shape
    e = w_gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        # a pair of a row that is not live takes the id E: behind every group
        flat = jnp.where(live[:, None], experts, e).reshape(t * k)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
        xs = x[order // k]                                   # [T * k, h]
    with jax.named_scope("moe.experts"):
        a = (jax.nn.silu(_grouped(xs, w_gate, sizes))
             * _grouped(xs, w_up, sizes)).astype(x.dtype)
        y = _grouped(a, w_down, sizes)                       # [T * k, h]
    with jax.named_scope("moe.combine"):
        g = jnp.where(live[:, None], gates, 0.0).reshape(t * k)[order]
        # a select, not a multiply: rows behind the last group hold
        # whatever the grouped product left there
        y = jnp.where((flat[order] < e)[:, None], y * g[:, None], 0.0)
        back = jnp.argsort(order).astype(jnp.int32)         # pair -> its row
        return jnp.sum(y[back].reshape(t, k, -1), axis=1).astype(x.dtype)
