"""Xing4.0-29B-A4B (``xing4``) weights from ``--seed``, made on the device in
the type they are served in.  Used by both sides, like
``lib/glm4_moe_lite_weights.py``, whose rules and functions these are for
everything the two architectures share (attention, router, experts, dense
FFN, the top tables: every branch O(1), router logits of unit spread,
``e_score_correction_bias`` = 0.025 x N(0, 1) so that routing is uneven).

The hyper-connection's own rule (``assumed`` in the configuration file), a
sub-layer's ``phi [n C, n^2 + 2 n]``, ``b`` and ``alpha`` in float32:
``phi`` ~ N(0, 1 / (n C)) and ``alpha`` = 1, so that the dynamic part of
each ``H~`` has unit spread over tokens (the flattened norm has unit
entries); ``b_res`` = ``B_RES_DIAGONAL`` x I, ``b_pre`` = ``b_post`` = 0.
The mixing matrix is then visibly neither the identity nor uniform (the
measured mean diagonal of ``H_res`` is in the configuration file), ``H_pre``
scatters around 1/2 and ``H_post`` around 1: leaving the mixing out, or
one of its factors, or 19 of its 20 Sinkhorn steps, moves the logits.
"""
import collections
import functools

import jax
import jax.numpy as jnp

from benchmark.lib import glm4_moe_lite_weights as G
from benchmark.lib.falcon_h1_weights import _normal
from benchmark.lib.weights import _key, split_seed

HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
           "mhc_h_res_clamp_max")
MODEL_KEYS = G.MODEL_KEYS + ("rope_scaling",) + HC_KEYS

# the sizes the weights are drawn from, hashable (a jit static): GLM's and
# the number of residual streams
Dims = collections.namedtuple("Dims", G.Dims._fields + ("streams",))

B_RES_DIAGONAL = 2.0
# fold-in tags of the two sub-layers' phi, behind GLM's 0..16
_HC_TAGS = {1: 17, 2: 18}

is_dense = G.is_dense
top_leaf = G.top_leaf
top_weights = G.top_weights


def model_sizes(config):
    """The published keys of a configuration file that the shared code
    reads (``m``)."""
    return {k: config[k] for k in MODEL_KEYS if k in config}


def dims_of(m):
    return Dims(*G.dims_of(m), m["hc_mult"])


def shared_dims(d):
    """The sizes GLM's functions draw from: ``d`` less the streams."""
    return G.Dims(*d[:-1])


@functools.partial(jax.jit, static_argnames=("hidden", "n"))
def _hyper(seed_lo, seed_hi, idx, hidden, n):
    base = _key(seed_lo, seed_hi, idx + jnp.uint32(1))
    wide, cols = n * hidden, n * n + 2 * n
    b = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                         B_RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32)
                         .reshape(-1)])
    out = {}
    for which, tag in _HC_TAGS.items():
        out[f"hc{which}_phi"] = _normal(jax.random.fold_in(base, tag),
                                        (wide, cols), wide ** -0.5,
                                        "float32")
        out[f"hc{which}_b"] = b
        out[f"hc{which}_alpha"] = jnp.ones((3,), jnp.float32)
    return out


def layer_weights(seed, idx, d, dtype):
    """Leaves of layer ``idx``: GLM's, and the two sub-layers'
    hyper-connection parameters (``hc1_*`` attention, ``hc2_*`` FFN)."""
    p = dict(G.layer_weights(seed, idx, shared_dims(d), dtype))
    lo, hi = split_seed(seed)
    p.update(_hyper(lo, hi, jnp.uint32(idx), hidden=d.hidden, n=d.streams))
    return p
