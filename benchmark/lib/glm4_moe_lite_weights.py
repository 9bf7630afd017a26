"""GLM-4.7-Flash (``glm4_moe_lite``) weights from ``--seed``, made on the
device in the type they are served in: one jitted call per layer (one
compiled program for the dense layers, one for the expert layers), one for
the embedding and one for the head, so the program's own initial weights can
be dropped group by group as these arrive.

Used by both sides, like ``lib/weights.py``: the driver hands these arrays
to the program, and the plain reference calls the same functions again for
itself.

Distribution (``assumed`` in the configuration file).  Every branch
contributes O(1) so that the logits have unit spread and a lower precision
shows: ``embed`` N(0, 1); every projection N(0, 1 / fan_in) (normed inputs
have unit entries, so queries, keys, values, latents and each expert's
hidden units come out with unit spread; attention scores are sums over
``qk_nope + qk_rope`` = 256 unit products divided by 16); norm gains 1.  The
router ``W_r`` N(0, 1 / hidden): router logits of unit spread.  The
selection bias ``e_score_correction_bias`` is ``ROUTER_BIAS_STD`` x N(0, 1),
a layer's own draw: a trained router is uneven, and an even one would never
show a dropped token — with this spread the most loaded expert of a layer
takes about twice the mean (the measured ratio is in the configuration
file).  The published ``kv_b_proj`` is drawn as its two halves ``w_uk
[heads, nope, rank]`` and ``w_uv [heads, rank, v]``.
"""
import collections
import functools

import jax
import jax.numpy as jnp

from benchmark.lib.falcon_h1_weights import _normal
from benchmark.lib.weights import _key, split_seed

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
    "n_group", "topk_group", "vocab_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings")

# the sizes the weights are drawn from, hashable (a jit static)
Dims = collections.namedtuple("Dims", (
    "hidden", "inter", "moe_inter", "heads", "q_rank", "kv_rank", "nope",
    "rope", "v_dim", "vocab", "experts", "shared", "top_k", "route_scale",
    "first_dense"))

# spread of the selection bias, in units of the sigmoid score it is added to
ROUTER_BIAS_STD = 0.025
# the large tables (embedding, head, the stacked experts) are drawn in this
# many blocks of rows, so that their float32 bits are never resident at once
ROW_BLOCKS = 8


def model_sizes(config):
    """The published keys of a configuration file that the shared code
    reads (``m``)."""
    return {k: config[k] for k in MODEL_KEYS if k in config}


def dims_of(m):
    return Dims(
        m["hidden_size"], m["intermediate_size"], m["moe_intermediate_size"],
        m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"],
        m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
        m["vocab_size"], m["n_routed_experts"], m["n_shared_experts"],
        m["num_experts_per_tok"], float(m["routed_scaling_factor"]),
        m["first_k_dense_replace"])


def is_dense(d, idx):
    return idx < d.first_dense


@functools.partial(jax.jit, static_argnames=("d", "dtype", "dense"))
def _layer(seed_lo, seed_hi, idx, d, dtype, dense):
    h = d.hidden
    base = _key(seed_lo, seed_hi, idx + jnp.uint32(1))
    k = lambda n: jax.random.fold_in(base, n)
    proj = lambda n, shape, fan_in, blocks=1: _normal(
        k(n), shape, fan_in ** -0.5, dtype, blocks)
    p = {
        "ln1": jnp.ones((h,), dtype), "ln2": jnp.ones((h,), dtype),
        "w_dq": proj(0, (h, d.q_rank), h),
        "q_norm": jnp.ones((d.q_rank,), dtype),
        "w_uq": proj(1, (d.q_rank, d.heads * (d.nope + d.rope)), d.q_rank),
        "w_dkv": proj(2, (h, d.kv_rank + d.rope), h),
        "kv_norm": jnp.ones((d.kv_rank,), dtype),
        "w_uk": proj(3, (d.heads, d.nope, d.kv_rank), d.kv_rank),
        "w_uv": proj(4, (d.heads, d.kv_rank, d.v_dim), d.kv_rank),
        "wo": proj(5, (d.heads * d.v_dim, h), d.heads * d.v_dim),
    }
    if dense:
        p.update(gate=proj(6, (h, d.inter), h), up=proj(7, (h, d.inter), h),
                 down=proj(8, (d.inter, h), d.inter))
        return p
    e, f, sf = d.experts, d.moe_inter, d.moe_inter * d.shared
    blocks = ROW_BLOCKS if e % ROW_BLOCKS == 0 else 1
    p.update(
        router=proj(9, (h, e), h),
        router_bias=ROUTER_BIAS_STD * jax.random.normal(
            k(10), (e,), jnp.float32),
        e_gate=proj(11, (e, h, f), h, blocks),
        e_up=proj(12, (e, h, f), h, blocks),
        e_down=proj(13, (e, f, h), f, blocks),
        s_gate=proj(14, (h, sf), h), s_up=proj(15, (h, sf), h),
        s_down=proj(16, (sf, h), sf))
    return p


@functools.partial(jax.jit, static_argnames=("d", "dtype", "leaf"))
def _top(seed_lo, seed_hi, d, dtype, leaf):
    base = _key(seed_lo, seed_hi, jnp.uint32(0))
    blocks = ROW_BLOCKS if d.vocab % ROW_BLOCKS == 0 \
        and d.hidden % ROW_BLOCKS == 0 else 1
    if leaf == "embed":
        return _normal(jax.random.fold_in(base, 0), (d.vocab, d.hidden),
                       1.0, dtype, blocks)
    if leaf == "lm_head":
        return _normal(jax.random.fold_in(base, 1), (d.hidden, d.vocab),
                       d.hidden ** -0.5, dtype, blocks)
    return jnp.ones((d.hidden,), dtype)


def layer_weights(seed, idx, d, dtype):
    """Leaves of layer ``idx`` ([in, out] projections; the experts stacked
    ``[E, ..]``)."""
    lo, hi = split_seed(seed)
    return _layer(lo, hi, jnp.uint32(idx), d=d, dtype=jnp.dtype(dtype).name,
                  dense=is_dense(d, idx))


def top_leaf(seed, d, dtype, leaf):
    """``embed`` [vocab, hidden], ``norm`` [hidden] or ``lm_head``
    [hidden, vocab] — one at a time."""
    lo, hi = split_seed(seed)
    return _top(lo, hi, d=d, dtype=jnp.dtype(dtype).name, leaf=leaf)


def top_weights(seed, d, dtype):
    return {leaf: top_leaf(seed, d, dtype, leaf)
            for leaf in ("embed", "norm", "lm_head")}
