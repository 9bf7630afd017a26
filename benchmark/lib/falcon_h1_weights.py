"""Falcon-H1 weights from ``--seed``, made on the device in the type they
are served in: one jitted call per block (one compiled program for every
block), one for the embedding and one for the head, so the program's own
initial weights can be dropped group by group as these arrive.

Used by both sides, like ``lib/weights.py``: the driver hands these arrays
to the program, and the plain reference calls the same functions again for
itself.

Distribution (``assumed`` in the configuration file).  The published muP
multipliers (0.0078 .. 5.66) are tuned to the trained weights' scales; with
N(0, 1/fan_in) everywhere the logits would be ~1e-2 and no check could
tell bf16 from fp8.  Every multiplier stays as published in the program and
in the reference, and each projection is drawn with std ``1 / (its
multipliers x sqrt(fan_in))`` so that its branch's contribution is O(1):

- ``embed`` N(0, 1/embedding_multiplier^2); ``lm_head`` std
  1/(lm_head_multiplier sqrt(h));
- ``wq``, ``wv`` std 1/(attention_in_multiplier sqrt(h)); ``wk`` the same
  over ``key_multiplier``; ``wo`` std 1/(attention_out_multiplier
  sqrt(heads x head_dim));
- ``w_in`` by segment ``[z | x | B | C | dt]``: std 1/(ssm_in_multiplier x
  ssm_multipliers[seg] x sqrt(h)); ``w_out`` std 1/(ssm_out_multiplier
  sqrt(d_ssm));
- ``gate`` std 1/(mlp_multipliers[0] sqrt(h)), ``up`` std 1/sqrt(h),
  ``down`` std 1/(mlp_multipliers[1] sqrt(intermediate));
- Mamba-2's own initialisation for the recurrence: ``dt`` log-uniform in
  [0.001, 0.1] and ``dt_bias`` its inverse softplus, ``A`` uniform in
  [1, 16] and ``A_log`` its log, ``D`` = 1; conv weights N(0, 1/4), conv
  bias N(0, 1/16); norm gains 1.
"""
import collections
import functools

import jax
import jax.numpy as jnp

from benchmark.lib.weights import _key, split_seed

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "rms_norm_eps", "rope_theta", "tie_word_embeddings",
    "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
    "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_proj_bias", "mamba_rms_norm",
    "mamba_norm_before_gate", "attention_bias", "mlp_bias",
    "embedding_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "lm_head_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
    "mlp_multipliers")

# the sizes and multipliers the weights are drawn from, hashable (a jit
# static)
Dims = collections.namedtuple("Dims", (
    "hidden", "inter", "heads", "kv_heads", "head_dim", "vocab", "d_ssm",
    "ssm_heads", "groups", "d_state", "d_conv", "embed_mult", "attn_in",
    "attn_out", "key_mult", "head_mult", "ssm_in", "ssm_out", "ssm_mults",
    "mlp_mults"))

# the embedding and the head are drawn in this many blocks of rows (both
# row counts, vocabulary and hidden size, are multiples of it at every size
# the benchmark and its tests use)
TOP_ROW_BLOCKS = 8

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_in", "conv_w",
                "conv_b", "dt_bias", "a_log", "d", "norm_w", "w_out",
                "gate", "up", "down")


def model_sizes(config):
    """The published keys of a configuration file that the shared code
    reads (``m``)."""
    return {k: config[k] for k in MODEL_KEYS if k in config}


def dims_of(m):
    return Dims(
        m["hidden_size"], m["intermediate_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["vocab_size"],
        m["mamba_d_ssm"], m["mamba_n_heads"], m["mamba_n_groups"],
        m["mamba_d_state"], m["mamba_d_conv"],
        float(m["embedding_multiplier"]),
        float(m["attention_in_multiplier"]),
        float(m["attention_out_multiplier"]), float(m["key_multiplier"]),
        float(m["lm_head_multiplier"]), float(m["ssm_in_multiplier"]),
        float(m["ssm_out_multiplier"]),
        tuple(float(x) for x in m["ssm_multipliers"]),
        tuple(float(x) for x in m["mlp_multipliers"]))


def in_proj_segments(d):
    """Widths of ``w_in``'s five output segments ``[z | x | B | C | dt]``."""
    gn = d.groups * d.d_state
    return (d.d_ssm, d.d_ssm, gn, gn, d.ssm_heads)


def conv_channels(d):
    return d.d_ssm + 2 * d.groups * d.d_state


def _normal(key, shape, std, dtype, row_blocks=1):
    """N(0, std^2) of ``shape`` in ``dtype``.  ``row_blocks`` > 1 draws the
    rows block by block (a loop inside the program), so that the float32
    bits of the whole array are never resident: at the published vocabulary
    they would be 5.3 GB beside the model."""
    if row_blocks == 1:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    rows = shape[0] // row_blocks
    if rows * row_blocks != shape[0]:
        raise ValueError(f"{shape[0]} rows do not split into {row_blocks}")
    blocks = jax.lax.map(
        lambda i: _normal(jax.random.fold_in(key, i), (rows,) + shape[1:],
                          std, dtype),
        jnp.arange(row_blocks, dtype=jnp.uint32))
    return blocks.reshape(shape)


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _layer(seed_lo, seed_hi, idx, d, dtype):
    h, qd, kvd = d.hidden, d.heads * d.head_dim, d.kv_heads * d.head_dim
    base = _key(seed_lo, seed_hi, idx + jnp.uint32(1))
    k = lambda n: jax.random.fold_in(base, n)
    rt = h ** -0.5
    segs = in_proj_segments(d)
    w_in = jnp.concatenate([
        _normal(jax.random.fold_in(k(6), j), (h, w),
                rt / (d.ssm_in * d.ssm_mults[j]), dtype)
        for j, w in enumerate(segs)], axis=1)
    dt = jnp.exp(jax.random.uniform(
        k(9), (d.ssm_heads,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a = jax.random.uniform(k(10), (d.ssm_heads,), jnp.float32, 1.0, 16.0)
    return {
        "ln1": jnp.ones((h,), dtype), "ln2": jnp.ones((h,), dtype),
        "wq": _normal(k(0), (h, qd), rt / d.attn_in, dtype),
        "wk": _normal(k(1), (h, kvd), rt / (d.attn_in * d.key_mult), dtype),
        "wv": _normal(k(2), (h, kvd), rt / d.attn_in, dtype),
        "wo": _normal(k(3), (qd, h), qd ** -0.5 / d.attn_out, dtype),
        "w_in": w_in,
        "conv_w": _normal(k(7), (d.d_conv, conv_channels(d)), 0.5, dtype),
        "conv_b": _normal(k(8), (conv_channels(d),), 0.25, dtype),
        # inverse softplus of dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(a).astype(dtype),
        "d": jnp.ones((d.ssm_heads,), dtype),
        "norm_w": jnp.ones((d.d_ssm,), dtype),
        "w_out": _normal(k(11), (d.d_ssm, h),
                         d.d_ssm ** -0.5 / d.ssm_out, dtype),
        "gate": _normal(k(12), (h, d.inter), rt / d.mlp_mults[0], dtype),
        "up": _normal(k(13), (h, d.inter), rt, dtype),
        "down": _normal(k(14), (d.inter, h),
                        d.inter ** -0.5 / d.mlp_mults[1], dtype),
    }


@functools.partial(jax.jit, static_argnames=("d", "dtype", "leaf"))
def _top(seed_lo, seed_hi, d, dtype, leaf):
    base = _key(seed_lo, seed_hi, jnp.uint32(0))
    if leaf == "embed":
        return _normal(jax.random.fold_in(base, 0), (d.vocab, d.hidden),
                       1.0 / d.embed_mult, dtype, TOP_ROW_BLOCKS)
    if leaf == "lm_head":
        return _normal(jax.random.fold_in(base, 1), (d.hidden, d.vocab),
                       d.hidden ** -0.5 / d.head_mult, dtype, TOP_ROW_BLOCKS)
    return jnp.ones((d.hidden,), dtype)


def layer_weights(seed, idx, d, dtype):
    """Leaves of block ``idx`` ([in, out] projections)."""
    lo, hi = split_seed(seed)
    return _layer(lo, hi, jnp.uint32(idx), d=d, dtype=jnp.dtype(dtype).name)


def top_leaf(seed, d, dtype, leaf):
    """``embed`` [vocab, hidden], ``norm`` [hidden] or ``lm_head``
    [hidden, vocab] — one at a time: together they are 5.35 GB at the
    published vocabulary."""
    lo, hi = split_seed(seed)
    return _top(lo, hi, d=d, dtype=jnp.dtype(dtype).name, leaf=leaf)


def top_weights(seed, d, dtype):
    return {leaf: top_leaf(seed, d, dtype, leaf)
            for leaf in ("embed", "norm", "lm_head")}
