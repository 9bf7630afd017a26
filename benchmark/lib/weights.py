"""Weights from ``--seed``, made on the device in the type they are served
in.  One jitted call per decoder layer (one compiled program for every
layer) and one for the embedding, final norm and lm_head, so the program's
own initial weights can be dropped layer by layer as these arrive.

Used by both sides: the driver hands these arrays to the program, and the
plain reference calls the same functions again for itself — it takes no
array from the program.

Distribution: projections N(0, 1/fan_in), embedding N(0, 1), norm gains 1 —
activations stay O(1) through the depth, attention scores are O(1) (so the
softmax is not flat) and logits are O(1).
"""
import functools

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "gate", "up", "down")


MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "vocab_size",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings", "head_dim")


def model_sizes(config):
    """The published sizes of a configuration file that the shared code
    reads (``m`` everywhere else in ``lib/``)."""
    return {k: config[k] for k in MODEL_KEYS if k in config}


def dims_of(m):
    d = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"], d,
            m["vocab_size"])


def layer_shapes(dims):
    h, i, nh, nkv, d, _ = dims
    return {"wq": (h, nh * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
            "wo": (nh * d, h), "gate": (h, i), "up": (h, i), "down": (i, h)}


def _key(seed_lo, seed_hi, tag):
    k = jax.random.PRNGKey(0)
    for x in (seed_lo, seed_hi, tag):
        k = jax.random.fold_in(k, x)
    return k


def split_seed(seed):
    seed = int(seed)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer(seed_lo, seed_hi, idx, dims, dtype):
    out = {"ln1": jnp.ones((dims[0],), dtype), "ln2": jnp.ones((dims[0],), dtype)}
    base = _key(seed_lo, seed_hi, idx + jnp.uint32(1))
    for n, (name, shape) in enumerate(sorted(layer_shapes(dims).items())):
        w = jax.random.normal(jax.random.fold_in(base, n), shape, jnp.float32)
        out[name] = (w * shape[0] ** -0.5).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _top(seed_lo, seed_hi, dims, dtype):
    h, v = dims[0], dims[5]
    base = _key(seed_lo, seed_hi, jnp.uint32(0))
    embed = jax.random.normal(jax.random.fold_in(base, 0), (v, h), jnp.float32)
    head = jax.random.normal(jax.random.fold_in(base, 1), (h, v), jnp.float32)
    return {"embed": embed.astype(dtype), "norm": jnp.ones((h,), dtype),
            "lm_head": (head * h ** -0.5).astype(dtype)}


def layer_weights(seed, idx, dims, dtype):
    """Leaves of decoder layer ``idx`` ([in, out] projections)."""
    lo, hi = split_seed(seed)
    return _layer(lo, hi, jnp.uint32(idx), dims=dims, dtype=jnp.dtype(dtype).name)


def top_weights(seed, dims, dtype):
    """``embed`` [vocab, hidden], ``norm`` [hidden], ``lm_head`` [hidden, vocab]."""
    lo, hi = split_seed(seed)
    return _top(lo, hi, dims=dims, dtype=jnp.dtype(dtype).name)


# the program's parameter names (``LlamaForCausalLM.named_parameters()``)
# -> (group, leaf); group is a layer index or "top"
_SUFFIX = {
    "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
    "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
    "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
    "mlp.down_proj.weight": "down", "input_layernorm.weight": "ln1",
    "post_attention_layernorm.weight": "ln2",
}


def locate(name):
    """``llama.layers.3.mlp.up_proj.weight`` -> (3, "up");
    ``lm_head.weight`` -> ("top", "lm_head")."""
    if name == "lm_head.weight":
        return "top", "lm_head"
    if name == "llama.embed_tokens.weight":
        return "top", "embed"
    if name == "llama.norm.weight":
        return "top", "norm"
    parts = name.split(".")
    if parts[:2] == ["llama", "layers"]:
        return int(parts[2]), _SUFFIX[".".join(parts[3:])]
    raise KeyError(f"no seeded weight for parameter {name!r}")


def place_into(model, seed, m, dtype):
    """Overwrite every parameter of ``model`` with the seeded weights, a
    layer at a time so the initial values are freed as they are replaced."""
    dims = dims_of(m)
    by_group = {}
    for name, p in model.named_parameters():
        group, leaf = locate(name)
        by_group.setdefault(group, []).append((name, leaf, tuple(p.shape)))
    for group, leaves in by_group.items():
        w = (top_weights(seed, dims, dtype) if group == "top"
             else layer_weights(seed, group, dims, dtype))
        for name, leaf, shape in leaves:
            if shape != tuple(w[leaf].shape):
                raise ValueError(f"{name}: program has {shape}, seeded "
                                 f"weights {tuple(w[leaf].shape)}")
        model.load_functional_state(
            params={name: w[leaf] for name, leaf, _ in leaves})
