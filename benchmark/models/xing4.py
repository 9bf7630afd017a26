"""What the benchmark knows of ONE architecture: Xing4.0-29B-A4B (``xing4``:
GLM-4.7-Flash's latent attention and routed experts under
manifold-constrained hyper-connections — four residual streams mixed by a
per-token Sinkhorn matrix — with YaRN-scaled positions), which the program
runs through ``models/xing4.py`` over ``models/glm4_moe_lite.py`` /
``models/glm4_moe_lite_decode.py``.  A configuration names this file by its
``model`` key; the drivers reach the architecture only through it.

The reference (``lib/xing4_ref.py``) and the seeded weights
(``lib/xing4_weights.py``, which says how they are drawn) import nothing of
the program; only ``build`` does.
"""
from benchmark.lib import xing4_ref
from benchmark.lib import xing4_weights as W
from benchmark.models import glm4_moe_lite as glm

sizes = W.model_sizes

# the program's parameter names -> (group, leaf): GLM's, and the two
# sub-layers' hyper-connection parameters
_HC = {f"{layer}.{leaf}": f"hc{which}_{leaf}"
       for which, layer in ((1, "attn_hc"), (2, "mlp_hc"))
       for leaf in ("phi", "b", "alpha")}

# the switches of the published config that the program implements one
# value of
_FIXED = {"norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "tie_word_embeddings": False, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "ep_size": 1, "moe_layer_freq": 1,
          "attention_bias": False, "hidden_act": "silu"}


def locate(name):
    """``model.layers.3.attn_hc.phi`` -> (3, "hc1_phi"); everything else as
    ``models/glm4_moe_lite.locate``."""
    parts = name.split(".")
    if parts[:2] == ["model", "layers"] and ".".join(parts[3:]) in _HC:
        return int(parts[2]), _HC[".".join(parts[3:])]
    return glm.locate(name)


def build(config, seed, max_positions, **extra):
    """``Xing4ForCausalLM`` at the configuration's sizes with the seed's
    weights, placed a layer (or one top table) at a time so that the
    model's own initial values are freed as they are replaced."""
    from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM

    m, dtype = sizes(config), config["torch_dtype"]
    d = W.dims_of(m)
    for key, want in _FIXED.items():
        if config.get(key, want) != want:
            raise SystemExit(f"the program has no path for {key}="
                             f"{config[key]!r} (it implements {want!r})")
    keys = Xing4Config.__dataclass_fields__
    model = Xing4ForCausalLM(Xing4Config(
        **{k: v for k, v in m.items() if k in keys},
        max_position_embeddings=max_positions, dtype=dtype, **extra))
    by_group = {}
    for name, p in model.named_parameters():
        group, leaf = locate(name)
        if group == "top":
            group = leaf                # one table at a time
        by_group.setdefault(group, []).append((name, leaf, tuple(p.shape)))
    for group, leaves in by_group.items():
        w = ({group: W.top_leaf(seed, W.shared_dims(d), dtype, group)}
             if isinstance(group, str) else W.layer_weights(seed, group, d,
                                                            dtype))
        for name, leaf, shape in leaves:
            if shape != tuple(w[leaf].shape):
                raise ValueError(f"{name}: program has {shape}, seeded "
                                 f"weights {tuple(w[leaf].shape)}")
        model.load_functional_state(
            params={name: w[leaf] for name, leaf, _ in leaves})
    return model


def initial_weights(config, seed, group):
    """The seed's weights of one group of leaves (a layer index or "top"),
    made again from the seed."""
    d, dtype = W.dims_of(sizes(config)), config["torch_dtype"]
    return (W.top_weights(seed, W.shared_dims(d), dtype) if group == "top"
            else W.layer_weights(seed, group, d, dtype))


def serve_logits(config, seed, tokens, rows, quants=(None,), routes=None,
                 stats=None, chosen=None):
    """The reference's (and a control precision's) full-forward logits;
    ``routes`` / ``stats`` / ``chosen`` as ``models/glm4_moe_lite.py``'s
    (``lib/xing4_ref.py``)."""
    return xing4_ref.serve_logits(
        sizes(config), seed, config["torch_dtype"], tokens, rows,
        quants=quants, routes=routes,
        route_margin=config.get("check", {}).get("route_margin", 0.0),
        stats=stats, chosen=chosen)
