"""What the benchmark knows of ONE architecture: GLM-4.7-Flash
(``glm4_moe_lite``: latent attention in every layer, routed experts plus a
shared one behind a sigmoid router), which the program runs through
``models/glm4_moe_lite.py`` / ``models/glm4_moe_lite_decode.py``.  A
configuration names this file by its ``model`` key; the drivers reach the
architecture only through it.

The reference (``lib/glm4_moe_lite_ref.py``) and the seeded weights
(``lib/glm4_moe_lite_weights.py``, which says how they are drawn) import
nothing of the program; only ``build`` does.
"""
from benchmark.lib import glm4_moe_lite_ref
from benchmark.lib import glm4_moe_lite_weights as W

sizes = W.model_sizes

# the program's parameter names (``Glm4MoeLiteForCausalLM
# .named_parameters()``) -> (group, leaf); group is a layer index or "top"
_SUFFIX = {
    "input_layernorm.weight": "ln1", "post_attention_layernorm.weight": "ln2",
    "self_attn.q_a_proj.weight": "w_dq",
    "self_attn.q_a_layernorm.weight": "q_norm",
    "self_attn.q_b_proj.weight": "w_uq",
    "self_attn.kv_a_proj_with_mqa.weight": "w_dkv",
    "self_attn.kv_a_layernorm.weight": "kv_norm",
    "self_attn.k_b_proj.weight": "w_uk", "self_attn.v_b_proj.weight": "w_uv",
    "self_attn.o_proj.weight": "wo",
    "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
    "mlp.down_proj.weight": "down",
    "mlp.gate.weight": "router",
    "mlp.gate.e_score_correction_bias": "router_bias",
    "mlp.experts_gate.weight": "e_gate", "mlp.experts_up.weight": "e_up",
    "mlp.experts_down.weight": "e_down",
    "mlp.shared_experts.gate_proj.weight": "s_gate",
    "mlp.shared_experts.up_proj.weight": "s_up",
    "mlp.shared_experts.down_proj.weight": "s_down",
}
_TOP = {"lm_head.weight": "lm_head", "model.embed_tokens.weight": "embed",
        "model.norm.weight": "norm"}

# the switches of the published config that the program implements one
# value of
_FIXED = {"norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "tie_word_embeddings": False, "attention_bias": False,
          "hidden_act": "silu", "topk_method": "noaux_tc",
          "rope_scaling": None, "partial_rotary_factor": 1}


def locate(name):
    """``model.layers.3.mlp.gate.weight`` -> (3, "router");
    ``lm_head.weight`` -> ("top", "lm_head")."""
    if name in _TOP:
        return "top", _TOP[name]
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        return int(parts[2]), _SUFFIX[".".join(parts[3:])]
    raise KeyError(f"no seeded weight for parameter {name!r}")


def build(config, seed, max_positions, **extra):
    """``Glm4MoeLiteForCausalLM`` at the configuration's sizes with the
    seed's weights, placed a layer (or one top table) at a time so that the
    model's own initial values are freed as they are replaced."""
    from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                 Glm4MoeLiteForCausalLM)

    m, dtype = sizes(config), config["torch_dtype"]
    d = W.dims_of(m)
    for key, want in _FIXED.items():
        if config.get(key, want) != want:
            raise SystemExit(f"the program has no path for {key}="
                             f"{config[key]!r} (it implements {want!r})")
    keys = Glm4MoeLiteConfig.__dataclass_fields__
    model = Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig(
        **{k: v for k, v in m.items() if k in keys},
        max_position_embeddings=max_positions, dtype=dtype, **extra))
    by_group = {}
    for name, p in model.named_parameters():
        group, leaf = locate(name)
        if group == "top":
            group = leaf                # one table at a time
        by_group.setdefault(group, []).append((name, leaf, tuple(p.shape)))
    for group, leaves in by_group.items():
        w = ({group: W.top_leaf(seed, d, dtype, group)}
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
    return (W.top_weights(seed, d, dtype) if group == "top"
            else W.layer_weights(seed, group, d, dtype))


def serve_logits(config, seed, tokens, rows, quants=(None,), routes=None,
                 stats=None, chosen=None):
    """The reference's (and a control precision's) full-forward logits;
    ``routes`` / ``stats``: recorded routes followed within the
    configuration's ``check.route_margin``; ``chosen``: the sets each pass
    used (``lib/glm4_moe_lite_ref.py``)."""
    return glm4_moe_lite_ref.serve_logits(
        sizes(config), seed, config["torch_dtype"], tokens, rows,
        quants=quants, routes=routes,
        route_margin=config.get("check", {}).get("route_margin", 0.0),
        stats=stats, chosen=chosen)
