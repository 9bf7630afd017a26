"""What the benchmark knows of ONE architecture: Falcon-H1 (parallel Mamba-2
and attention in every block), which the program runs through
``models/falcon_h1.py`` / ``models/falcon_h1_decode.py``.  A configuration
names this file by its ``model`` key; the drivers reach the architecture
only through it.

The reference (``lib/falcon_h1_ref.py``) and the seeded weights
(``lib/falcon_h1_weights.py``) import nothing of the program; only ``build``
does.

Weights from the seed (the configuration file's ``assumed``): the published
muP multipliers stay in the program and in the reference, and each
projection is drawn with std ``1 / (its multipliers x sqrt(fan_in))`` so that
its branch's contribution is O(1) and the logits have unit spread — with
N(0, 1/fan_in) everywhere they would be ~1e-2 and no check could tell bf16
from fp8; ``A_log``, ``dt_bias`` and ``D`` by Mamba-2's own initialisation,
conv weights N(0, 1/4) (``lib/falcon_h1_weights.py`` has each leaf).
"""
from benchmark.lib import falcon_h1_ref
from benchmark.lib import falcon_h1_weights as W

sizes = W.model_sizes

# the program's parameter names (``FalconH1ForCausalLM.named_parameters()``)
# -> (group, leaf); group is a block index or "top"
_SUFFIX = {
    "input_layernorm.weight": "ln1", "pre_ff_layernorm.weight": "ln2",
    "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
    "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
    "mamba.in_proj.weight": "w_in", "mamba.conv1d.weight": "conv_w",
    "mamba.conv1d.bias": "conv_b", "mamba.dt_bias": "dt_bias",
    "mamba.A_log": "a_log", "mamba.D": "d", "mamba.norm.weight": "norm_w",
    "mamba.out_proj.weight": "w_out",
    "feed_forward.gate_proj.weight": "gate",
    "feed_forward.up_proj.weight": "up",
    "feed_forward.down_proj.weight": "down",
}
_TOP = {"lm_head.weight": "lm_head", "model.embed_tokens.weight": "embed",
        "model.final_layernorm.weight": "norm"}


# the switches of the published config that the program implements one
# value of
_FIXED = {"mamba_conv_bias": True, "mamba_proj_bias": False,
          "mamba_rms_norm": True, "mamba_norm_before_gate": False,
          "attention_bias": False, "mlp_bias": False,
          "tie_word_embeddings": False}


def locate(name):
    """``model.layers.3.mamba.in_proj.weight`` -> (3, "w_in");
    ``lm_head.weight`` -> ("top", "lm_head")."""
    if name in _TOP:
        return "top", _TOP[name]
    parts = name.split(".")
    if parts[:2] == ["model", "layers"]:
        return int(parts[2]), _SUFFIX[".".join(parts[3:])]
    raise KeyError(f"no seeded weight for parameter {name!r}")


def build(config, seed, max_positions, **extra):
    """``FalconH1ForCausalLM`` at the configuration's sizes with the seed's
    weights, placed a block (or one top table) at a time so that the
    model's own initial values are freed as they are replaced."""
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM)

    m, dtype = sizes(config), config["torch_dtype"]
    d = W.dims_of(m)
    for key, want in _FIXED.items():
        if m.get(key, want) != want:
            raise SystemExit(f"the program has no path for {key}="
                             f"{m[key]!r} (it implements {want!r})")
    keys = FalconH1Config.__dataclass_fields__
    model = FalconH1ForCausalLM(FalconH1Config(
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
    """The seed's weights of one group of leaves (a block index or "top"),
    made again from the seed."""
    d, dtype = W.dims_of(sizes(config)), config["torch_dtype"]
    return (W.top_weights(seed, d, dtype) if group == "top"
            else W.layer_weights(seed, group, d, dtype))


def serve_logits(config, seed, tokens, rows, quants=(None,)):
    """The reference's (and a control precision's) full-forward logits."""
    return falcon_h1_ref.serve_logits(
        sizes(config), seed, config["torch_dtype"], tokens, rows,
        quants=quants)
