"""What the benchmark knows of ONE architecture: the Llama-shaped dense
decoder (Mistral-7B's published equations) that the program runs through
``models/llama.py`` / ``models/llama_decode.py``.  A configuration names this
file by its ``model`` key; the drivers reach the architecture only through
it, so a configuration of another architecture brings a file of its own
beside this one (the program's model built with the seed's weights, and its
plain reference) and needs no edit to a driver.

The reference (``lib/llama_ref.py``) and the seeded weights
(``lib/weights.py``) import nothing of the program; only ``build`` does.
"""
from benchmark.lib import llama_ref
from benchmark.lib import weights as W

sizes = W.model_sizes
locate = W.locate


def build(config, seed, max_positions, **extra):
    """``LlamaForCausalLM`` at the configuration's sizes with the seed's
    weights.  Parameters are born in the served type (the framework's
    default dtype is set for the construction): a float32 copy of the model
    does not fit the chip."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    m = sizes(config)
    dtype = config["torch_dtype"]
    if m["hidden_size"] // m["num_attention_heads"] != m.get(
            "head_dim", m["hidden_size"] // m["num_attention_heads"]):
        raise SystemExit("the program derives head_dim as hidden/heads")
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
            intermediate_size=m["intermediate_size"],
            num_hidden_layers=m["num_hidden_layers"],
            num_attention_heads=m["num_attention_heads"],
            num_key_value_heads=m["num_key_value_heads"],
            max_position_embeddings=max_positions,
            rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
            tie_word_embeddings=m["tie_word_embeddings"], dtype=dtype,
            **extra))
    finally:
        paddle.set_default_dtype(prev)
    W.place_into(model, seed, m, dtype)
    return model


def initial_weights(config, seed, group):
    """The seed's weights of one group of leaves (a layer index or "top"),
    made again from the seed."""
    dims, dtype = W.dims_of(sizes(config)), config["torch_dtype"]
    return (W.top_weights(seed, dims, dtype) if group == "top"
            else W.layer_weights(seed, group, dims, dtype))


def serve_logits(config, seed, tokens, rows, quants=(None,)):
    """The reference's (and a control precision's) full-forward logits."""
    return llama_ref.serve_logits(sizes(config), seed, config["torch_dtype"],
                                  tokens, rows, quants=quants)


def train_reference(config, seed, batches, opt, **kw):
    """The reference's first steps of training."""
    return llama_ref.train_reference(sizes(config), seed,
                                     config["torch_dtype"], batches, opt, **kw)
