"""Operations and bytes GLM-4.7-Flash (``glm4_moe_lite``) needs, from shapes
alone — the numerators of the new cell's MFU and roofline readers.  Kept with
the benchmark so that no later change to the program moves the yardstick.
``m`` is a configuration's published keys
(``glm4_moe_lite_weights.model_sizes``).  Matmul parameters only: the
embedding table is a gather and is not counted.

Counting rules.  A multiply-add is 2 operations.  A token is multiplied by
its ACTIVE parameters: the attention's projections, in an expert layer the
router, ``num_experts_per_tok`` routed experts and the shared ones, in a
dense layer the dense FFN, and the head.  Attention is counted in the form
the program runs, the absorbed one: a (query, cached row) pair costs, a head,
a score over the whole latent row (``kv_lora_rank + qk_rope_head_dim``) and a
value sum over its first ``kv_lora_rank`` columns.  Causal work counts its
triangle.
"""


def row_width(m):
    """Values of one cached latent row ``[c | k_r]``."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m):
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
            + h * row_width(m)
            + heads * m["kv_lora_rank"] * (m["qk_nope_head_dim"]
                                           + m["v_head_dim"])
            + heads * m["v_head_dim"] * h)


def expert_params(m):
    """ONE routed expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m):
    return m["hidden_size"] * m["n_routed_experts"]


def moe_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def dense_layer_params(m):
    return attention_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def moe_layer_active_params(m):
    """What one token is multiplied by in an expert layer."""
    return (attention_params(m) + router_params(m)
            + (m["num_experts_per_tok"] + m["n_shared_experts"])
            * expert_params(m))


def moe_layer_params(m):
    """Every parameter of an expert layer (what the chip holds)."""
    return (attention_params(m) + router_params(m)
            + (m["n_routed_experts"] + m["n_shared_experts"])
            * expert_params(m))


def lm_head_params(m):
    return m["hidden_size"] * m["vocab_size"]


def active_layer_params(m):
    return (m["first_k_dense_replace"] * dense_layer_params(m)
            + moe_layers(m) * moe_layer_active_params(m))


def active_params(m):
    """Parameters one token is multiplied by, the head included."""
    return active_layer_params(m) + lm_head_params(m)


def attn_flops_per_pair(m):
    """One (query token, cached row) pair, every layer, absorbed form."""
    return (2 * m["num_attention_heads"]
            * (row_width(m) + m["kv_lora_rank"]) * m["num_hidden_layers"])


def decode_token_flops(m, context):
    return 2 * active_params(m) + attn_flops_per_pair(m) * context


def prefill_flops(m, n_tokens, with_head=False):
    """Prefilling ``n_tokens`` from position 0 (the head once)."""
    return (2 * active_layer_params(m) * n_tokens
            + attn_flops_per_pair(m) * n_tokens * (n_tokens + 1) / 2
            + (2 * lm_head_params(m) if with_head else 0))


def latent_bytes(m, rows, itemsize=2):
    """``rows`` latent rows read or written, ONE layer's worth each."""
    return rows * row_width(m) * itemsize


def experts_flops(m, pairs):
    """The three grouped products over ``pairs`` (token, expert) pairs."""
    return 2 * expert_params(m) * pairs


def experts_bytes(m, touched, pairs, itemsize=2):
    """What the grouped products have to move: the weights of the experts
    TOUCHED (``touched`` = experts with at least one pair, summed over
    layers and runs) once each, and each pair's activations (the token in,
    the two hidden products out and back in, the result out)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return (touched * expert_params(m) + pairs * (2 * h + 3 * f)) * itemsize
