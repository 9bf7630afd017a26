"""Operations and bytes Falcon-H1 needs, from shapes alone — the numerators
of the new cell's MFU and roofline readers.  Kept with the benchmark so that
no later change to the program moves the yardstick.  ``m`` is a
configuration's published keys (``falcon_h1_weights.model_sizes``).  Matmul
parameters only: the embedding table is a gather and is not counted.

Counting rules.  A multiply-add is 2 operations.  Causal work counts its
triangle (half the square), in the attention and inside an SSD chunk alike.
The recurrence's elementwise work is counted per state element: decay (1),
outer product and add (2), contraction with C (2).
"""
from benchmark.lib import flops as llama_flops

kv_bytes_per_token = llama_flops.kv_bytes_per_token
attn_flops_per_token = llama_flops.attn_flops_per_token
lm_head_params = llama_flops.lm_head_params


def conv_channels(m):
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def in_proj_width(m):
    """``[z | x | B | C | dt]``."""
    return m["mamba_d_ssm"] + conv_channels(m) + m["mamba_n_heads"]


def attention_params(m):
    h, d = m["hidden_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return h * nh * d + 2 * h * nkv * d + nh * d * h


def mixer_params(m):
    """The Mamba-2 branch's two projections."""
    return (m["hidden_size"] * in_proj_width(m)
            + m["mamba_d_ssm"] * m["hidden_size"])


def mlp_params(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_matmul_params(m):
    return attention_params(m) + mixer_params(m) + mlp_params(m)


def matmul_params(m):
    return m["num_hidden_layers"] * layer_matmul_params(m) + lm_head_params(m)


def state_elements(m):
    """Elements of ONE slot's recurrent state in ONE layer."""
    return m["mamba_d_ssm"] * m["mamba_d_state"]


def state_bytes_per_slot(m, state_itemsize=4, tail_itemsize=2):
    """One slot's recurrent state and conv tail over every layer."""
    return m["num_hidden_layers"] * (
        state_elements(m) * state_itemsize
        + (m["mamba_d_conv"] - 1) * conv_channels(m) * tail_itemsize)


def conv_flops_per_token(m):
    return 2 * m["mamba_d_conv"] * conv_channels(m) * m["num_hidden_layers"]


def state_update_flops_per_token(m):
    """The one-token update of decode, every layer: 5 per state element."""
    return 5 * state_elements(m) * m["num_hidden_layers"]


def scan_flops(m, n_tokens):
    """The chunked scan over ``n_tokens`` of one sequence, every layer:
    inside a chunk the causal half of ``C B^T`` (Q x Q x N a group) and of
    its product with x (Q x Q x P a head); per token the chunk's state
    contribution and the read of the incoming state (2 x P x N a head
    each); per chunk boundary one decay-and-add of the state."""
    q, g, n = m["mamba_chunk_size"], m["mamba_n_groups"], m["mamba_d_state"]
    d_ssm = m["mamba_d_ssm"]
    inside = n_tokens * q * (g * n + d_ssm)          # halves of 2 x ...
    across = n_tokens * 4 * d_ssm * n
    boundary = -(-n_tokens // q) * 2 * d_ssm * n
    return m["num_hidden_layers"] * (inside + across + boundary)


def scan_bytes(m, n_tokens, n_chunk_runs, itemsize=2):
    """What the chunked scan has to move, every layer: per prefill-chunk run
    the slot's state read and written (float32), per token x, B, C in, dt
    (float32) in and y (float32) out."""
    per_token = (conv_channels(m) * itemsize + m["mamba_n_heads"] * 4
                 + m["mamba_d_ssm"] * 4)
    return m["num_hidden_layers"] * (
        n_chunk_runs * 2 * state_elements(m) * 4 + n_tokens * per_token)


def state_update_bytes(m, n_slot_steps):
    """What the decode update has to move for ``n_slot_steps`` (live slot x
    step) pairs: the slot's state and tail read and written, every layer."""
    return 2 * state_bytes_per_slot(m) * n_slot_steps


def decode_token_flops(m, context):
    """Forward flops of one decoded token whose cache holds ``context``
    rows: every matmul parameter once, attention over the context, the
    convolution and the state update."""
    return (2 * matmul_params(m) + attn_flops_per_token(m, context)
            + conv_flops_per_token(m) + state_update_flops_per_token(m))


def prefill_flops(m, n_tokens, with_head=False):
    """Forward flops of prefilling ``n_tokens`` prompt tokens from position
    0: the layers' matmuls and convolution for every token, causal
    attention, the chunked scan, and the head once when ``with_head``."""
    layers = 2 * m["num_hidden_layers"] * layer_matmul_params(m) * n_tokens
    attn = attn_flops_per_token(m, 1) * n_tokens * (n_tokens + 1) / 2
    head = 2 * lm_head_params(m) if with_head else 0
    return (layers + attn + conv_flops_per_token(m) * n_tokens
            + scan_flops(m, n_tokens) + head)


def decode_step_bytes(m, live_context_rows, n_live, itemsize=2):
    """Bytes ONE decode step has to move: every matmul weight once, the
    live slots' K/V rows read and one row each written, and the live
    slots' recurrent state read and written."""
    kv = kv_bytes_per_token(m, itemsize)
    return (matmul_params(m) * itemsize + kv * (live_context_rows + n_live)
            + state_update_bytes(m, n_live))
