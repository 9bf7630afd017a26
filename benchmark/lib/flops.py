"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no later change to the program moves the
yardstick.  ``m`` is a configuration's ``model`` group (the published
``config.json`` keys).  Matmul parameters only: the embedding table is a
gather, not a matmul, and is not counted (``bench.py`` counted it).
"""


def head_dim(m):
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def layer_matmul_params(m):
    """Matmul parameters of one decoder layer: q, k, v, o, gate, up, down."""
    h, i, d = m["hidden_size"], m["intermediate_size"], head_dim(m)
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * i


def lm_head_params(m):
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m):
    """Every parameter a token is multiplied by: the layers and lm_head."""
    return m["num_hidden_layers"] * layer_matmul_params(m) + lm_head_params(m)


def kv_bytes_per_token(m, itemsize=2):
    """K and V rows one token adds, over every layer."""
    return (2 * m["num_key_value_heads"] * head_dim(m) * itemsize
            * m["num_hidden_layers"])


def attn_flops_per_token(m, context):
    """QK^T and PV of ONE new token against ``context`` cached rows, over
    every layer (2 matmuls x 2 flops x heads x head_dim x context)."""
    return (4 * m["num_attention_heads"] * head_dim(m) * context
            * m["num_hidden_layers"])


def decode_token_flops(m, context):
    """Forward flops of one decoded token whose cache holds ``context``
    rows: every matmul parameter once, plus attention."""
    return 2 * matmul_params(m) + attn_flops_per_token(m, context)


def prefill_flops(m, n_tokens, offset=0, with_head=False):
    """Forward flops of prefilling ``n_tokens`` prompt tokens that sit at
    positions ``offset .. offset+n-1``: the layers' matmuls for every token
    (the lm_head only for the last one, when ``with_head``), and causal
    attention (token at position p sees p+1 rows)."""
    layers = 2 * m["num_hidden_layers"] * layer_matmul_params(m) * n_tokens
    rows = n_tokens * offset + n_tokens * (n_tokens + 1) / 2
    attn = attn_flops_per_token(m, 1) * rows
    head = 2 * lm_head_params(m) if with_head else 0
    return layers + attn + head


def decode_step_bytes(m, live_context_rows, n_live, itemsize=2):
    """Bytes ONE decode step has to move: every matmul weight once, the
    live slots' cached K/V rows read, and one new K/V row per live slot
    written."""
    weights = matmul_params(m) * itemsize
    kv = kv_bytes_per_token(m, itemsize)
    return weights + kv * live_context_rows + kv * n_live


def train_flops_per_token(m, seq):
    """Model flops per trained token, forward + backward: ``6 N`` over the
    matmul parameters plus causal attention ``6 L h seq`` (the causal
    triangle counted as half of the square); recompute is not counted."""
    return (6 * matmul_params(m)
            + 6 * m["num_hidden_layers"] * m["hidden_size"] * seq)


def flash_attention_cost(m, batch, seq, itemsize=2):
    """(flops, bytes) of causal flash attention forward + backward over a
    whole step.  Forward: QK^T and PV on the causal half = 2 x seq^2 x d per
    head.  Backward: five matmuls of the same size against the forward's
    two (2.5 x).  Bytes: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  head_dim(m))
    fwd = 2 * seq * seq * d * nh * batch
    flops = m["num_hidden_layers"] * fwd * 3.5
    q = batch * seq * nh * d * itemsize
    kv = batch * seq * nkv * d * itemsize
    fwd_bytes = 2 * q + 2 * kv
    bwd_bytes = 4 * q + 4 * kv
    return flops, m["num_hidden_layers"] * (fwd_bytes + bwd_bytes)
