"""Operations and bytes Xing4.0-29B-A4B (``xing4``) needs, from shapes alone
— the numerators of the new cell's MFU and roofline readers.  Kept with the
benchmark so that no later change to the program moves the yardstick.  ``m``
is a configuration's published keys (``xing4_weights.model_sizes``).

Everything the architecture shares with GLM-4.7-Flash is
``lib/glm4_moe_lite_flops.py``'s count, the same function of ``m``
(attention in the absorbed form, the ACTIVE parameters a token, the grouped
products).  Added here: the hyper-connection.  A sub-layer's coefficients
are one ``[n C] x [n C, n^2 + 2 n]`` product a token; reading the branch's
input and writing its output back are elementwise over the streams and count
as bytes, not operations.
"""
from benchmark.lib import glm4_moe_lite_flops as G

attention_params = G.attention_params
expert_params = G.expert_params
lm_head_params = G.lm_head_params


def hc_columns(m):
    n = m["hc_mult"]
    return n * n + 2 * n


def hc_params(m):
    """ONE sub-layer's ``phi`` (``b`` and ``alpha`` are 27 numbers)."""
    return m["hc_mult"] * m["hidden_size"] * hc_columns(m)


def hc_sublayers(m):
    return 2 * m["num_hidden_layers"]


def dense_layer_params(m):
    return G.dense_layer_params(m) + 2 * hc_params(m)


def moe_layer_params(m):
    """Every parameter of an expert layer (what the chip holds)."""
    return G.moe_layer_params(m) + 2 * hc_params(m)


def moe_layer_active_params(m):
    """What one token is multiplied by in an expert layer."""
    return G.moe_layer_active_params(m) + 2 * hc_params(m)


def hc_flops_per_token(m):
    """The coefficient products of every sub-layer."""
    return 2 * hc_params(m) * hc_sublayers(m)


def hc_bytes(m, rows, runs, itemsize=2):
    """What the hyper-connections have to move for ``rows`` live rows in
    ``runs`` runs of a program: a sub-layer and row, ``n C`` read once for
    coefficients and branch input together, ``n C`` and the branch's ``C``
    read and ``n C`` written for the output (13 C at n = 4), in the
    model's dtype; and every sub-layer's float32 ``phi`` once a run."""
    n, c = m["hc_mult"], m["hidden_size"]
    return hc_sublayers(m) * (rows * (3 * n + 1) * c * itemsize
                              + runs * hc_params(m) * 4)


def active_params(m):
    """Matmul parameters one token is multiplied by, the head and the
    hyper-connections' ``phi`` included."""
    return G.active_params(m) + hc_params(m) * hc_sublayers(m)


def decode_token_flops(m, context):
    return G.decode_token_flops(m, context) + hc_flops_per_token(m)


def prefill_flops(m, n_tokens, with_head=False):
    return (G.prefill_flops(m, n_tokens, with_head)
            + hc_flops_per_token(m) * n_tokens)
