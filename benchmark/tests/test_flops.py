"""``lib/flops.py`` against hand counts for Mistral-7B's published sizes."""
from benchmark.lib import flops, peaks

M = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 16,
     "num_attention_heads": 32, "num_key_value_heads": 8, "vocab_size": 32768}


def test_parameters():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x14336
    assert flops.layer_matmul_params(M) == 218_103_808
    assert flops.lm_head_params(M) == 134_217_728
    assert flops.matmul_params(M) == 16 * 218_103_808 + 134_217_728


def test_kv_and_attention():
    assert flops.kv_bytes_per_token(M) == 2 * 8 * 128 * 2 * 16 == 65_536
    # one token against 1000 rows: 2 matmuls x 2 x 32 heads x 128 x 1000 x 16
    assert flops.attn_flops_per_token(M, 1000) == 4 * 32 * 128 * 1000 * 16
    assert flops.decode_token_flops(M, 0) == 2 * flops.matmul_params(M)


def test_prefill_counts_the_causal_triangle():
    layers = 2 * 16 * 218_103_808 * 256
    tri = 256 * 257 // 2
    assert flops.prefill_flops(M, 256) == layers + 4 * 32 * 128 * 16 * tri
    # the second chunk of a prompt sees the first chunk's 256 rows too
    assert flops.prefill_flops(M, 256, offset=256) - flops.prefill_flops(
        M, 256) == 4 * 32 * 128 * 16 * 256 * 256
    # chunks add up to the whole prompt
    assert flops.prefill_flops(M, 512) == flops.prefill_flops(
        M, 256) + flops.prefill_flops(M, 256, offset=256)


def test_decode_bytes():
    weights = flops.matmul_params(M) * 2
    assert flops.decode_step_bytes(M, 0, 0) == weights
    assert flops.decode_step_bytes(M, 1000, 4) == weights + 65_536 * 1004


def test_train_and_flash():
    m = dict(M, num_hidden_layers=6)
    n = 6 * 218_103_808 + 134_217_728
    assert flops.train_flops_per_token(m, 4096) == 6 * n + 6 * 6 * 4096 * 4096
    work, nbytes = flops.flash_attention_cost(m, 4, 4096)
    fwd = 2 * 4096 * 4096 * 128 * 32 * 4          # causal half of 4 L^2 d
    assert work == 6 * fwd * 3.5
    q, kv = 4 * 4096 * 32 * 128 * 2, 4 * 4096 * 8 * 128 * 2
    assert nbytes == 6 * (6 * q + 6 * kv)


def test_unknown_device_is_an_error():
    import pytest

    assert peaks.peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(RuntimeError):
        peaks.peaks_of("cpu")
