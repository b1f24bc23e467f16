"""Operation and byte counts against hand counts at published widths."""
import pytest

from chipbench import counts, model


def test_qwen3_decode_reads_8_04e9_weight_bytes():
    spec = model.load_spec("qwen3-4b")
    # 36 layers x (2560*4096 + 2*2560*1024 + 4096*2560 + 3*2560*9728)
    # plus the tied 2560 x 151936 head, two bytes each
    per_layer = 2560 * 4096 * 2 + 2 * 2560 * 1024 + 3 * 2560 * 9728
    assert per_layer == 100_925_440
    assert counts.matmul_params(spec) == 36 * per_layer + 2560 * 151936
    assert counts.decode_weight_bytes(spec) == 8_044_544_000
    # every weight the generator draws: the head is the embedding, plus
    # float32 norms
    assert model.weight_bytes(spec) == 8_044_544_000 + 4 * (
        2560 + 36 * (2 * 2560 + 2 * 128))


def test_kv_bytes_per_token():
    # 36 layers x 8 KV heads x head_dim 128 x (K and V) x 2 bytes
    assert counts.kv_bytes_per_token(model.load_spec("qwen3-4b")) == \
        36 * 8 * 128 * 2 * 2 == 147_456


def test_kernel_cost_by_hand():
    ops, byt = counts.kernel_cost(8, 2560, 4096, passes=1)
    assert ops == 2 * 8 * 2560 * 4096
    assert byt == 8 * 2560 * 2 + 2560 * 4096 * 2 + 8 * 4096 * 4
    assert counts.kernel_cost(8, 2560, 4096, passes=3)[0] == 3 * ops


def test_forward_kernel_calls_count_weights_once():
    spec = model.load_spec("qwen3-4b")
    calls = counts.forward_kernel_calls(spec, rows=8, tokens=1, passes=1)
    assert len(calls) == counts.kernels_per_forward(spec) == 36 * 7
    w = sum(K * N for _, K, N in counts.layer_matmuls(spec)) * 36
    act = sum(8 * (K * 2 + N * 4) for _, K, N in counts.layer_matmuls(spec)) * 36
    assert sum(b for _, b in calls) == 2 * w + act
    assert sum(o for o, _ in calls) == 2 * 8 * w


def test_token_flops_adds_attention_over_the_context():
    spec = model.load_spec("qwen3-4b")
    base = 2 * counts.matmul_params(spec)
    assert counts.token_flops(spec, 1) == base + 4 * 36 * 32 * 128
    assert (counts.token_flops(spec, 1001) - counts.token_flops(spec, 1)
            == 4 * 36 * 32 * 128 * 1000)


def test_unknown_chip_is_an_error():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
