import pytest

from bench import roofline

QWEN = {"num_hidden_layers": 36, "hidden_size": 2560,
        "intermediate_size": 9728, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}
V5E = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# K*N summed over q, k, v, o, gate, up, down of one qwen3-4b layer
KN = (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728)


def test_projection_shapes():
    assert sum(k * n for k, n in roofline.projections(QWEN)) == KN


def test_decode_step_is_weight_bytes_at_real_rows():
    # 16 active slots: bandwidth-bound; the rows are the 16 real ones,
    # not the 128 the kernel pads to
    m = 16
    per_layer = sum(k * n + m * k + 2 * m * n + 4 * n + 4
                    for k, n in roofline.projections(QWEN))
    want = 36 * per_layer / 819e9
    got = roofline.nibble_least_s(QWEN, [m], V5E)
    assert got == pytest.approx(want, rel=1e-12)
    padded = roofline.nibble_least_s(QWEN, [128], V5E)
    assert padded > got * 1.05


def test_prefill_is_ops_at_real_rows_without_plane_doubling():
    m = 1000
    got = roofline.nibble_least_s(QWEN, [m], V5E)
    # compute-bound at 1,000 rows: 2*M*K*N once, not twice for the two
    # nibble planes, and not at the 2,048-row slot budget
    assert got == pytest.approx(36 * 2 * m * KN / 393e12, rel=1e-3)
    assert roofline.nibble_least_s(QWEN, [2048], V5E) > 2 * got


def test_least_time_adds_over_dispatches():
    one = roofline.nibble_least_s(QWEN, [16], V5E)
    assert roofline.nibble_least_s(QWEN, [16] * 8, V5E) == \
        pytest.approx(8 * one)
    assert roofline.nibble_least_s(QWEN, [], V5E) == 0.0


def test_model_flops():
    proj = 36 * KN
    attn = 4 * 36 * 32 * 128
    got = roofline.model_flops(QWEN, [10], [11, 12], 3)
    want = (2 * proj * 10 + attn * 55 + 2 * proj * 2 + attn * 23
            + 3 * 2 * 2560 * 151936)
    assert got == pytest.approx(want)
