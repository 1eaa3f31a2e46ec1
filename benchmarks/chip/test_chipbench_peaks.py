"""The peaks table and the operation and byte counts, against counts
worked out by hand."""

import pytest

from chip import peaks

TINY = dict(hidden_size=8, intermediate_size=12, num_attention_heads=4,
            num_key_value_heads=2, head_dim=2, num_hidden_layers=3,
            vocab_size=10, sliding_window=None)


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("cpu")


@pytest.mark.parametrize("seq,window,pairs", [
    (4, None, 1 + 2 + 3 + 4),       # causal: position i sees i + 1 keys
    (6, 2, 1 + 2 + 2 + 2 + 2 + 2),  # window 2 caps every row at 2
    (3, 8, 1 + 2 + 3),              # a window longer than the sequence
])
def test_attended_pairs(seq, window, pairs):
    assert peaks.attended_pairs(seq, window) == pairs


def test_matmul_and_all_params():
    # per layer: q and o 8*4*2 each, k and v 8*2*2 each, MLP 3*8*12
    per_layer = 64 + 64 + 32 + 32 + 288
    assert peaks.matmul_params(TINY) == 3 * per_layer + 10 * 8
    # plus the embedding and 2 norms a layer and the final one
    assert peaks.param_count(TINY) == 3 * per_layer + 80 + 80 + 7 * 8


def test_train_flops_per_token():
    seq = 4
    matmul = 3 * 480 + 80
    # QK and PV: 2 * 2 flops per pair per head dim, 10 pairs over 4 tokens
    attn = 4 * 4 * 2 * 10 / 4
    assert peaks.train_flops_per_token(TINY, seq) == pytest.approx(
        3 * (2 * matmul + 3 * attn))


def test_attn_fwd_and_apply_costs():
    flops, nbytes = peaks.attn_fwd_cost(2, 4, TINY, 2)
    assert flops == 4 * 2 * 4 * 2 * 10
    # q and o: 4 heads, k and v: 2 heads; 2 rows of 4 tokens, 2 dims
    assert nbytes == 2 * 2 * 4 * 2 * (2 * 4 + 2 * 2)
    assert peaks.fused_update_cost(100, 2) == (500.0, 1000.0)


def test_roofline_names_its_bound():
    pk = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.roofline_time(1000.0, 10.0, pk) == (10.0, "compute")
    assert peaks.roofline_time(10.0, 1000.0, pk) == (100.0, "memory")
