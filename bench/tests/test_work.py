"""Operation and byte counts at tiny sizes against hand-worked numbers."""

import pytest

from bench import weights, work
from bench.peaks import PEAKS, peaks_for

# one layer: d 8, 2 query heads and 1 KV head of 4, d_ff 16, vocab 10,
# SSM inner width 8 (2 heads of 4), state 2, conv width 2, window 4
C = dict(family="hybrid", num_layers=1, d_model=8, num_heads=2, num_kv_heads=1, head_dim=4,
         d_ff=16, vocab_size=10, ssm_state=2, ssm_head_dim=4, ssm_conv_width=2,
         sliding_window=4, tie_embeddings=False)
WIRE = {"weights": "t16", "kv_cache": "t8"}


def test_window_clipped_mean_keys():
    # positions 0..7 attend 1,2,3,4,4,4,4,4 keys
    assert work.mean_keys(8, 4) == pytest.approx(26 / 8)
    assert work.mean_keys(4, 0) == pytest.approx(2.5)
    assert work.mean_keys(4, 16) == pytest.approx(2.5)


def test_matmul_params():
    # attention 64 + 32 + 32 + 64; SwiGLU 3*8*16; SSM in_proj 8*(16+4+2),
    # out_proj 64, conv 2*12
    assert work.layer_matmul_params(C) == 192 + 384 + 176 + 64 + 24
    assert work.matmul_params(C) == 840 + 80


def test_train_flops_per_token():
    # 6 * 920 + 3 * (4 * 2 * 4) * 26/8 + 15 * (2 * 2 * 4)
    assert work.train_flops_per_token(C, 8) == pytest.approx(5520 + 312 + 240)


def test_decode_flops_per_step():
    # per sequence: 2 * 920 + 32 * min(6, 4) + 5 * 16, for 2 sequences
    assert work.decode_flops_per_step(C, 2, 6) == pytest.approx(2 * (1840 + 128 + 80))


def test_param_count_matches_the_weights():
    p = work.param_count(C)
    assert p == {"embed": 80, "stacked": 882, "head": 80, "final": 8}
    assert sum(p.values()) == weights.n_params(C) == 1050


def test_decode_bytes_per_step():
    # weights: (882 + 80) * 2 + final 8 * 4 + 2 embedding rows * 8 * 2 = 1988
    # KV: 2 sequences * (4 + 1) positions * 2 (K, V) * 4 * 1 byte = 80
    # SSM state 2 * 2 * 16 * 4 = 256; conv 2 * 2 * 1 * 12 * 2 = 96; logits 2*10*4
    got = work.decode_bytes_per_step(C, WIRE, 2, 6, conv_bytes=2, ssm_bytes=4)
    assert got == pytest.approx(1988 + 80 + 256 + 96 + 80)


def test_ssm_family_counts():
    c = dict(family="ssm", num_layers=2, d_model=4, ssm_expand=2, ssm_head_dim=4,
             ssm_state=2, ssm_conv_width=2, vocab_size=6, tie_embeddings=True)
    # d_in 8, 2 heads, F 12: in_proj 4*(16+4+2), out_proj 32, conv 24
    assert work.layer_matmul_params(c) == 88 + 32 + 24
    assert work.decode_flops_per_step(c, 1, 100) == pytest.approx(
        2 * (2 * 144 + 24) + 5 * 2 * 16)
    # tied: the whole table is read as the head.  Per layer 120 matmul
    # weights, ln1 4, conv 24 + 12, A, dt, D 6, norm 8: 174
    p = work.param_count(c)
    assert p == {"embed": 24, "stacked": 348, "head": 0, "final": 4}
    b = work.decode_bytes_per_step(c, WIRE, 1, 100, conv_bytes=4, ssm_bytes=4)
    # weights (348 + 24) * 2 + final 4 * 4; state 2 * 2 layers * 16 * 4;
    # conv 2 * 2 * 1 * 12 * 4; logits 6 * 4
    assert b == pytest.approx(744 + 16 + 256 + 192 + 24)


def test_roofline_and_peaks():
    pk = peaks_for("TPU v5 lite")
    assert pk is PEAKS["TPU v5 lite"] and pk["flops_bf16"] == 197e12
    assert work.roofline_seconds(197e12, 1.0, pk) == (pytest.approx(1.0), "flops")
    assert work.roofline_seconds(1.0, 819e9, pk) == (pytest.approx(1.0), "bytes")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
