"""Count functions against hand-worked numbers, and the peaks table."""

import pytest

from bench import counts, peaks


def test_kv_batch_bytes_hand_worked():
    row = counts.kv_row_bytes(assoc=8, planes=3)
    assert row == 96
    # 8000 distinct sets read and written, 8192 queries of 16 B in, 26 B out
    assert counts.kv_batch_bytes(8000, 8192, row, 16, 26) == 1_880_064


def test_phi3_decode_flops_and_bytes_hand_worked():
    s = counts.dense_decoder_sizes(d=3072, n_heads=32, n_kv_heads=32,
                                   d_head=96, d_ff=8192, vocab=32064,
                                   n_layers=32)
    assert s["layer_matmul"] == 113_246_208
    assert s["head"] == 98_500_608
    # one row at 299 live tokens: weights 2 x 3,722,379,264 FLOPs, and
    # attention 32 layers x 4 x 3072 x 300
    assert counts.decode_flops(s, [299]) == 7_444_758_528 + 117_964_800
    # weights 7,444,758,528 B + norms 65 x 3072 x 4 + KV 32 x 6144 x 2 x 300
    # + one embedding row
    assert counts.decode_bytes(s, [299]) == (7_444_758_528 + 798_720
                                             + 117_964_800 + 6144)
    # rows add their own attention; weights count once per launch
    two = counts.decode_bytes(s, [0, 299]) - counts.decode_bytes(s, [299])
    assert two == 32 * 6144 * 2 * 1 + 6144


def test_peaks_keyed_by_device_kind():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
