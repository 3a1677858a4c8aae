"""Operations and bytes counted from shapes, and the peaks table."""

import json
from pathlib import Path

import pytest

from chipbench import counts

H2O = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "h2o-danube-3-4b.json").read_text())["model"]


def test_h2o_matmul_weights_by_hand():
    # per layer: q and o 3840 x (32 * 120), k and v 3840 x (8 * 120),
    # gate, up and down 3840 x 10240; then the head over 32000 tokens
    layer = 2 * 3840 * 3840 + 2 * 3840 * 960 + 3 * 3840 * 10240
    assert layer == 154_828_800
    want = 2 * layer + 3840 * 32000
    assert counts.dense_matmul_params(H2O) == want == 432_537_600


def test_h2o_train_flops_per_token_by_hand():
    # causal over 1024 positions, window 4096 never reached: a query sees
    # (1024 + 1) / 2 keys on average; 12 * heads * head_dim per key and layer
    attn = 2 * 12 * 32 * 120 * 512.5
    want = 6 * 432_537_600 + attn
    assert counts.train_flops_per_token(H2O, 1024) == pytest.approx(want)
    assert counts.train_flops_per_token(H2O, 1024) == pytest.approx(
        2.6425e9, rel=1e-3)


def test_sliding_window_caps_the_context():
    assert counts.mean_context(4, None) == (1 + 2 + 3 + 4) / 4
    assert counts.mean_context(4, 2) == (1 + 2 + 2 + 2) / 4


def test_alltoall_egress_bytes():
    # [4, 6144, 5120] bf16 per chip over a group of 4: three blocks leave
    buf = 4 * 6144 * 5120 * 2
    assert buf == 240 * 2**20
    assert counts.alltoall_egress_bytes(buf, 4) == 3 * 6144 * 5120 * 2
    assert counts.alltoall_egress_bytes(buf, 1) == 0


def test_peaks_table():
    row = counts.peaks("TPU v5 lite")
    assert row["bf16_flop_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["ici_bytes_per_s"] == 1600e9 / 8
    with pytest.raises(KeyError):
        counts.peaks("cpu")
