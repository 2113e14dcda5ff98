"""Model FLOPs per token and the peaks table, pinned."""

import json
from pathlib import Path

import pytest

from perfbench.lib import flops, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name,seq,n_matmul,k_mean,per_token", [
    # 48 x (1600 x 4800 + 1600^2 + 2 x 1600 x 6400) + 50257 x 1600
    ("gpt2-1.5b", 1024, 1_554_971_200, 512.5, 9_802_147_200.0),
    # 30 x (2 x 3072^2 + 2 x 3072 x 256 + 2 x 3072 x 12288) + 3072 x 49152
    ("starcoder2-3b", 4096, 3_029_336_064, 2048.5, 20_441_493_504.0),
])
def test_per_token(name, seq, n_matmul, k_mean, per_token):
    m = model(name)
    assert flops.matmul_params(m) == n_matmul
    assert flops.mean_keys(seq, m["sliding_window"]) == k_mean
    assert flops.per_token(m, seq) == pytest.approx(per_token, rel=1e-12)


def test_window_shorter_than_sequence():
    # 8 positions, window 4: keys 1, 2, 3, 4, 4, 4, 4, 4
    assert flops.mean_keys(8, 4) == 26 / 8


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite") == (197e12, 819e9, 200e9)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_roofline_share_names_its_bound():
    share, bound = peaks.roofline_share(197e12, 1.0, 2.0, "TPU v5 lite")
    assert (share, bound) == (50.0, "compute")
    share, bound = peaks.roofline_share(1.0, 819e9, 4.0, "TPU v5 lite")
    assert (share, bound) == (25.0, "memory")
