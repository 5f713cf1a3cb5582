"""Operation and byte counts against hand counts; the peaks table."""

import json
from pathlib import Path

import pytest

import benchpath  # noqa: F401
from benchkit import counts, peaks

CONFIGS = Path(benchpath.BENCH) / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qmm_counts():
    assert counts.qmm_ops(32, 8192, 22016) == 2 * 32 * 8192 * 22016
    # two planes of 256 words a row, plus an f32 scale per channel
    assert counts.packed_weight_bytes(8192, 22016, "tnn") == \
        2 * 22016 * 256 * 4 + 22016 * 4
    assert counts.packed_weight_bytes(8192, 22016, "bnn") == \
        22016 * 256 * 4 + 22016 * 4
    assert counts.packed_weight_bytes(100, 8, "tbn") == 8 * 4 * 4 + 8 * 4
    assert counts.qmm_bytes(4, 64, 8, "tnn") == \
        4 * 64 * 4 + (2 * 8 * 2 * 4 + 8 * 4) + 4 * 8 * 4


def test_least_time_takes_the_binding_roof():
    p = peaks.peaks_for("TPU v5 lite")
    # decode of one ternary 8192 x 22016 layer at m=32: bytes bind
    ops = counts.qmm_ops(32, 8192, 22016)
    nbytes = counts.qmm_bytes(32, 8192, 22016, "tnn")
    t = counts.least_time_s(ops, nbytes, p)
    assert t == pytest.approx(nbytes / 819e9)
    assert t > ops / 393e12
    # a big square product: operations bind
    assert counts.least_time_s(2 * 8192 ** 3, 1e6, p) == \
        pytest.approx(2 * 8192 ** 3 / 393e12)


def test_qconv_counts():
    # 3x3 tbn conv, 256 -> 256 channels at 16x16, batch 1024: one plane
    # of 72 words for each of 256 filters + scales, f32 in and out
    assert counts.qconv_ops(1024, 16, 16, 3, 3, 256, 256) == \
        2 * 1024 * 16 * 16 * 9 * 256 * 256
    assert counts.qconv_bytes(1024, 16, 16, 16, 16, 3, 3, 256, 256, "tbn") \
        == (1024 * 16 * 16 * 256 * 4 + 256 * 72 * 4 + 256 * 4
            + 1024 * 16 * 16 * 256 * 4)


def test_binarynet_operations_per_image():
    convs = [2 * 32 * 32 * 9 * 3 * 128, 2 * 32 * 32 * 9 * 128 * 128,
             2 * 16 * 16 * 9 * 128 * 256, 2 * 16 * 16 * 9 * 256 * 256,
             2 * 8 * 8 * 9 * 256 * 512, 2 * 8 * 8 * 9 * 512 * 512]
    fcs = [2 * 4 * 4 * 512 * 1024, 2 * 1024 * 1024, 2 * 1024 * 10]
    cfg = _cfg("binarynet-cifar10")
    assert counts.cnn_image_ops(cfg) == sum(convs) + sum(fcs) == 1_233_932_288
    geo = counts.cnn_layers(cfg)
    assert [(g["kind"], g["mode"], g["cin"], g["cout"]) for g in geo] == [
        ("conv", "bf16", 3, 128), ("conv", "tnn", 128, 128),
        ("conv", "tnn", 128, 256), ("conv", "tbn", 256, 256),
        ("conv", "tbn", 256, 512), ("conv", "bnn", 512, 512),
        ("fc", "bnn", 8192, 1024), ("fc", "bnn", 1024, 1024),
        ("fc", "bnn", 1024, 10)]
    assert [g["h"] for g in geo[:6]] == [32, 32, 16, 16, 8, 8]
    assert [g["pool"] for g in geo[:6]] == [False, True] * 3
    with pytest.raises(ValueError, match="modes"):
        counts.cnn_layers(dict(cfg, modes=cfg["modes"][:-1]))


def test_lm_operations_per_token():
    cfg = _cfg("chameleon-34b-4l-bf16")
    per_layer = 8192 * 8192 * 2 + 2 * 8192 * 1024 + 3 * 8192 * 22016
    assert counts.lm_layer_params(cfg) == per_layer == 692_060_160
    head = 2 * 8192 * 65536
    assert counts.lm_token_ops(cfg, 0, head=True) == 4 * 2 * per_layer + head
    assert counts.lm_token_ops(cfg, 1000, head=False) == \
        4 * (2 * per_layer + 4 * 1000 * 64 * 128)


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
