"""Kernel operation and byte counts against hand-worked shapes."""

from bench import harness


def cost(k):
    return harness.load_module(harness.BENCH, "costs", f"{k}.py")


def test_fused_seq_serving_round():
    s = {"t": 8, "m": 64, "k": 2312, "n": 128, "noise": True, "train": False}
    assert cost("fused_seq").ops(s) == 2 * 8 * 64 * 2312 * 128
    events = 4 * 8 * 64 * 2312
    planes = 2 * 2312 * 128
    small = 4 * 3 * 128 + 4 * 64 * 128 * 2
    outs = 4 * 8 * 64 * 128 * 2 + 4 * 8 * 64
    noise = 4 * 8 * 64 * 128
    assert cost("fused_seq").nbytes(s) == events + planes + small + outs \
        + noise


def test_fused_seq_training_adds_its_traces():
    s = {"t": 20, "m": 64, "k": 2312, "n": 128, "noise": False,
         "train": True}
    base = dict(s, train=False)
    assert cost("fused_seq").nbytes(s) - cost("fused_seq").nbytes(base) \
        == 2 * 4 * 20 * 64 * 128


def test_bptt():
    s = {"t": 20, "m": 64, "k": 2312, "n": 128}
    assert cost("fused_seq_bptt").ops(s) == 2 * 20 * 64 * 2312 * 128
    assert cost("fused_seq_bptt").nbytes(s) == (
        4 * 20 * 64 * 2312 + 16 * 20 * 64 * 128 + 4 * 128
        + 8 * 64 * 128 + 4 * 2312 * 128)


def test_multi_seq_sums_its_layers():
    s = {"t": 30, "m": 64, "layers": [[2048, 256], [256, 128]]}
    assert cost("fused_multi_seq").ops(s) == \
        2 * 30 * 64 * (2048 * 256 + 256 * 128)
    layer = [2 * k * n + 4 * 3 * n + 4 * 64 * n * 2 + 4 * 30 * 64 * n
             + 4 * 30 * 64 * 2 for k, n in s["layers"]]
    assert cost("fused_multi_seq").nbytes(s) == (
        4 * 30 * 64 * 2048 + sum(layer) + 4 * 30 * 64 * 128 * 2)


def test_peaks_are_keyed_by_device_kind():
    peaks = harness.peaks_for("TPU v5 lite", require_chip=True)
    assert peaks["int8_ops"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    import pytest
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary", require_chip=True)
