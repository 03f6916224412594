"""The required-work counts of the fused step, against hand-worked values."""
import numpy as np
import pytest

import work

# P=4 packets, F=3 features, T=2 trees of depth 2, K=5 classes
SHAPE = work.Shape(depth=4, n_features=3, n_trees=2, tree_depth=2, n_classes=5)
PEAK = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}


def test_counts_by_hand():
    # per flow: 4 packets x (4 float32 + direction + flag byte) = 72,
    # 4 metadata floats = 16, 5 probabilities = 20
    assert work.bytes_per_flow(SHAPE) == 108
    # per call: 2 trees x (3 internal nodes x 8 bytes + 4 leaves x 5 x 4 bytes)
    assert work.bytes_per_call(SHAPE) == 208
    # per flow: 4 x 3 reduction steps + 2 x 2 comparisons + 2 x 5 vote adds
    assert work.ops_per_flow(SHAPE) == 26
    assert work.call_work(SHAPE, 3) == (78, 3 * 108 + 208)
    assert work.roofline_s(SHAPE, 3, PEAK) == pytest.approx(532 / 1e2)
    assert work.roofline_s(SHAPE, 3, {"flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}) == 78.0


def test_padding_is_not_work():
    flow_len = np.zeros(256, np.int32)
    flow_len[:37] = np.arange(1, 38)
    n = work.real_flows(flow_len)
    assert n == 37
    assert work.call_work(SHAPE, n) == work.call_work(SHAPE, work.real_flows(flow_len[:64]))
    assert work.roofline_s(SHAPE, n, PEAK) == work.roofline_s(SHAPE, 37, PEAK)


def test_peaks_table():
    v5e = work.peak_for("TPU v5 lite")
    assert v5e == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        work.peak_for("cpu")


def test_config_shapes():
    import json

    cfg = json.loads((work.PEAKS.parent / "configs" / "iot-rf25.json").read_text())
    s = work.Shape.of(cfg)
    assert (s.depth, s.n_features, s.n_trees, s.tree_depth, s.n_classes) == (20, 15, 25, 10, 28)
    assert work.ops_per_flow(s) == 20 * 15 + 25 * 10 + 25 * 28
