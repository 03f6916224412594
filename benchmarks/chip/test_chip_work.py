"""The required-work counts of the fused step, against hand-worked values,
and the forest they are counted from."""
import hashlib
import json

import numpy as np
import pytest

import forest_build
import gen
import harness
import work

# P=4 packets, F=3 features, T=2 full trees of depth 2 (3 internal nodes
# each), K=5 classes
SHAPE = work.Shape(depth=4, n_features=3, n_trees=2, n_internal=6, tree_depth=2,
                   n_classes=5)
PEAK = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}


def _cfg(name):
    return json.loads((work.PEAKS.parent / "configs" / f"{name}.json").read_text())


def test_counts_by_hand():
    # per flow: 4 packets x (4 float32 + direction + flag byte) = 72,
    # 4 metadata floats = 16, 5 probabilities = 20
    assert work.bytes_per_flow(SHAPE) == 108
    # per call: 2 trees x (3 internal nodes x 8 bytes + 4 leaves x 5 x 4 bytes)
    assert SHAPE.n_leaves == 8
    assert work.bytes_per_call(SHAPE) == 208
    # per flow: 4 x 3 reduction steps + 2 x 2 comparisons + 2 x 5 vote adds
    assert work.ops_per_flow(SHAPE) == 26
    assert work.call_work(SHAPE, 3) == (78, 3 * 108 + 208)
    assert work.roofline_s(SHAPE, 3, PEAK) == pytest.approx(532 / 1e2)
    assert work.roofline_s(SHAPE, 3, {"flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}) == 78.0


def test_pass_through_slots_are_padding():
    # two trees in a depth-3 dense layout (7 slots): the first splits at its
    # root and at its right child, whose subtree and the left child's are
    # pass-through; the second splits at its root alone
    inf = np.inf
    thr = np.array([[0.5, inf, 2.0, inf, inf, inf, inf],
                    [1.5, inf, inf, inf, inf, inf, inf]], np.float32)
    assert work.real_nodes(thr) == (3, 2)
    s = work.Shape.of({"packet_depth": 4, "features": ["a", "b", "c"], "n_classes": 5}, thr)
    assert (s.n_trees, s.n_internal, s.n_leaves, s.tree_depth) == (2, 3, 5, 2)
    # 3 internal nodes x 8 bytes + 5 leaves x 5 x 4 bytes, not the 2 x (7 x 8
    # + 8 x 20) bytes of the padded tables
    assert work.bytes_per_call(s) == 124
    assert work.ops_per_flow(s) == 4 * 3 + 2 * 2 + 2 * 5
    assert work.real_nodes(np.full((3, 7), inf, np.float32)) == (0, 0)


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


@pytest.mark.parametrize("name, shape", [("iot-rf25", (20, 15, 25, 10, 28))])
def test_config_shapes(name, shape):
    cfg = _cfg(name)
    feats, _, (f, th, lf) = harness.build_forest(cfg, 2 ** 31 + 7)
    P, F, T, D, K = shape
    assert th.shape == (T, 2 ** D - 1) and lf.shape == (T, 2 ** D, K)
    s = work.Shape.of(cfg, th)
    assert (s.depth, s.n_features, s.n_trees, s.n_classes) == (P, F, T, K)
    assert s.n_internal == int(np.isfinite(th).sum()) and 0 < s.tree_depth <= D
    assert work.ops_per_flow(s) == P * F + T * s.tree_depth + T * K
    assert work.bytes_per_call(s) == s.n_internal * 8 + (s.n_internal + T) * K * 4


def _training_set(cfg, seed):
    feats = sorted(cfg["features"])
    P = int(cfg["packet_depth"])
    tr = gen.make_templates(cfg["use_case"], int(cfg["train_flows"]),
                            gen.seed_rng(seed, 3), int(cfg["class_seed"]))
    x = harness.window_features(feats, tr, np.arange(tr.n_flows),
                                np.minimum(tr.flow_len, P), P)
    return x.astype(np.float32), tr.label


def _sklearn_count(trees):
    return (sum(int((t.children_left >= 0).sum()) for t in trees),
            max(int(t.max_depth) for t in trees))


def test_dense_count_is_the_fitted_forest_count():
    cfg = _cfg("iot-rf25")
    x, y = _training_set(cfg, 11)
    kw = dict(n_trees=int(cfg["n_trees"]), depth=int(cfg["max_depth"]), seed=11)
    trees = forest_build.fit(x, y, **kw)
    _, th, _ = forest_build.grow(x, y, n_classes=int(cfg["n_classes"]), **kw)
    assert work.real_nodes(th) == _sklearn_count(trees)


def test_paper_forest_counts_megabytes_not_gigabytes():
    # the paper's iot-class forest: 100 trees of depth up to 20, counted
    # from the fitted trees (its dense tables would take 12.6 GB to build)
    cfg = _cfg("iot-rf25")
    x, y = _training_set(cfg, 7)
    n_internal, deepest = _sklearn_count(
        forest_build.fit(x, y, n_trees=100, depth=20, seed=7))
    s = work.Shape(20, 15, 100, n_internal, deepest, 28)
    padded = 100 * ((2 ** 20 - 1) * 8 + 2 ** 20 * 28 * 4)
    assert padded > 12.5e9
    assert 1e6 < work.bytes_per_call(s) < 20e6
    assert 15 <= deepest <= 20


# sha256 of (dtype, shape, bytes) of the feature, threshold and leaf arrays
# that the stacking build of the parent commit grew for iot-rf25
IOT_RF25_FORESTS = {
    2 ** 31 + 7: "f75f01ef990c802f4addece24d3a3b46c836046f31b5abe14dc67a81d5b7ff4f",
    12345: "5a095a3f445e2a29f1ccecae31004fd5d82e97d77b4343eca1c980d1108377e6",
}


@pytest.mark.parametrize("seed", sorted(IOT_RF25_FORESTS))
def test_grown_forest_is_bit_identical(seed):
    _, _, forest = harness.build_forest(_cfg("iot-rf25"), seed)
    h = hashlib.sha256()
    for a in forest:
        h.update(a.dtype.str.encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == IOT_RF25_FORESTS[seed]
