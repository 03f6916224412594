"""The paper's forest as the program serves it (`CompactForest`): built from
the fitted trees' CART arrays it equals the compaction of the dense form the
harness hands the program, at 100 trees of depth 20 it is megabytes and not
gigabytes, its bfloat16 control can be read a tree at a time
(`control_by_tree`), and a traced run of `iot-rf100-uniform-sat` (on the CPU, at a small
size, through the served fused kernel) reads `forest_lane_use_pct` from the
program's counters.
"""
import numpy as np
import pytest

import control_by_tree
import forest_build
import harness
import reference
from test_chip_control import SEED, SMALL
from test_chip_work import _cfg, _sklearn_count, _training_set

CELL = "iot-rf100-uniform-sat"


def _cart(trees, y):
    from repro.core.forest import CompactForest

    return CompactForest.from_cart(trees, 28, columns=np.unique(y))


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_cart_arrays_give_the_compacted_dense_forest(seed):
    from repro.core.forest import CompactForest, DenseForest

    # the iot-rf25 shape: its dense form is small enough to grow here
    cfg = _cfg("iot-rf25")
    x, y = _training_set(cfg, seed)
    kw = dict(n_trees=int(cfg["n_trees"]), depth=int(cfg["max_depth"]), seed=seed)
    f, th, lf = forest_build.grow(x, y, n_classes=int(cfg["n_classes"]), **kw)
    dense = CompactForest.from_dense(DenseForest(f, th, lf, kw["depth"], x.shape[1]))
    cart = _cart(forest_build.fit(x, y, **kw), y)
    assert cart.widths == dense.widths and cart.n_internal == dense.n_internal
    assert np.array_equal(cart.nodes, dense.nodes)
    assert np.array_equal(cart.leaf, dense.leaf)


def test_paper_forest_compacts_to_megabytes():
    cfg = _cfg("iot-rf100-d20")
    x, y = _training_set(cfg, 7)
    trees = forest_build.fit(x, y, n_trees=int(cfg["n_trees"]),
                             depth=int(cfg["max_depth"]), seed=7)
    cf = _cart(trees, y)
    assert (cf.n_internal, cf.depth) == _sklearn_count(trees)
    assert cf.n_trees == 100 and 15 <= cf.depth <= 20
    assert cf.nbytes < 20e6
    # a flow's one-hot lanes a tree: level windows and the leaf table,
    # about 3.5k against the 2**20-wide leaf one-hot of the dense layout
    per_tree = cf.lanes // cf.n_trees + cf.leaf.shape[2]
    assert per_tree < 6000


def test_control_by_tree_is_the_checks_control():
    """A tree at a time, the float64 interval and the bfloat16 control are
    the ones the check computes over the whole dense forest of the seed."""
    cfg = dict(_cfg("iot-rf25"), **SMALL, n_trees=5, max_depth=9)
    feats, pool, (f, th, lf) = harness.build_forest(cfg, SEED)
    P, D = int(cfg["packet_depth"]), int(cfg["max_depth"])
    x = harness.window_features(feats, pool, np.arange(pool.n_flows),
                                np.minimum(pool.flow_len, P), P)
    lo, hi, ctl = control_by_tree.by_tree(cfg, SEED)
    want = reference.prob_interval(x, f, th, lf, D) + (
        reference.control_probs(x, f, th, lf, D),)
    for got, w in zip((lo, hi, ctl), want):
        np.testing.assert_allclose(got, w, rtol=0, atol=1e-12)
    assert (ctl != lo).any()


@pytest.fixture(scope="module")
def traced():
    small = dict(SMALL, n_trees=4, max_depth=8)
    return harness.run(CELL, SEED, 1.5, True, pps=4000.0, require_tpu=False,
                       log=lambda s: None, overrides=small)


def test_lane_use_reads_the_program_counters(traced):
    out, r = traced
    assert out["correct"] is True
    c = r.program["counters"]
    batches = r.program["spans"]["submit"]["calls"]
    assert batches > 0
    # every submitted batch counts the served forest once
    assert c["forest.nodes"] % batches == 0 and c["forest.lanes"] % batches == 0
    use = 100.0 * c["forest.nodes"] / c["forest.lanes"]
    assert out["metrics"]["forest_lane_use_pct"] == {"value": use, "unit": "%"}
    assert 0 < use <= 100
    assert harness.metric_reader("forest_lane_use_pct")(harness.Result()) is None
