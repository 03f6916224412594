"""The real-node forest layout (`CompactForest`) against the plain references.

Seeded random forests of irregular shape — leaves at every level, a
single-leaf tree, one perfect tree, depths 1 to 12, classes whose leaves
are all zero — are written both as CART node arrays and in the dense
level-order layout. The compact walk must equal the dense walk bit for
bit, and every kernel that reads the compact layout (unfused, fused,
aggregate, multi-tenant; interpret mode) must give the float32
reference's classes on the dense arrays, with probabilities within 1e-5.
"""
import dataclasses
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import (
    CompactForest, DenseForest, compact_apply_np, forest_apply_np,
)
from repro.core.search_space import FeatureRep
from repro.kernels import ops, ref
from repro.traffic import extract_features, make_dataset
from repro.traffic.multi_tenant import build_multi_tenant_pipeline
from repro.traffic.pipeline import build_pipeline

K = 5
DEPTHS = [1, 2, 5, 8, 12]


def random_cart(rng, depth, p_leaf, n_features, dead, x=None):
    """One CART tree as scikit-learn's ``tree_`` arrays: each node on a
    level above `depth` is a leaf with probability `p_leaf`; thresholds are
    float32 values (drawn from `x`'s column where given), leaf values
    class counts, zero in the classes `dead`."""
    left, right, feat, thr = [], [], [], []
    frontier, n = [0], 1
    level = 0
    while frontier:
        nxt = []
        for _ in frontier:
            if level < depth and rng.random() >= p_leaf:
                f = int(rng.integers(n_features))
                t = (rng.standard_normal() if x is None
                     else x[rng.integers(len(x)), f])
                left.append(n)
                right.append(n + 1)
                feat.append(f)
                thr.append(float(np.float32(t)))
                nxt += [n, n + 1]
                n += 2
            else:
                left.append(-1)
                right.append(-1)
                feat.append(-2)
                thr.append(-2.0)
        frontier = nxt
        level += 1
    # breadth-first ids: a level's children follow the level in order
    value = rng.integers(0, 20, (n, 1, K)).astype(np.float64)
    value[:, 0, dead] = 0
    value[:, 0, [c for c in range(K) if c not in dead][0]] += 1
    return types.SimpleNamespace(
        children_left=np.array(left), children_right=np.array(right),
        feature=np.array(feat), threshold=np.array(thr), value=value)


def dense_of(trees, depth, n_features, rng):
    """The dense level-order layout of CART trees: a leaf above the last
    level becomes pass-through slots down to its leftmost leaf slot, which
    holds its distribution; every other slot below it holds noise that no
    walk reads."""
    T = len(trees)
    feature = rng.integers(0, n_features, (T, 2 ** depth - 1)).astype(np.int32)
    threshold = np.full((T, 2 ** depth - 1), np.inf, np.float32)
    leaf = rng.random((T, 2 ** depth, K)).astype(np.float32)
    for t, tr in enumerate(trees):
        value = tr.value[:, 0, :] / tr.value[:, 0, :].sum(axis=1, keepdims=True)
        stack = [(0, 0, 0)]                     # (node, slot, level)
        while stack:
            node, g, lvl = stack.pop()
            if tr.children_left[node] >= 0:
                feature[t, g] = tr.feature[node]
                threshold[t, g] = tr.threshold[node]
                stack += [(tr.children_left[node], 2 * g + 1, lvl + 1),
                          (tr.children_right[node], 2 * g + 2, lvl + 1)]
            else:
                while lvl < depth:
                    g, lvl = 2 * g + 1, lvl + 1
                leaf[t, g - (2 ** depth - 1)] = value[node]
    return DenseForest(feature, threshold, leaf, depth, n_features)


def irregular(seed, depth, n_trees=6, n_features=7, x=None):
    """(CART trees, their dense forest): tree 0 a single leaf, tree 1
    perfect to `depth`, the rest random with leaves on every level."""
    rng = np.random.default_rng(seed)
    dead = [1, 3]
    p = [1.0, 0.0] + list(rng.uniform(0.1, 0.4, n_trees - 2))
    trees = [random_cart(rng, depth, q, n_features, dead, x) for q in p]
    return trees, dense_of(trees, depth, n_features, rng)


@pytest.mark.parametrize("depth", DEPTHS)
def test_compact_walk_is_the_dense_walk_bit_for_bit(depth):
    trees, dense = irregular(depth, depth)
    cf = CompactForest.from_dense(dense)
    X = np.random.default_rng(depth).standard_normal((2000, 7)).astype(np.float32)
    assert np.array_equal(compact_apply_np(cf, X), forest_apply_np(dense, X))
    # the layout holds the trees' real nodes and no more
    assert cf.n_internal == sum(int((t.children_left >= 0).sum()) for t in trees)
    assert cf.depth == depth and cf.n_trees == len(trees)
    assert all(w % 128 == 0 for w in cf.widths) and cf.leaf.shape[2] % 128 == 0
    assert cf.lanes == len(trees) * sum(cf.widths) >= cf.n_internal
    assert not cf.leaf[:, [1, 3]].any()


@pytest.mark.parametrize("depth", DEPTHS)
def test_from_cart_equals_the_compacted_dense_form(depth):
    trees, dense = irregular(100 + depth, depth)
    a = CompactForest.from_cart(trees, K)
    b = CompactForest.from_dense(dense)
    assert a.widths == b.widths and a.n_internal == b.n_internal
    assert np.array_equal(a.nodes, b.nodes) and np.array_equal(a.leaf, b.leaf)


def test_from_cart_rounds_thresholds_down_to_float32():
    # float32(0.1) lies above 0.1: it must go right, as against the
    # float64 threshold, and the float32 just below it left
    tr = types.SimpleNamespace(
        children_left=np.array([1, -1, -1]), children_right=np.array([2, -1, -1]),
        feature=np.array([0, -2, -2]), threshold=np.array([0.1, -2.0, -2.0]),
        value=np.array([[[2.0, 2.0]], [[1.0, 0.0]], [[0.0, 3.0]]]))
    cf = CompactForest.from_cart([tr], 2)
    x = np.array([[np.float32(0.1)], [np.nextafter(np.float32(0.1), 0)]], np.float32)
    assert float(np.float32(0.1)) > 0.1
    assert np.array_equal(compact_apply_np(cf, x), [[0.0, 1.0], [1.0, 0.0]])


@pytest.mark.parametrize("depth", DEPTHS)
def test_unfused_kernel_matches_the_dense_reference(depth):
    _, dense = irregular(200 + depth, depth)
    cf = CompactForest.from_dense(dense)
    X = np.random.default_rng(depth).standard_normal((300, 7)).astype(np.float32)
    got = np.asarray(ops.forest_infer(jnp.asarray(X), jnp.asarray(cf.nodes),
                                      jnp.asarray(cf.leaf), cf.widths,
                                      block_n=128, block_t=4))
    want = np.asarray(ref.forest_infer_ref(
        jnp.asarray(X), jnp.asarray(dense.feature), jnp.asarray(dense.threshold),
        jnp.asarray(dense.leaf), depth))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.array_equal(got.argmax(axis=1), want.argmax(axis=1))


# no median: every feature is a running statistic, so the pipelines have
# the aggregate entry
FEATS = ("dur", "s_load", "s_bytes_mean", "d_iat_std", "ack_cnt", "s_pkt_cnt",
         "d_bytes_max")


@pytest.fixture(scope="module")
def traffic():
    ds = make_dataset("app-class", n_flows=150, max_pkts=12, seed=5)
    return ds, np.asarray(extract_features(ds, FEATS, 12), np.float32)


def _assert_as_reference(probs, want):
    np.testing.assert_allclose(probs, want, atol=1e-5)
    assert np.array_equal(probs.argmax(axis=1), want.argmax(axis=1))


@pytest.mark.parametrize("depth", [5, 12])
def test_fused_and_aggregate_kernels_match_the_dense_reference(traffic, depth):
    ds, x = traffic
    _, dense = irregular(300 + depth, depth, x=x)
    rep = FeatureRep(FEATS, depth=12)
    fused = build_pipeline(rep, dense, 12, fused=True)
    plain = build_pipeline(rep, dense, 12, use_kernel=False)
    cf = CompactForest.from_dense(dense)
    assert fused.counts == {"forest.nodes": cf.n_internal, "forest.lanes": cf.lanes}
    assert plain.counts == {}
    _assert_as_reference(fused.probabilities(ds), plain.probabilities(ds))
    # the aggregate entry on running-statistic rows of the same flows
    from repro.serve.runtime import FlowTable, PacketStream

    st = PacketStream.from_dataset(ds, seed=0)
    table = FlowTable(1024, 12, track_agg=True)
    f = st.fid
    table.observe_batch(st.key[f], st.base_t, st.rel_ts32, st.size, st.direction,
                        st.ttl, st.winsize, st.flags_byte, st.proto[f],
                        st.s_port[f], st.d_port[f], f, st.fin)
    table.flush_agg()
    slots = np.flatnonzero(table.ctrl["state"] != 0)[:128]
    args = (table.agg[slots], table.proto[slots], table.s_port[slots],
            table.d_port[slots])
    _assert_as_reference(np.asarray(fused.predict_agg(*args)),
                         np.asarray(plain.predict_agg(*args)))


def test_multi_tenant_kernel_matches_the_dense_reference(traffic):
    ds, x = traffic
    reps = [FeatureRep(FEATS, depth=12), FeatureRep(FEATS[2:6], depth=8)]
    forests = [irregular(401, 9, x=x)[1],
               irregular(402, 4, n_trees=3, n_features=4, x=x[:, 2:6])[1]]
    fused = build_multi_tenant_pipeline(reps, forests, fused=True)
    unfused = build_multi_tenant_pipeline(reps, forests, use_kernel=True)
    plain = build_multi_tenant_pipeline(reps, forests, use_kernel=False)
    want = plain.probabilities(ds)
    _assert_as_reference(fused.probabilities(ds), want)
    _assert_as_reference(unfused.probabilities(ds), want)
    compact = [CompactForest.from_dense(f) for f in forests]
    assert fused.counts["forest.nodes"] == sum(c.n_internal for c in compact)
    assert fused.counts["forest.lanes"] == sum(c.lanes for c in compact)


def test_pipelines_serve_a_compact_forest_as_its_dense_form(traffic):
    """Both builders take the compact layout itself (as `from_cart` makes
    it, with no dense form): the same probabilities, classes and counters
    as from the dense forest it compacts; the jnp reference needs the
    dense tables."""
    ds, x = traffic
    reps = [FeatureRep(FEATS, depth=12), FeatureRep(FEATS[2:6], depth=8)]
    dense = [irregular(501, 6, x=x)[1],
             irregular(502, 3, n_trees=3, n_features=4, x=x[:, 2:6])[1]]
    dense = [dataclasses.replace(f, classes=np.arange(K) + 10 * (t + 1))
             for t, f in enumerate(dense)]
    compact = [CompactForest.from_dense(f) for f in dense]
    solo = [build_pipeline(reps[0], f[0], 12) for f in (dense, compact)]
    multi = [build_multi_tenant_pipeline(reps, f) for f in (dense, compact)]
    for a, b in (solo, multi):
        assert np.array_equal(a.probabilities(ds), b.probabilities(ds))
        assert np.array_equal(a(ds), b(ds)) and a.counts == b.counts
    assert set(np.unique(multi[1](ds)[:, 1])) <= set(range(20, 20 + K))
    with pytest.raises(TypeError):
        build_pipeline(reps[0], compact[0], 12, use_kernel=False)
    with pytest.raises(TypeError):
        build_multi_tenant_pipeline(reps, compact, use_kernel=False)
