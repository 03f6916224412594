"""Fused single-launch pipeline: reference-parity and kernel padding contracts.

The fused Pallas kernel (`repro.kernels.fused_pipeline`) and the two-launch
kernel path must each agree with the float32 reference
(`build_pipeline(use_kernel=False)`) for every feature family, connection
depth, and batch geometry: equal predicted classes and probabilities within
1e-5 (DESIGN.md §7). They are not bitwise equal — a kernel body may round a
reduction differently from XLA, on the CPU interpreter as on the chip. Also
covers the block-padding satellite: `flow_stats_kernel_call` and
`forest_infer_kernel_call` accept arbitrary (non-block-multiple) sizes
directly, with no assert to lose under ``python -O``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import CompactForest, DenseForest
from repro.core.search_space import FeatureRep
from repro.kernels import ref
from repro.kernels.feature_extract import flow_stats_kernel_call
from repro.kernels.tree_infer import forest_infer_kernel_call
from repro.traffic import FEATURE_NAMES, extract_features, make_dataset
from repro.traffic.extraction import stats_plan
from repro.traffic.models import train_traffic_model
from repro.traffic.pipeline import build_pipeline

R = np.random.default_rng(7)

# one representative per op family the emitter knows: durations, metadata,
# loads, counts, handshake timings, flag counters, and every stat over
# bytes/iat/winsize/ttl including the sorting (median) and two-pass (std)
FEATURE_SUBSETS = [
    ("dur", "proto", "s_port", "d_port"),
    ("s_load", "d_load", "s_pkt_cnt", "d_pkt_cnt"),
    ("tcp_rtt", "syn_ack", "ack_dat", "syn_cnt", "ack_cnt", "fin_cnt"),
    ("s_bytes_sum", "s_bytes_mean", "s_bytes_min", "s_bytes_max",
     "s_bytes_med", "s_bytes_std"),
    ("d_iat_mean", "d_iat_std", "d_iat_med", "s_iat_min", "s_iat_max"),
    ("s_winsize_mean", "d_winsize_std", "s_ttl_min", "d_ttl_max",
     "d_winsize_med"),
]


@pytest.fixture(scope="module")
def ds():
    # 257 flows: exercises flow-axis padding in every launch geometry
    return make_dataset("app-class", n_flows=257, max_pkts=16, seed=11)


def _forest(ds, rep, model="rf-fast"):
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model=model, seed=0)
    return forest


def _assert_matches_ref(ref_pipe, pipes, ds):
    """Each pipeline predicts the reference's class for every flow, with
    probabilities within 1e-5 of the float32 reference."""
    p_ref = ref_pipe.probabilities(ds)
    y_ref = ref_pipe(ds)
    for pipe in pipes:
        np.testing.assert_allclose(pipe.probabilities(ds), p_ref, atol=1e-5)
        assert np.array_equal(pipe(ds), y_ref)


@pytest.mark.parametrize("features", FEATURE_SUBSETS)
@pytest.mark.parametrize("depth", [4, 12])
def test_fused_bit_identical_to_unfused(ds, features, depth):
    """Fused and unfused kernel paths both hold the reference contract."""
    rep = FeatureRep(features, depth=depth)
    forest = _forest(ds, rep)
    _assert_matches_ref(
        build_pipeline(rep, forest, ds.max_pkts, use_kernel=False),
        [build_pipeline(rep, forest, ds.max_pkts, use_kernel=True),
         build_pipeline(rep, forest, ds.max_pkts, fused=True)], ds)


def test_fused_parity_full_feature_set(ds):
    """All 67 registry features through the fused kernel at once."""
    rep = FeatureRep(tuple(FEATURE_NAMES), depth=10)
    forest = _forest(ds, rep, model="tree-fast")
    unfused = build_pipeline(rep, forest, ds.max_pkts, use_kernel=True)
    fused = build_pipeline(rep, forest, ds.max_pkts, fused=True)
    assert np.array_equal(unfused.probabilities(ds), fused.probabilities(ds))


@pytest.mark.parametrize("n", [1, 5, 8, 37, 130])
def test_fused_arbitrary_batch_sizes(ds, n):
    """Bucket-shaped and ragged batch sizes all hold the reference contract."""
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "d_iat_std"), depth=8)
    forest = _forest(ds, rep)
    _assert_matches_ref(
        build_pipeline(rep, forest, ds.max_pkts, use_kernel=False),
        [build_pipeline(rep, forest, ds.max_pkts, use_kernel=True),
         build_pipeline(rep, forest, ds.max_pkts, fused=True)],
        ds.take(np.arange(n)))


def test_fused_predictions_match_ref_path(ds):
    """Vote accumulation order differs from the jnp reference by ulps at
    most — class predictions must still agree."""
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "ack_cnt"), depth=8)
    forest = _forest(ds, rep)
    _assert_matches_ref(
        build_pipeline(rep, forest, ds.max_pkts, use_kernel=False),
        [build_pipeline(rep, forest, ds.max_pkts, fused=True)], ds)


def test_stats_plan_static_and_total():
    """The plan is hashable (a jit static arg), order-preserving, and
    rejects unknown features."""
    plan = stats_plan(("dur", "s_bytes_med", "ack_cnt", "d_load"))
    assert isinstance(hash(plan), int)
    assert plan[0] == ("dur",) and plan[3] == ("load", "d")
    assert len(stats_plan(FEATURE_NAMES)) == 67
    with pytest.raises(ValueError):
        stats_plan(("nope_bytes_gm",))


# ---------------------------------------------------------------------------
# kernel-call padding (satellite): direct calls, no ops.py pre-padding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,P,bn", [(73, 17, 32), (5, 8, 512), (256, 12, 64)])
def test_flow_stats_kernel_pads_flow_axis(n, P, bn):
    v = jnp.asarray(R.standard_normal((n, P)), jnp.float32)
    m = jnp.asarray(R.random((n, P)) < 0.4)
    got = flow_stats_kernel_call(v, m, block_n=bn, interpret=True)
    assert got.shape == (n, 5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.flow_stats_ref(v, m)), atol=1e-5)


@pytest.mark.parametrize("n,T,bn,bt", [(77, 5, 32, 4), (130, 3, 128, 8),
                                       (9, 12, 256, 5)])
def test_forest_kernel_pads_both_axes(n, T, bn, bt):
    depth, F, K = 4, 6, 3
    feature = jnp.asarray(R.integers(0, F, (T, 2 ** depth - 1)), jnp.int32)
    threshold = jnp.asarray(R.standard_normal((T, 2 ** depth - 1)), jnp.float32)
    leaf = jnp.asarray(R.random((T, 2 ** depth, K)), jnp.float32)
    x = jnp.asarray(R.standard_normal((n, F)), jnp.float32)
    cf = CompactForest.from_dense(DenseForest(
        np.asarray(feature), np.asarray(threshold), np.asarray(leaf), depth, F))
    got = forest_infer_kernel_call(
        x, jnp.asarray(cf.nodes), jnp.asarray(cf.leaf), cf.widths,
        block_n=bn, block_t=bt, interpret=True)
    assert got.shape == (n, K)
    want = ref.forest_infer_ref(x, feature, threshold, leaf, depth)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
