"""The served kernels compile for a TPU v5e — no chip needed.

Each case lowers a served entry point at a width the system serves for a
*described* v5e (`jax.experimental.topologies`) and compiles it with the
chip's compiler, so a refusal that interpret mode cannot show (a gather
Mosaic cannot lower, an unaligned slice, more VMEM than a kernel may use)
fails here rather than on the chip. The compiled program must hold a
Mosaic kernel (``tpu_custom_call``). A compile is not a run: nothing here
says anything about results or time.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, so only the worker that runs
this file loads it.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.forest import CompactForest, DenseForest
from repro.kernels import ops
from repro.kernels.fused_pipeline import (
    fused_agg_infer,
    fused_forest_infer,
    fused_multi_forest_infer,
    stack_multi_forests,
)
from repro.traffic.extraction import AGG_WIDTH, merge_stats_plans, stats_plan

# one entry per emitter family: duration, metadata, loads, counts,
# handshake timings, flag counters, and every statistic over bytes, iat,
# window size and ttl, the median included
EVERY_FAMILY = (
    "dur", "proto", "s_port", "d_port", "s_load", "d_load", "s_pkt_cnt",
    "d_pkt_cnt", "tcp_rtt", "syn_ack", "ack_dat", "syn_cnt", "ack_cnt",
    "fin_cnt", "s_bytes_sum", "s_bytes_mean", "s_bytes_min", "s_bytes_max",
    "s_bytes_med", "s_bytes_std", "d_iat_mean", "d_iat_std", "d_iat_med",
    "s_iat_min", "s_iat_max", "s_winsize_mean", "d_ttl_max",
)
N, P = 256, 16                  # the largest dispatch bucket; packet depth
WIDTHS = {                      # (trees, depth, classes)
    "app": (1, 10, 7),          # MODEL_GRIDS["tree"], app-class
    "iot": (25, 10, 28),        # MODEL_GRIDS["rf"], iot-class
    "iot100": (100, 20, 28),    # the paper's iot-class forest, grown
}
# features of the grown iot-class forest (the chip benchmark's iot cells)
IOT_FEATURES = ("ack_cnt", "d_bytes_med", "d_iat_std", "d_pkt_cnt", "d_port",
                "d_winsize_min", "dur", "proto", "psh_cnt", "s_bytes_mean",
                "s_iat_mean", "s_load", "s_ttl_max", "syn_ack", "tcp_rtt")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip):
    """Shape factory on the described chip, with the persistent cache off
    (an entry written for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def shape(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    yield shape
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _packets(shape):
    return [shape((N, P)), shape((N, P)), shape((N, P), jnp.uint8),
            shape((N, P)), shape((N, P)), shape((N, P, 8), jnp.uint8),
            shape((N,), jnp.int32), shape((N,)), shape((N,)), shape((N,))]


@pytest.fixture(scope="module")
def grown():
    """The compact forest of a grown 100-tree, depth-20 iot-class forest:
    scikit-learn's CART on 8,000 of the program's synthetic iot-class
    flows, as the chip benchmark grows it."""
    from sklearn.ensemble import RandomForestClassifier

    from repro.traffic import extract_features, make_dataset

    ds = make_dataset("iot-class", n_flows=8000, max_pkts=20, seed=7)
    x = np.asarray(extract_features(ds, IOT_FEATURES, 20), np.float32)
    est = RandomForestClassifier(n_estimators=100, max_depth=20, random_state=7,
                                 n_jobs=4).fit(x, ds.label)
    return CompactForest.from_cart([e.tree_ for e in est.estimators_], 28,
                                   columns=est.classes_)


def _layout(width, request):
    """(nodes shape, leaf shape, level widths) served at a width: the
    grown forest's for the paper's forest, else a perfect tree's, the
    largest compact layout of its depth."""
    trees, depth, classes = WIDTHS[width]
    if width == "iot100":
        f = request.getfixturevalue("grown")
        return f.nodes.shape, f.leaf.shape, f.widths
    widths = tuple(max(128, -(-2 ** d // 128) * 128) for d in range(depth))
    return ((trees, 2, sum(widths)), (trees, classes, max(128, 2 ** depth)),
            widths)


def _forest(shape, width, request):
    nodes, leaf, widths = _layout(width, request)
    return [shape(nodes), shape(leaf)], widths


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_fused_forest_infer_compiles(compile_for, width, request):
    forest, widths = _forest(compile_for, width, request)
    _assert_mosaic(fused_forest_infer.lower(
        *_packets(compile_for), *forest,
        plan=stats_plan(EVERY_FAMILY), depth=P, widths=widths,
        interpret=False).compile())


def test_fused_kernel_keeps_its_name_under_another_wrapper(compile_for, request):
    """A device trace names the kernel by its instruction; the benchmark's
    readers match ``%fused_forest_infer.N``. Renaming the jit wrapper must
    not move it."""
    forest, widths = _forest(compile_for, "app", request)

    @functools.partial(jax.jit, static_argnames=("plan", "depth", "widths",
                                                 "interpret"))
    def renamed_entry(*a, **k):
        return fused_forest_infer.__wrapped__(*a, **k)

    text = renamed_entry.lower(
        *_packets(compile_for), *forest,
        plan=stats_plan(EVERY_FAMILY[:6]), depth=P, widths=widths,
        interpret=False).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert re.match(r"\s*(ROOT )?%fused_forest_infer\.\d+ = ", calls[0])


def _agg_compiles(compile_for, width, request):
    forest, widths = _forest(compile_for, width, request)
    plan = stats_plan(tuple(f for f in EVERY_FAMILY if not f.endswith("_med")))
    _assert_mosaic(fused_agg_infer.lower(
        compile_for((N, AGG_WIDTH)), compile_for((N,)), compile_for((N,)),
        compile_for((N,)), *forest,
        plan=plan, widths=widths, interpret=False).compile())


def test_fused_agg_infer_compiles(compile_for, request):
    _agg_compiles(compile_for, "iot", request)


def test_fused_agg_infer_compiles_with_the_paper_forest(compile_for, request):
    _agg_compiles(compile_for, "iot100", request)


def _random_forest(rng, feats, width):
    """A perfect random tree per tree at a width's depth, compacted."""
    t, d, k = WIDTHS[width]
    return CompactForest.from_dense(DenseForest(
        feature=rng.integers(0, len(feats), (t, 2 ** d - 1)).astype(np.int32),
        threshold=rng.random((t, 2 ** d - 1)).astype(np.float32),
        leaf=rng.random((t, 2 ** d, k)).astype(np.float32),
        depth=d, n_features=len(feats)))


def _multi_compiles(compile_for, tenants, forests):
    merged, cols = merge_stats_plans(
        [stats_plan(f) for f, _ in tenants], [d for _, d in tenants])
    nodes, leaf, spec = stack_multi_forests(forests, cols)
    _assert_mosaic(fused_multi_forest_infer.lower(
        *_packets(compile_for), compile_for(nodes.shape), compile_for(leaf.shape),
        merged=merged, tenants=spec, interpret=False).compile())


def test_fused_multi_forest_infer_compiles(compile_for):
    # three tenants at three connection depths, two of them at the iot
    # width: 51 resident trees, which must fit the kernel's VMEM
    tenants = [(EVERY_FAMILY[:7], P, "iot"), (EVERY_FAMILY[7:14], P // 2, "app"),
               (EVERY_FAMILY[18:25], 12, "iot")]
    rng = np.random.default_rng(0)
    _multi_compiles(compile_for, [(f, d) for f, d, _ in tenants],
                    [_random_forest(rng, f, w) for f, _, w in tenants])


def test_fused_multi_forest_infer_compiles_with_the_paper_forest(compile_for, grown):
    # the paper's iot forest beside an app tree: 101 resident trees
    tenants = [(IOT_FEATURES, P), (EVERY_FAMILY[7:14], P // 2)]
    forests = [grown, _random_forest(np.random.default_rng(1), tenants[1][0], "app")]
    _multi_compiles(compile_for, tenants, forests)


def test_ops_forest_infer_compiles(compile_for, request):
    forest, widths = _forest(compile_for, "iot", request)
    _assert_mosaic(ops.forest_infer.lower(
        compile_for((N, 12)), *forest, widths, interpret=False).compile())
