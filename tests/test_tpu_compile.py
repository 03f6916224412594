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

from repro.core.forest import DenseForest
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
}


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


def _forest(shape, trees, depth, classes):
    return [shape((trees, 2 ** depth - 1), jnp.int32),
            shape((trees, 2 ** depth - 1)),
            shape((trees, 2 ** depth, classes))]


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_fused_forest_infer_compiles(compile_for, width):
    trees, depth, classes = WIDTHS[width]
    _assert_mosaic(fused_forest_infer.lower(
        *_packets(compile_for), *_forest(compile_for, trees, depth, classes),
        plan=stats_plan(EVERY_FAMILY), depth=P, forest_depth=depth,
        interpret=False).compile())


def test_fused_kernel_keeps_its_name_under_another_wrapper(compile_for):
    """A device trace names the kernel by its instruction; the benchmark's
    readers match ``%fused_forest_infer.N``. Renaming the jit wrapper must
    not move it."""
    trees, depth, classes = WIDTHS["app"]

    @functools.partial(jax.jit, static_argnames=("plan", "depth",
                                                 "forest_depth", "interpret"))
    def renamed_entry(*a, **k):
        return fused_forest_infer.__wrapped__(*a, **k)

    text = renamed_entry.lower(
        *_packets(compile_for), *_forest(compile_for, trees, depth, classes),
        plan=stats_plan(EVERY_FAMILY[:6]), depth=P, forest_depth=depth,
        interpret=False).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert re.match(r"\s*(ROOT )?%fused_forest_infer\.\d+ = ", calls[0])


def test_fused_agg_infer_compiles(compile_for):
    trees, depth, classes = WIDTHS["iot"]
    plan = stats_plan(tuple(f for f in EVERY_FAMILY if not f.endswith("_med")))
    _assert_mosaic(fused_agg_infer.lower(
        compile_for((N, AGG_WIDTH)), compile_for((N,)), compile_for((N,)),
        compile_for((N,)), *_forest(compile_for, trees, depth, classes),
        plan=plan, forest_depth=depth, interpret=False).compile())


def test_fused_multi_forest_infer_compiles(compile_for):
    # three tenants at three connection depths, two of them at the iot
    # width: 51 resident trees, which must fit the kernel's VMEM
    tenants = [(EVERY_FAMILY[:7], P, WIDTHS["iot"]),
               (EVERY_FAMILY[7:14], P // 2, WIDTHS["app"]),
               (EVERY_FAMILY[18:25], 12, WIDTHS["iot"])]
    merged, cols = merge_stats_plans(
        [stats_plan(f) for f, _, _ in tenants], [d for _, d, _ in tenants])
    rng = np.random.default_rng(0)
    forests = [DenseForest(
        feature=rng.integers(0, len(f), (t, 2 ** d - 1)).astype(np.int32),
        threshold=rng.random((t, 2 ** d - 1)).astype(np.float32),
        leaf=rng.random((t, 2 ** d, k)).astype(np.float32),
        depth=d, n_features=len(f)) for f, _, (t, d, k) in tenants]
    feature, threshold, leaf, spec = stack_multi_forests(forests, cols)
    _assert_mosaic(fused_multi_forest_infer.lower(
        *_packets(compile_for), compile_for(feature.shape, jnp.int32),
        compile_for(threshold.shape), compile_for(leaf.shape),
        merged=merged, tenants=spec, interpret=False).compile())


def test_ops_forest_infer_compiles(compile_for):
    trees, depth, classes = WIDTHS["iot"]
    _assert_mosaic(ops.forest_infer.lower(
        compile_for((N, 12)), *_forest(compile_for, trees, depth, classes),
        depth, interpret=False).compile())
