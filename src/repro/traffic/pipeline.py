"""The generated end-to-end serving pipeline (paper §3.4, Pipeline Generation).

`build_pipeline` takes a Pareto-optimal feature representation selected by
the Optimizer plus its trained model and returns a single compiled callable

    packets (dense flow tensors) -> class predictions

containing exactly the extraction ops for (F, n) (jit specialization ==
conditional compilation, DESIGN.md §3) fused with the forest inference
stage. The kernels serve the forest by its real nodes: `build_pipeline`
takes a `CompactForest` as it is, or compacts a trained `DenseForest` on the
host (`CompactForest.from_dense`) before anything reaches the device; only
the jnp reference (``use_kernel=False``) reads a `DenseForest`'s tables,
on the device. Two fusion levels exist:

- ``fused=False`` (two launches): the jit-specialized XLA extraction
  executable materializes the ``(N, F)`` feature matrix, then the
  `tree_infer` Pallas kernel (``use_kernel=True``) or the jnp reference
  consumes it.
- ``fused=True`` (one launch): the `fused_pipeline` Pallas kernel computes
  the feature columns from the static stats plan *inside* the flow tile and
  runs the forest traversal on the in-register features — no HBM
  materialization, donated input buffers (DESIGN.md §7). Both paths trace
  the same column emitter and the same traversal (`forest_votes`), so they
  agree with the float32 reference (``use_kernel=False``) on every
  predicted class and to 1e-5 in probability; they are not bitwise equal,
  since a kernel body may round a reduction differently from XLA.

This is the deployable artifact — `examples/deploy_pipeline.py` drives it.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import CompactForest, DenseForest
from repro.core.search_space import FeatureRep
from repro.kernels import ops

from .extraction import emit_agg_features, extraction_fn, stats_plan
from .synth import TrafficDataset

__all__ = ["ServingPipeline", "build_pipeline", "served_forest", "forest_counts"]


def served_forest(forest: DenseForest | CompactForest, tracer=None) -> CompactForest:
    """The layout the kernels read: a `CompactForest` as it is, a
    `DenseForest` compacted (`CompactForest.from_dense`), timed as a
    ``forest.compact`` layer span (items: trees) while `tracer` (an
    `obs.Tracer`) is enabled."""
    if isinstance(forest, CompactForest):
        return forest
    if tracer is not None and tracer.enabled:
        with tracer.layer("forest.compact", forest.n_trees):
            return CompactForest.from_dense(forest)
    return CompactForest.from_dense(forest)


def forest_counts(forests) -> dict:
    """Counters a traced submit adds per batch: ``forest.nodes``, the
    served forests' real internal nodes, and ``forest.lanes``, the node
    slots the traversal's one-hots read for one flow over every tree and
    level."""
    return {"forest.nodes": sum(f.n_internal for f in forests),
            "forest.lanes": sum(f.lanes for f in forests)}


@functools.partial(jax.jit, static_argnames=("plan",))
def _agg_extract(agg, proto, s_port, d_port, *, plan):
    """Feature matrix from incremental aggregate rows (DESIGN.md §12):
    the same static-plan column emitter the window path traces, evaluated
    over per-flow running statistics instead of the raw packet window."""
    cols = emit_agg_features(plan, agg, proto=proto, s_port=s_port,
                             d_port=d_port)
    return jnp.stack(cols, axis=1)


@dataclasses.dataclass
class ServingPipeline:
    rep: FeatureRep
    forest: DenseForest | CompactForest     # as `build_pipeline` was given it
    _fn: Callable
    fused: bool = False
    _agg_fn: Optional[Callable] = None
    # what a traced submit counts per batch (`forest_counts`); empty on the
    # jnp reference path, which reads the dense tables
    counts: dict = dataclasses.field(default_factory=dict)

    def __call__(self, ds: TrafficDataset) -> np.ndarray:
        """Predicted class ids for every flow in the batch."""
        return self.finalize(self.predict_async(ds))

    @property
    def supports_agg(self) -> bool:
        """True when this pipeline has an incremental (aggregate-block)
        inference entry — i.e. every feature in the plan is maintainable
        as a running statistic (no median-style order stats)."""
        return self._agg_fn is not None

    def predict_agg(self, agg, proto, s_port, d_port) -> jax.Array:
        """Infer from per-flow incremental aggregate rows (n, AGG_WIDTH)
        instead of the raw packet window; resolves via `finalize` like any
        other submission. Bit-identical column semantics to the window
        path for whole-flow windows (both trace the shared stats plan)."""
        if self._agg_fn is None:
            raise ValueError(
                "pipeline has no incremental entry (plan not incremental)")
        return self._agg_fn(agg, proto, s_port, d_port)

    def predict_async(self, ds: TrafficDataset) -> jax.Array:
        """Submit the batch and return the (unresolved) device array.

        JAX dispatch is asynchronous: the caller can keep accumulating the
        next micro-batch while this one runs, and only block in `finalize`.
        The streaming runtime's double-buffered dispatch relies on this.

        Buffer lifetime: the XLA CPU client may alias host numpy buffers
        zero-copy instead of copying at submit, so the caller must NOT
        overwrite `ds`'s arrays until this batch has been finalized — the
        dispatcher guarantees it by rotating `max_pending + 1` staging
        arenas per bucket (DESIGN.md §7.3).
        """
        return self._fn(ds)

    def finalize(self, probs: jax.Array) -> np.ndarray:
        """Block on a `predict_async` result and map to class labels."""
        idx = np.asarray(jnp.argmax(probs, axis=1))
        if self.forest.classes is not None:
            return self.forest.classes[idx]
        return idx

    def probabilities(self, ds: TrafficDataset) -> np.ndarray:
        return np.asarray(self._fn(ds))

    def warm(self, buckets: "list[int]") -> None:
        """Pre-compile this pipeline's executables for the given dispatch
        shape buckets (swap-safe handle, DESIGN.md §9.3).

        A pipeline hot-swap must never pay an XLA compile on the serving
        path: the control plane compiles the replacement in the
        background by warming every batch geometry the dispatcher can
        submit (`min_bucket..max_batch` powers of two). Each call runs a
        zero-filled batch through the real jit entry, so the executable
        cache — keyed on (feature plan, depth, batch shape), disjoint
        per configuration — holds every shape before the swap flips the
        handle. Safe to run while the old pipeline serves: caches are
        keyed by static config, so coexisting pipelines never evict or
        alias each other, and the dummy buffers are donated like any
        other batch."""
        P = int(self.rep.depth)
        for b in buckets:
            ds = TrafficDataset(
                ts=np.zeros((b, P), np.float32),
                size=np.zeros((b, P), np.float32),
                direction=np.zeros((b, P), np.uint8),
                ttl=np.zeros((b, P), np.float32),
                winsize=np.zeros((b, P), np.float32),
                flags=np.zeros((b, P, 8), np.uint8),
                flow_len=np.zeros(b, np.int32),
                proto=np.zeros(b, np.float32),
                s_port=np.zeros(b, np.float32),
                d_port=np.zeros(b, np.float32),
                label=np.zeros(b, np.int32),
                name="warm",
            )
            self.finalize(self.predict_async(ds))


def build_pipeline(
    rep: FeatureRep,
    forest: DenseForest | CompactForest,
    max_pkts: int,
    *,
    use_kernel: bool = True,
    fused: bool = False,
    tracer=None,
) -> ServingPipeline:
    """The serving pipeline of `rep` and its trained `forest`, a
    `CompactForest` or a `DenseForest` (`served_forest`; the jnp reference
    needs the dense one); `tracer` (an enabled `obs.Tracer`, e.g. during a
    hot swap) times a dense forest's compaction."""
    from .extraction import plan_is_incremental

    plan = stats_plan(rep.features)
    incremental = plan_is_incremental(plan)

    counts = {}
    if fused or use_kernel:
        cf = served_forest(forest, tracer)
        nodes_t, leaf_t, widths = (jnp.asarray(cf.nodes), jnp.asarray(cf.leaf),
                                   cf.widths)
        counts = forest_counts([cf])

        def infer(x):
            return ops.forest_infer(x, nodes_t, leaf_t, widths)
    else:
        from repro.kernels import ref

        if not isinstance(forest, DenseForest):
            raise TypeError("the jnp reference (use_kernel=False) reads a "
                            "DenseForest's tables")
        dense = [jnp.asarray(a)
                 for a in (forest.feature, forest.threshold, forest.leaf)]

        def infer(x):
            return ref.forest_infer_ref(x, *dense, forest.depth)

    if fused:
        from repro.kernels.fused_pipeline import (
            fused_agg_infer,
            fused_forest_infer,
        )

        conn_depth = int(rep.depth)

        def run(ds: TrafficDataset):
            with warnings.catch_warnings():
                # donation cannot engage on the CPU backend (no aliasable
                # output buffer) and XLA warns once per compile — expected;
                # scoped here so other code's donation warnings survive
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return fused_forest_infer(
                    ds.ts, ds.size, ds.direction, ds.ttl, ds.winsize,
                    ds.flags, ds.flow_len, ds.proto, ds.s_port, ds.d_port,
                    nodes_t, leaf_t,
                    plan=plan, depth=conn_depth, widths=widths,
                )

        run_agg = None
        if incremental:
            def run_agg(agg, proto, s_port, d_port):
                return fused_agg_infer(
                    jnp.asarray(agg), jnp.asarray(proto),
                    jnp.asarray(s_port), jnp.asarray(d_port),
                    nodes_t, leaf_t,
                    plan=plan, widths=widths,
                )

        return ServingPipeline(rep, forest, run, fused=True, _agg_fn=run_agg,
                               counts=counts)

    extract = extraction_fn(rep.features, rep.depth, max_pkts)

    def run(ds: TrafficDataset):
        return infer(extract(ds))

    run_agg = None
    if incremental:
        def run_agg(agg, proto, s_port, d_port):
            return infer(_agg_extract(
                jnp.asarray(agg), jnp.asarray(proto), jnp.asarray(s_port),
                jnp.asarray(d_port), plan=plan))

    return ServingPipeline(rep, forest, run, _agg_fn=run_agg, counts=counts)
