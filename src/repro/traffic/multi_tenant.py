"""Multi-tenant white-box serving: N models, one fleet (DESIGN.md §15).

A real vantage point runs many analyses over the same packets — app class,
QoS, anomaly, per-customer models. Served black-box, that is N fleets with
N flow tables and N redundant extraction passes. PRETZEL's white-box
argument breaks the model boundary instead: tenants share operators and
state. Here the sharing is structural:

- **Merged extraction plan** (`merge_stats_plans`): the union of every
  tenant's `stats_plan`, deduped on (op, depth), extracted ONCE per flow
  over one `FlowTable` at the union connection depth; each tenant reads
  its column subset through a static index map.
- **One inference pass**: fused mode launches the single multi-forest
  Pallas kernel (`fused_multi_forest_infer` — tenant-stacked forests over
  the shared in-VMEM feature tile); unfused mode gathers each tenant's
  columns from the merged matrix and runs the solo forest kernel per
  tenant. Tenant by tenant, the unfused path is bit-identical to running
  each pipeline alone, and the fused launch matches it to float32
  rounding with equal predictions.
- **Co-optimization**: `MultiTenantRep`/`MultiTenantSpace`/
  `MultiTenantProfiler` expose the joint configuration space to
  `CatoOptimizer` with the union-plan cost (shared ops counted once) —
  the overlap discount that reshapes which configurations are
  Pareto-optimal (CATO's thesis applied to the sharing itself).

`MultiTenantPipeline` is duck-compatible with `ServingPipeline` (its
`rep` is a genuine union `FeatureRep`), so flow tables, dispatch, reuse
gating, hot-swap, sharding, and replay serve it unchanged; `finalize`
returns an ``(n, T)`` per-tenant class matrix and `results[fid]` holds a
length-T vector.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import CompactForest, DenseForest
from repro.core.search_space import FeatureRep, SearchSpace

from .extraction import (
    emit_merged_agg_features,
    emit_merged_columns,
    merge_stats_plans,
    merged_plan_is_incremental,
    pack_flags,
    stats_plan,
)
from .features import modeled_extraction_cost_ns
from .pipeline import forest_counts, served_forest
from .profiler import ProfileResult, TrafficProfiler
from .synth import TrafficDataset

__all__ = [
    "MultiTenantPipeline",
    "MultiTenantProfiler",
    "MultiTenantRep",
    "MultiTenantSpace",
    "build_multi_tenant_pipeline",
    "union_rep",
]


def union_rep(reps: Sequence[FeatureRep]) -> FeatureRep:
    """The shared-state representation: union features at max depth.

    This is what the fleet's `FlowTable` is sized by — one table holds
    every packet column any tenant needs, to the deepest prefix any
    tenant reads. A genuine `FeatureRep`, so every `pipeline.rep`
    consumer (table sizing, reuse gating, anchors, hot-swap) works
    unchanged."""
    feats: set[str] = set()
    for r in reps:
        feats.update(r.features)
    return FeatureRep(tuple(sorted(feats)), max(int(r.depth) for r in reps))


@functools.partial(jax.jit, static_argnames=("merged",))
def _merged_extract(
    ts, size, direction, ttl, winsize, flags, flow_len, proto, s_port, d_port,
    *, merged,
):
    cols = emit_merged_columns(
        merged,
        ts=ts, size=size, direction=direction, ttl=ttl, winsize=winsize,
        flags=pack_flags(flags), flow_len=flow_len, proto=proto, s_port=s_port,
        d_port=d_port,
    )
    return jnp.stack(cols, axis=1)


@functools.partial(jax.jit, static_argnames=("merged",))
def _merged_agg_extract(agg, proto, s_port, d_port, *, merged):
    cols = emit_merged_agg_features(
        merged, agg, proto=proto, s_port=s_port, d_port=d_port)
    return jnp.stack(cols, axis=1)


@dataclasses.dataclass
class MultiTenantPipeline:
    """N tenants' pipelines fused behind one `ServingPipeline` interface.

    `predict_async` returns stacked per-tenant probability lanes
    ``(n, sum K_t)``; `finalize` maps them to an ``(n, T)`` class matrix
    (column t bit-identical to tenant t's solo `finalize`). `lanes[t]`
    is tenant t's ``(lo, hi)`` probability slice — the observability
    layer uses it for per-tenant attribution."""

    rep: FeatureRep                         # union features @ max depth
    tenant_reps: tuple[FeatureRep, ...]
    forests: tuple[DenseForest | CompactForest, ...]   # as given
    merged: tuple                           # merged plan: ((entry, depth), ...)
    tenant_cols: tuple[tuple[int, ...], ...]
    lanes: tuple[tuple[int, int], ...]      # per-tenant prob column spans
    _fn: Callable
    fused: bool = False
    _agg_fn: Optional[Callable] = None
    # per-batch counters of a traced submit, as `ServingPipeline.counts`
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def n_tenants(self) -> int:
        return len(self.tenant_reps)

    @property
    def drift_prob_slice(self) -> slice:
        """Tenant 0's probability lane: the slice the drift monitor's
        confidence signal is computed over (per-tenant class id spaces
        must not mix in one histogram — DESIGN.md §15.4)."""
        lo, hi = self.lanes[0]
        return slice(lo, hi)

    def __call__(self, ds: TrafficDataset) -> np.ndarray:
        return self.finalize(self.predict_async(ds))

    @property
    def supports_agg(self) -> bool:
        return self._agg_fn is not None

    def predict_agg(self, agg, proto, s_port, d_port) -> jax.Array:
        if self._agg_fn is None:
            raise ValueError(
                "pipeline has no incremental entry (plan not incremental)")
        return self._agg_fn(agg, proto, s_port, d_port)

    def predict_async(self, ds: TrafficDataset) -> jax.Array:
        return self._fn(ds)

    def probabilities(self, ds: TrafficDataset) -> np.ndarray:
        return np.asarray(self._fn(ds))

    def finalize(self, probs) -> np.ndarray:
        """Block on a `predict_async` result; (n, T) class matrix.

        Per tenant: argmax over its own lane slice, mapped through its
        own class table — the exact solo `finalize`, so column t of the
        result is bitwise the solo prediction vector."""
        p = np.asarray(probs)
        cols = []
        for (lo, hi), f in zip(self.lanes, self.forests):
            idx = np.argmax(p[:, lo:hi], axis=1)
            cols.append(f.classes[idx] if f.classes is not None else idx)
        return np.stack(cols, axis=1)

    def warm(self, buckets: "list[int]") -> None:
        """Pre-compile every dispatch bucket geometry (DESIGN.md §9.3) —
        same zero-batch protocol as `ServingPipeline.warm`, at the union
        connection depth the shared table stages."""
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


def build_multi_tenant_pipeline(
    reps: Sequence[FeatureRep],
    forests: Sequence[DenseForest | CompactForest],
    *,
    use_kernel: bool = True,
    fused: bool = False,
) -> MultiTenantPipeline:
    """Compile N tenants' (rep, forest) pairs into one shared pipeline.

    ``fused=True`` launches the single multi-forest Pallas kernel (one
    launch: merged columns in VMEM, tenant-stacked traversal); unfused
    gathers per-tenant column subsets from the merged feature matrix and
    runs the solo forest kernel (`use_kernel=True`) or the jnp reference
    per tenant. The incremental (aggregate) entry always takes the
    unfused route — refresh batches are low-rate (DESIGN.md §12). The
    kernels serve each forest's real nodes (`served_forest`: a
    `CompactForest` as it is, a `DenseForest` compacted on the host); only
    the jnp reference reads the dense tables, and needs `DenseForest`s."""
    reps = tuple(reps)
    forests = tuple(forests)
    if len(reps) != len(forests) or not reps:
        raise ValueError("need one forest per tenant rep (and >= 1 tenant)")
    plans = [stats_plan(r.features) for r in reps]
    merged, tenant_cols = merge_stats_plans(plans, [r.depth for r in reps])
    urep = union_rep(reps)
    lanes, k0 = [], 0
    for f in forests:
        k = int(f.n_out)
        lanes.append((k0, k0 + k))
        k0 += k

    incremental = merged_plan_is_incremental(merged)
    col_idx = [np.asarray(c, np.int32) for c in tenant_cols]
    counts = {}
    if fused or use_kernel:
        from repro.kernels import ops

        compact = [served_forest(f) for f in forests]
        counts = forest_counts(compact)
        consts = [(jnp.asarray(c.nodes), jnp.asarray(c.leaf), c.widths)
                  for c in compact]

        def infer_one(x, c):
            return ops.forest_infer(x, *c)
    else:
        from repro.kernels import ref

        if not all(isinstance(f, DenseForest) for f in forests):
            raise TypeError("the jnp reference (use_kernel=False) reads "
                            "DenseForests' tables")
        consts = [(jnp.asarray(f.feature), jnp.asarray(f.threshold),
                   jnp.asarray(f.leaf), int(f.depth)) for f in forests]

        def infer_one(x, c):
            return ref.forest_infer_ref(x, *c)

    def infer_tenants(X):
        return jnp.concatenate([infer_one(X[:, idx], c)
                                for idx, c in zip(col_idx, consts)], axis=1)

    if fused:
        from repro.kernels.fused_pipeline import (
            fused_multi_forest_infer,
            stack_multi_forests,
        )

        nodes_all, leaf_all, tenants_spec = stack_multi_forests(
            compact, tenant_cols)
        nodes_all, leaf_all = jnp.asarray(nodes_all), jnp.asarray(leaf_all)

        def run(ds: TrafficDataset):
            with warnings.catch_warnings():
                # donation cannot engage on the CPU backend — same scoped
                # suppression as the solo fused path
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return fused_multi_forest_infer(
                    ds.ts, ds.size, ds.direction, ds.ttl, ds.winsize,
                    ds.flags, ds.flow_len, ds.proto, ds.s_port, ds.d_port,
                    nodes_all, leaf_all,
                    merged=merged, tenants=tenants_spec,
                )
    else:
        def run(ds: TrafficDataset):
            X = _merged_extract(
                ds.ts, ds.size, ds.direction, ds.ttl, ds.winsize, ds.flags,
                ds.flow_len, ds.proto, ds.s_port, ds.d_port, merged=merged)
            return infer_tenants(X)

    run_agg = None
    if incremental:
        def run_agg(agg, proto, s_port, d_port):
            X = _merged_agg_extract(
                jnp.asarray(agg), jnp.asarray(proto), jnp.asarray(s_port),
                jnp.asarray(d_port), merged=merged)
            return infer_tenants(X)

    return MultiTenantPipeline(
        rep=urep, tenant_reps=reps, forests=forests, merged=merged,
        tenant_cols=tenant_cols, lanes=tuple(lanes), _fn=run, fused=fused,
        _agg_fn=run_agg, counts=counts,
    )


# ---------------------------------------------------------------------------
# joint configuration space (DESIGN.md §15.5)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MultiTenantRep:
    """Joint config point: one `FeatureRep` per tenant.

    `features`/`depth` present the union view (what the shared table
    costs are a function of), `key()` the per-tenant identity the
    memoized evaluator caches on."""

    reps: tuple[FeatureRep, ...]

    def __post_init__(self):
        object.__setattr__(self, "reps", tuple(self.reps))

    def key(self) -> tuple:
        return tuple(r.key() for r in self.reps)

    @property
    def features(self) -> tuple[str, ...]:
        return union_rep(self.reps).features

    @property
    def depth(self) -> int:
        return max(int(r.depth) for r in self.reps)


@dataclasses.dataclass
class MultiTenantSpace:
    """Product of per-tenant search spaces, optimizer-protocol compatible
    (encode / sample_uniform / mutate — `CatoOptimizer` needs nothing
    else). Encoding is the concatenation of per-tenant encodings, so the
    surrogate sees the joint space; mutation perturbs one tenant at a
    time (the neighborhood a shared-fleet operator actually explores)."""

    spaces: tuple[SearchSpace, ...]

    def __post_init__(self):
        self.spaces = tuple(self.spaces)

    @property
    def dim(self) -> int:
        return sum(s.dim for s in self.spaces)

    @property
    def size(self) -> float:
        out = 1.0
        for s in self.spaces:
            out *= s.size
        return out

    def encode(self, x: MultiTenantRep) -> np.ndarray:
        return np.concatenate(
            [s.encode(r) for s, r in zip(self.spaces, x.reps)])

    def encode_batch(self, xs: Sequence[MultiTenantRep]) -> np.ndarray:
        return np.stack([self.encode(x) for x in xs])

    def decode(self, v: np.ndarray) -> MultiTenantRep:
        reps, off = [], 0
        for s in self.spaces:
            reps.append(s.decode(v[off:off + s.dim]))
            off += s.dim
        return MultiTenantRep(tuple(reps))

    def sample_uniform(
        self, rng: np.random.Generator, n: int
    ) -> list[MultiTenantRep]:
        per = [s.sample_uniform(rng, n) for s in self.spaces]
        return [MultiTenantRep(tuple(p[i] for p in per)) for i in range(n)]

    def mutate(self, rng: np.random.Generator,
               x: MultiTenantRep) -> MultiTenantRep:
        t = int(rng.integers(len(self.spaces)))
        reps = list(x.reps)
        reps[t] = self.spaces[t].mutate(rng, reps[t])
        return MultiTenantRep(tuple(reps))


class MultiTenantProfiler:
    """Joint profiler: perf is the mean per-tenant hold-out macro-F1,
    cost is the modeled shared-fleet cost — ONE union-plan extraction
    pass (shared ops deduped across tenants, the overlap discount) plus
    every tenant's inference. ``shared=False`` is the ablation arm: the
    same tenants billed as independent fleets (sum of solo costs). Both
    arms share the per-tenant profilers' trained-model caches, so a
    joint-vs-independent comparison trains each distinct (tenant, rep)
    at most once.

    Duck-compatible with `TrafficProfiler` as an evaluator: callable
    ``(x, metric) -> ProfileResult`` over `MultiTenantRep` points, so
    `MemoizedEvaluator`/`CatoOptimizer` drive it unchanged.
    """

    def __init__(self, profilers: Sequence[TrafficProfiler], *,
                 shared: bool = True):
        if not profilers:
            raise ValueError("need >= 1 tenant profiler")
        self.profilers = tuple(profilers)
        self.shared = shared
        self.n_profile_calls = 0

    def _depth_eff(self, depth: int) -> float:
        ds = self.profilers[0].test_ds
        return float(np.minimum(ds.flow_len, depth).mean())

    def __call__(self, x: MultiTenantRep,
                 metric: Optional[str] = None) -> ProfileResult:
        self.n_profile_calls += 1
        f1s, infer_ns, indep_ns = [], [], 0.0
        for p, r in zip(self.profilers, x.reps):
            f1, forest = p.perf_f1(r)
            f1s.append(float(f1))
            inf = p._inference_ns(forest)
            infer_ns.append(inf)
            indep_ns += modeled_extraction_cost_ns(
                r.features, self._depth_eff(r.depth)) + inf
        # union-plan extraction: one pass over the shared table, every
        # shared op across tenants counted once, at the union depth
        shared_ns = modeled_extraction_cost_ns(
            x.features, self._depth_eff(x.depth)) + sum(infer_ns)
        cost_ns = shared_ns if self.shared else indep_ns
        return ProfileResult(
            cost=cost_ns / 1e3,
            perf=float(np.mean(f1s)),
            aux={
                "per_tenant_f1": f1s,
                "cost_shared_us": shared_ns / 1e3,
                "cost_independent_us": indep_ns / 1e3,
                "overlap_discount": 1.0 - shared_ns / max(indep_ns, 1e-9),
                "tenant_infer_ns": infer_ns,
            },
        )
