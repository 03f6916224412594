"""Multi-tenant white-box serving (DESIGN.md §15).

The contracts under test:

- plan merging: shared (op, depth) work units are deduped across tenants,
  and every tenant's static column map reads back exactly its own plan;
- column-subset property: over random tenant rep sets, each tenant's
  columns of the merged extraction matrix match its solo extraction at
  its own connection depth to float32 ulp (the depth-group static
  slicing that makes sharing an optimization, not a model change);
- fused ≡ unfused ≡ solo: the single multi-forest kernel launch, the
  unfused gather path, and N solo pipelines agree bitwise, lane by lane;
- serving parity: a shared fleet under overflow pressure and control-plane
  migration produces per-tenant predictions bit-identical to N solo
  fleets replaying the same stream, and attributes per-tenant counters;
- deploy: `MultiTenantBundlePoint` round-trips through its document form,
  `compile_multi_tenant` fuses per-tenant points (cost = independent sum,
  the discount is what deployment buys), and a fused bundle hot-swaps
  into a live fleet with zero drops and exactly-once prediction;
- co-optimization: `MultiTenantProfiler` prices the union plan below the
  independent sum for overlapping tenants, identically for perf;
- observability: per-tenant prediction counters survive the registry
  round-trip, render as ``tenant`` labels in valid Prometheus output,
  and the replay tracer carries per-tenant infer sub-lanes.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.search_space import FeatureRep
from repro.serve import (
    PacketStream,
    ServeSession,
    ServiceModel,
    ShardedRuntime,
    build_multi_tenant_pipeline,
    compile_multi_tenant,
    make_swap,
    replay,
)
from repro.serve.control import ControlConfig
from repro.serve.deploy import BundlePoint, MultiTenantBundlePoint, _forest_to_doc
from repro.serve.obs import Observability, Tracer, check_prometheus, render_prometheus
from repro.serve.obs.trace import TID_TENANT0
from repro.serve.runtime import RuntimeMetrics
from repro.traffic import TrafficProfiler, extract_features
from repro.traffic.extraction import merge_stats_plans, stats_plan
from repro.traffic.models import train_traffic_model
from repro.traffic.multi_tenant import (
    MultiTenantProfiler,
    MultiTenantRep,
    MultiTenantSpace,
    union_rep,
)
from repro.traffic.pipeline import build_pipeline
from repro.traffic.synth import make_scenario_dataset

FEATURE_POOL = (
    "s_bytes_mean", "s_bytes_max", "s_iat_mean", "d_iat_std", "s_load",
    "d_load", "dur", "proto", "s_port", "s_ttl_mean", "d_pkt_cnt",
    "ack_cnt", "psh_cnt",
)

TENANT_REPS = (
    FeatureRep(("s_bytes_mean", "s_iat_mean", "proto", "s_load"), depth=8),
    FeatureRep(("s_bytes_mean", "s_bytes_max", "dur", "d_load"), depth=12),
    FeatureRep(("s_iat_mean", "s_load", "d_pkt_cnt", "ack_cnt"), depth=8),
)


def _clip(ds, depth):
    """The (rows, depth) view a solo tenant's flow table would hold."""
    d = min(int(depth), ds.max_pkts)
    return dataclasses.replace(
        ds, ts=ds.ts[:, :d], size=ds.size[:, :d],
        direction=ds.direction[:, :d], ttl=ds.ttl[:, :d],
        winsize=ds.winsize[:, :d], flags=ds.flags[:, :d, :])


@pytest.fixture(scope="module")
def ds():
    return make_scenario_dataset("app-class", "zipf", n_flows=100,
                                 max_pkts=48, seed=5)


@pytest.fixture(scope="module")
def forests(ds):
    out = []
    for t, rep in enumerate(TENANT_REPS):
        X = extract_features(ds, rep.features, rep.depth)
        out.append(train_traffic_model(X, ds.label, model="tree-fast",
                                       seed=t)[0])
    return tuple(out)


@pytest.fixture(scope="module")
def solo_pipes(ds, forests):
    return [build_pipeline(r, f, max_pkts=r.depth, use_kernel=False)
            for r, f in zip(TENANT_REPS, forests)]


@pytest.fixture(scope="module")
def mt_pipe(forests):
    return build_multi_tenant_pipeline(TENANT_REPS, forests,
                                       use_kernel=False)


@pytest.fixture(scope="module")
def stream(ds):
    return PacketStream.from_dataset(ds, seed=0)


@pytest.fixture(scope="module")
def service():
    return ServiceModel(
        pkt_accum_ns=800.0, pkt_track_ns=200.0,
        bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
        gather_ns_per_flow=200.0, source="synthetic",
    )


# ---------------------------------------------------------------------------
# plan merging
# ---------------------------------------------------------------------------


def test_merge_dedups_shared_work_units():
    plans = [stats_plan(r.features) for r in TENANT_REPS]
    merged, cols = merge_stats_plans(plans, [r.depth for r in TENANT_REPS])
    # dedup is real: strictly fewer merged columns than plan positions
    assert len(merged) < sum(len(p) for p in plans)
    assert len(set(merged)) == len(merged)
    # every tenant's column map reads back exactly its own plan entries
    for plan, c, r in zip(plans, cols, TENANT_REPS):
        assert len(c) == len(plan)
        for pos, mc in enumerate(c):
            entry, depth = merged[mc]
            assert entry == plan[pos]
            assert depth == (0 if entry[0] == "meta" else r.depth)
    # meta entries are depth-0, so they dedup across different depths:
    # tenant0 (depth 8) and tenant2 (depth 6) share `s_load`'s meta deps?
    # directly: same meta feature at two depths -> one merged column
    m2, c2 = merge_stats_plans(
        [stats_plan(("proto",)), stats_plan(("proto",))], [4, 16])
    assert len(m2) == 1 and c2 == ((0,), (0,))


def test_union_rep_is_union_at_max_depth():
    u = union_rep(TENANT_REPS)
    assert u.depth == max(r.depth for r in TENANT_REPS)
    assert set(u.features) == set().union(*(r.features for r in TENANT_REPS))


def test_union_columns_match_solo_extraction_property(ds):
    """Random tenant sets: merged matrix column subsets == solo extracts."""
    import jax.numpy as jnp

    from repro.traffic.extraction import emit_merged_columns, pack_flags

    rng = np.random.default_rng(7)
    for _ in range(5):
        reps = []
        for _t in range(int(rng.integers(2, 5))):
            k = int(rng.integers(2, 6))
            feats = tuple(rng.choice(FEATURE_POOL, size=k, replace=False))
            reps.append(FeatureRep(feats, int(rng.integers(2, 33))))
        plans = [stats_plan(r.features) for r in reps]
        merged, cols = merge_stats_plans(plans, [r.depth for r in reps])
        u = _clip(ds, union_rep(reps).depth)
        out = emit_merged_columns(
            merged, ts=jnp.asarray(u.ts), size=jnp.asarray(u.size),
            direction=jnp.asarray(u.direction), ttl=jnp.asarray(u.ttl),
            winsize=jnp.asarray(u.winsize),
            flags=pack_flags(u.flags),
            flow_len=jnp.asarray(u.flow_len), proto=jnp.asarray(u.proto),
            s_port=jnp.asarray(u.s_port), d_port=jnp.asarray(u.d_port))
        X = np.stack([np.asarray(c) for c in out], axis=1)
        for r, c in zip(reps, cols):
            solo = extract_features(_clip(ds, r.depth), r.features, r.depth)
            # ulp-level: each depth group reduces exactly solo-width
            # slices, but the merged program fuses differently under XLA
            # so float reduction order may differ by one rounding step.
            # End-to-end *predictions* are bit-identical (tests below).
            np.testing.assert_allclose(
                X[:, list(c)], solo, rtol=2e-7, atol=1e-7,
                err_msg=f"tenant {r.features}@{r.depth} columns diverged")


# ---------------------------------------------------------------------------
# fused ≡ unfused ≡ solo
# ---------------------------------------------------------------------------


def test_fused_unfused_solo_bitwise_parity(ds, forests, solo_pipes, mt_pipe):
    fused = build_multi_tenant_pipeline(TENANT_REPS, forests, fused=True)
    batch = _clip(ds, mt_pipe.rep.depth)
    p_unfused = mt_pipe.probabilities(batch)
    p_fused = fused.probabilities(batch)
    np.testing.assert_array_equal(p_fused, p_unfused)
    for t, ((lo, hi), solo, rep) in enumerate(
            zip(mt_pipe.lanes, solo_pipes, TENANT_REPS)):
        solo_p = np.asarray(solo.predict_async(_clip(ds, rep.depth)))
        np.testing.assert_array_equal(
            p_unfused[:, lo:hi], solo_p,
            err_msg=f"tenant {t} probability lane diverged")
    # finalize: column t is tenant t's solo class decisions
    out = mt_pipe.finalize(p_unfused)
    assert out.shape == (ds.n_flows, len(TENANT_REPS))
    for t, (solo, rep) in enumerate(zip(solo_pipes, TENANT_REPS)):
        solo_cls = solo.finalize(solo.predict_async(_clip(ds, rep.depth)))
        np.testing.assert_array_equal(out[:, t], solo_cls)


def test_incremental_entry_matches_merged_plan(mt_pipe):
    # this tenant set is all-incremental (no medians): the aggregate
    # entry must exist so the reuse/refresh path can serve it
    assert mt_pipe.supports_agg
    assert mt_pipe.drift_prob_slice == slice(*mt_pipe.lanes[0])


# ---------------------------------------------------------------------------
# serving parity under pressure + per-tenant observability
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity_replays(stream, solo_pipes, mt_pipe, service):
    # capacity 64 << 100 flows forces table overflow/eviction; the
    # control plane migrates flows between the 2 shards mid-trace
    def mk(pipe):
        def fleet():
            return ShardedRuntime(pipe, n_shards=2, capacity=64,
                                  max_batch=32, flush_timeout_s=2e-4,
                                  execute=True)
        return fleet

    cfg = dict(interval_pkts=256)
    sh = replay(stream, mk(mt_pipe), stream.base_pps, service,
                ring_capacity=512,
                session=ServeSession(control=ControlConfig(**cfg)))
    solos = [replay(stream, mk(p), stream.base_pps, service,
                    ring_capacity=512,
                    session=ServeSession(control=ControlConfig(**cfg)))
             for p in solo_pipes]
    return sh, solos


def test_shared_fleet_bitwise_parity_with_solo(parity_replays):
    sh, solos = parity_replays
    assert len(sh.predictions) > 0
    for t, solo in enumerate(solos):
        assert sorted(sh.predictions) == sorted(solo.predictions)
        keys = sorted(sh.predictions)
        np.testing.assert_array_equal(
            np.asarray([sh.predictions[k][t] for k in keys]),
            np.asarray([solo.predictions[k] for k in keys]),
            err_msg=f"tenant {t} diverged from solo fleet")


def test_tenant_prediction_counters(parity_replays):
    sh, _ = parity_replays
    m = sh.metrics
    n = m.flows_predicted
    assert n > 0
    # one fused batch answers every tenant: each lane advances in step
    assert m.tenant_predictions == {t: n for t in range(len(TENANT_REPS))}
    # registry round-trip preserves the per-tenant attribution exactly
    m2 = RuntimeMetrics.from_registry(m.to_registry())
    assert m2.tenant_predictions == m.tenant_predictions
    assert m2.flows_predicted == n
    assert "tenant_predictions" in m.summary()


def test_prometheus_tenant_labels(parity_replays):
    sh, _ = parity_replays
    reg = sh.metrics.to_registry(prefix="shard0.")
    text = render_prometheus(reg)
    assert check_prometheus(text) == []
    want = (f'cato_dispatch_flows_predicted{{shard="0",tenant="1"}} '
            f'{sh.metrics.flows_predicted}')
    assert want in text


# ---------------------------------------------------------------------------
# deploy: bundle round-trip + hot swap
# ---------------------------------------------------------------------------


def _points(forests, reps=TENANT_REPS):
    return [BundlePoint(rep=r, cost=float(1 + t), perf=0.5 + 0.1 * t,
                        fidelity="modeled", aux={},
                        compile_meta={"fused": False, "use_kernel": False},
                        forest_doc=_forest_to_doc(f))
            for t, (r, f) in enumerate(zip(reps, forests))]


def test_bundle_point_roundtrip(ds, forests, mt_pipe):
    mt = compile_multi_tenant(_points(forests), fused=False,
                              use_kernel=False, warm=False)
    assert mt.rep == union_rep(TENANT_REPS)
    assert mt.cost == pytest.approx(sum(1 + t for t in range(3)))
    assert mt.perf == pytest.approx(np.mean([0.5, 0.6, 0.7]))
    assert mt.aux["tenant_costs"] == [1.0, 2.0, 3.0]
    back = MultiTenantBundlePoint.from_doc(mt.to_doc())
    assert back.to_doc() == mt.to_doc()
    assert back.tenant_reps == TENANT_REPS
    # the rebuilt pipeline serves the exact same model
    pipe = back.build(warm=False)
    batch = _clip(ds, mt_pipe.rep.depth)
    np.testing.assert_array_equal(pipe.probabilities(batch),
                                  mt_pipe.probabilities(batch))


def test_hot_swap_multi_tenant_bundle(ds, stream, forests, service):
    reps_b = (
        FeatureRep(("s_bytes_mean", "s_iat_mean", "proto"), depth=6),
        FeatureRep(("s_bytes_mean", "dur", "d_load"), depth=8),
        FeatureRep(("s_load", "d_pkt_cnt"), depth=6),
    )
    forests_b = tuple(
        train_traffic_model(extract_features(ds, r.features, r.depth),
                            ds.label, model="tree-fast", seed=10 + t)[0]
        for t, r in enumerate(reps_b))
    start = compile_multi_tenant(_points(forests), fused=False,
                                 use_kernel=False, warm=False)
    target = compile_multi_tenant(_points(forests_b, reps_b), fused=False,
                                  use_kernel=False, warm=False)

    def fleet():
        return ShardedRuntime(start.pipeline, n_shards=2, capacity=2048,
                              max_batch=32, execute=True)

    swap = make_swap(target, after_pkts=stream.n_events // 2,
                     runtime=fleet())
    stats = replay(stream, fleet, stream.base_pps, service,
                   ring_capacity=1024,
                   session=ServeSession(control=ControlConfig(
                       interval_pkts=256, rebalance=False, swap=swap)))
    assert stats.drops == 0
    assert stats.control["swaps"] == 1
    assert len(stats.predictions) == ds.n_flows
    assert stats.metrics.duplicate_predictions == 0
    # every flow answered once FOR ALL TENANTS, before and after the swap
    assert {np.asarray(v).shape for v in stats.predictions.values()} \
        == {(len(TENANT_REPS),)}


def test_make_swap_uses_multi_tenant_service(forests):
    mt = compile_multi_tenant(_points(forests), fused=False,
                              use_kernel=False, warm=False)
    swap = make_swap(mt, after_pkts=10)
    fr = swap.service.tenant_fracs
    assert fr is not None and len(fr) == len(TENANT_REPS)
    assert sum(fr) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# co-optimization: the profiler prices the sharing
# ---------------------------------------------------------------------------


def test_profiler_overlap_discount(ds):
    pools = (("s_bytes_mean", "s_iat_mean", "s_load", "proto"),
             ("s_bytes_mean", "s_iat_mean", "dur", "ack_cnt"))
    profs = [TrafficProfiler(ds, p, model="tree-fast", cost_mode="modeled",
                             seed=0) for p in pools]
    shared = MultiTenantProfiler(profs, shared=True)
    indep = MultiTenantProfiler(profs, shared=False)
    x = MultiTenantRep((
        FeatureRep(("s_bytes_mean", "s_iat_mean", "s_load"), depth=8),
        FeatureRep(("s_bytes_mean", "s_iat_mean", "dur"), depth=8),
    ))
    r_sh, r_in = shared(x), indep(x)
    # same tenants, same models: perf identical; only the billing moves
    assert r_sh.perf == r_in.perf
    assert r_sh.cost < r_in.cost
    assert r_sh.cost == pytest.approx(r_sh.aux["cost_shared_us"])
    assert r_in.cost == pytest.approx(r_in.aux["cost_independent_us"])
    assert r_sh.aux["overlap_discount"] > 0.1
    # identical tenant plans are the sharing limit: discount grows past
    # the partial-overlap config; disjoint plans share only the window
    # accumulation, so their discount sits strictly below both
    dup = MultiTenantRep((
        FeatureRep(("s_bytes_mean", "s_iat_mean"), depth=8),
        FeatureRep(("s_bytes_mean", "s_iat_mean"), depth=8),
    ))
    disj = MultiTenantRep((
        FeatureRep(("s_bytes_mean",), depth=8),
        FeatureRep(("dur",), depth=8),
    ))
    d_partial = r_sh.aux["overlap_discount"]
    assert shared(dup).aux["overlap_discount"] > d_partial
    assert shared(disj).aux["overlap_discount"] < d_partial


def test_space_protocol_roundtrip():
    spaces = (
        __import__("repro.core.search_space", fromlist=["SearchSpace"])
        .SearchSpace(("s_bytes_mean", "dur", "proto"), max_depth=8),
        __import__("repro.core.search_space", fromlist=["SearchSpace"])
        .SearchSpace(("s_iat_mean", "s_load"), max_depth=4),
    )
    joint = MultiTenantSpace(spaces)
    assert joint.dim == sum(s.dim for s in spaces)
    rng = np.random.default_rng(0)
    xs = joint.sample_uniform(rng, 8)
    for x in xs:
        assert joint.decode(joint.encode(x)) == x
        y = joint.mutate(rng, x)
        # one tenant moved, the others are untouched
        assert sum(a != b for a, b in zip(x.reps, y.reps)) <= 1
    assert joint.encode_batch(xs).shape == (8, joint.dim)


# ---------------------------------------------------------------------------
# replay tracer: per-tenant infer sub-lanes
# ---------------------------------------------------------------------------


def test_trace_has_per_tenant_infer_lanes(stream, mt_pipe, forests):
    svc = ServiceModel.modeled_multi_tenant(TENANT_REPS, forests)
    assert len(svc.tenant_fracs) == len(TENANT_REPS)
    assert sum(svc.tenant_fracs) == pytest.approx(1.0)
    obs = Observability(tracer=Tracer(capacity=1 << 14))
    replay(stream, lambda: ShardedRuntime(mt_pipe, n_shards=2,
                                          capacity=2048, max_batch=32),
           stream.base_pps, svc, session=ServeSession(obs=obs))
    names = set(obs.tracer._names)
    for t in range(len(TENANT_REPS)):
        assert f"infer.tenant{t}" in names
    meta = [e for e in obs.tracer.chrome()["traceEvents"]
            if e.get("name") == "thread_name"
            and e.get("tid", 0) >= TID_TENANT0]
    assert {e["args"]["name"] for e in meta} \
        == {f"tenant {t} infer" for t in range(len(TENANT_REPS))}
