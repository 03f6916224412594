"""Unified serving observability (DESIGN.md §11): registry exactness and
order-independent merge, bounded ring tracing with well-formed lifecycle
spans, control-plane audit coverage, and online drift signals that move
under the drift scenario and stay flat under uniform."""
import json

import numpy as np
import pytest

from repro.core.search_space import FeatureRep
from repro.serve import ServeSession
from repro.serve.control import ControlConfig, PipelineSwap
from repro.serve.control.replay import controlled_replay
from repro.serve.obs import (
    AuditLog,
    DriftMonitor,
    MetricsRegistry,
    Observability,
    StreamingMoments,
    Tracer,
    fleet_registry,
)
from repro.serve.runtime import (
    LatencyHistogram,
    PacketStream,
    RuntimeMetrics,
    ServiceModel,
    ShardedRuntime,
    StreamingRuntime,
    replay,
)
from repro.traffic import extract_features
from repro.traffic.models import train_traffic_model
from repro.traffic.pipeline import build_pipeline
from repro.traffic.synth import make_scenario_dataset


@pytest.fixture(scope="module")
def ds():
    # strong elephant skew: static 4-shard imbalance high enough that the
    # control plane rebalances several times within the trace
    return make_scenario_dataset("app-class", "zipf", n_flows=120,
                                 max_pkts=256, seed=3)


def _pipe(ds, rep):
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="tree-fast", seed=0)
    return build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)


@pytest.fixture(scope="module")
def pipeline(ds):
    return _pipe(ds, FeatureRep(
        ("dur", "s_load", "s_bytes_mean", "s_iat_mean", "ack_cnt"), depth=8))


@pytest.fixture(scope="module")
def pipeline_b(ds):
    return _pipe(ds, FeatureRep(
        ("dur", "s_load", "s_pkt_cnt", "d_bytes_med", "psh_cnt"), depth=12))


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


def fleet(pipeline, n_shards=4, execute=False, **kw):
    return ShardedRuntime(pipeline, n_shards=n_shards, capacity=2048,
                          max_batch=64, execute=execute, **kw)


# ---------------------------------------------------------------------------
# registry: snapshot / delta exactness
# ---------------------------------------------------------------------------


def test_registry_snapshot_delta_exact():
    reg = MetricsRegistry()
    reg.inc("flow_table.evictions", 3)
    reg.set_gauge("flow_table.load_factor", 0.25, reduce="max")
    reg.union("dispatch.shapes_seen", [(8, 5), (16, 5)])
    reg.extend_samples("dispatch.batch_occupancy", [4, 7])
    h = LatencyHistogram()
    h.record_many(np.array([1e-3, 2e-3, 5e-3]))
    reg.attach_hist("dispatch.latency", h)

    s1 = reg.snapshot()
    # untouched registry: two snapshots equal, self-delta all zero
    assert reg.snapshot() == s1
    d0 = MetricsRegistry.delta(s1, s1)
    assert d0["counters"]["flow_table.evictions"] == 0
    assert d0["hists"]["dispatch.latency"]["n"] == 0
    assert not any(d0["hists"]["dispatch.latency"]["counts"])
    assert d0["sets"]["dispatch.shapes_seen"] == []
    assert d0["samples"]["dispatch.batch_occupancy"] == []

    # interval activity, then the delta must be exactly that activity
    reg.inc("flow_table.evictions", 2)
    reg.union("dispatch.shapes_seen", [(32, 5)])
    reg.extend_samples("dispatch.batch_occupancy", [9])
    h.record_many(np.array([3e-3]))
    d = MetricsRegistry.delta(reg.snapshot(), s1)
    assert d["counters"]["flow_table.evictions"] == 2
    assert d["hists"]["dispatch.latency"]["n"] == 1
    assert sum(d["hists"]["dispatch.latency"]["counts"]) == 1
    assert d["sets"]["dispatch.shapes_seen"] == [[32, 5]]
    assert d["samples"]["dispatch.batch_occupancy"] == [9]

    # snapshots are JSON-serializable as-is (the artifact contract)
    json.dumps(reg.snapshot())


def test_registry_snapshot_excludes_reservoir():
    h = LatencyHistogram(max_samples=4)
    h.record_many(np.linspace(1e-3, 9e-3, 50))
    reg = MetricsRegistry()
    reg.attach_hist("dispatch.latency", h)
    doc = reg.snapshot()["hists"]["dispatch.latency"]
    # counts + exact scalars only: the (order-sensitive) reservoir never
    # leaks into a snapshot, so snapshot equality is well-defined
    assert set(doc) == {"n", "counts", "min_s", "max_s", "sum_s"}
    assert doc["n"] == 50
    assert sum(doc["counts"]) == 50
    assert doc["sum_s"] == pytest.approx(float(np.linspace(1e-3, 9e-3, 50).sum()))


def test_runtime_metrics_registry_roundtrip():
    m = RuntimeMetrics()
    for i, f in enumerate(RuntimeMetrics.counter_fields(), start=1):
        setattr(m, f, 10 * i + 3)
    m.batch_occupancy = [1, 5, 9]
    m.shapes_seen = {(8, 4), (16, 4)}
    m.latency.record_many(np.array([2e-3, 4e-3]))
    back = RuntimeMetrics.from_registry(m.to_registry())
    for f in RuntimeMetrics.counter_fields():
        assert getattr(back, f) == getattr(m, f)
    assert back.batch_occupancy == m.batch_occupancy
    assert back.shapes_seen == m.shapes_seen
    assert back.latency.n == m.latency.n


# ---------------------------------------------------------------------------
# registry: cross-shard merge
# ---------------------------------------------------------------------------


def _random_part(seed):
    rng = np.random.default_rng(seed)
    reg = MetricsRegistry()
    reg.inc("ingest.pkts_total", int(rng.integers(1, 1000)))
    reg.inc("flow_table.drops", int(rng.integers(0, 50)))
    reg.set_gauge("flow_table.load_factor", float(rng.random()), reduce="max")
    reg.set_gauge("dispatch.queue_depth", float(rng.integers(0, 9)),
                  reduce="sum")
    h = LatencyHistogram()
    h.record_many(rng.uniform(1e-4, 1e-1, size=int(rng.integers(5, 40))))
    reg.attach_hist("dispatch.latency", h)
    reg.union("dispatch.shapes_seen", [(int(b), 5) for b in
                                       rng.choice([8, 16, 32], size=2)])
    reg.extend_samples("dispatch.batch_occupancy",
                       rng.integers(1, 64, size=5).tolist())
    return reg


def test_merge_order_independent_and_sums():
    parts = [_random_part(s) for s in range(5)]
    fwd = MetricsRegistry.merge(parts)
    rev = MetricsRegistry.merge(parts[::-1])
    # counters: bit-identical to the per-part integer sums, any order
    for name in ("ingest.pkts_total", "flow_table.drops"):
        want = sum(p.counter(name) for p in parts)
        assert fwd.counter(name) == want
        assert rev.counter(name) == want
    # gauges fold under their declared reduction
    assert fwd.gauge("flow_table.load_factor") == max(
        p.gauge("flow_table.load_factor") for p in parts)
    assert rev.gauge("flow_table.load_factor") == \
        fwd.gauge("flow_table.load_factor")
    # histogram counts are integer adds: exact and order-independent
    want_counts = sum(p.hist("dispatch.latency").counts() for p in parts)
    assert np.array_equal(fwd.hist("dispatch.latency").counts(), want_counts)
    assert np.array_equal(rev.hist("dispatch.latency").counts(), want_counts)
    assert fwd.hist("dispatch.latency").n == sum(
        p.hist("dispatch.latency").n for p in parts)
    # sets union; samples concatenate (statistics permutation-invariant)
    assert fwd.snapshot()["sets"] == rev.snapshot()["sets"]
    assert sorted(fwd._samples["dispatch.batch_occupancy"]) == \
        sorted(rev._samples["dispatch.batch_occupancy"])
    # merge is a pure read: parts' histograms were not mutated or aliased
    assert fwd.hist("dispatch.latency") is not parts[0].hist("dispatch.latency")


def test_merge_with_prefixes_keeps_per_shard_columns():
    parts = [_random_part(s) for s in range(3)]
    agg = MetricsRegistry.merge(parts, prefixes=[f"shard{i}." for i in range(3)])
    for i, p in enumerate(parts):
        assert agg.counter(f"shard{i}.ingest.pkts_total") == \
            p.counter("ingest.pkts_total")
    assert agg.counter("ingest.pkts_total") == \
        sum(p.counter("ingest.pkts_total") for p in parts)


def test_gauge_reduce_mismatch_raises():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.set_gauge("x", 1.0, reduce="sum")
    b.set_gauge("x", 2.0, reduce="max")
    with pytest.raises(ValueError, match="reduce mismatch"):
        MetricsRegistry.merge([a, b])


def test_fleet_merged_bit_identical_to_per_shard_sums(pipeline, stream,
                                                      service):
    """The satellite claim: `AggregateMetrics.merged` (now a registry
    round-trip) reproduces the hand-summed per-shard counters bit-for-bit,
    and the fleet registry carries the same totals."""
    created = []

    def mk():
        rt = fleet(pipeline, execute=False)
        created.append(rt)
        return rt

    stats = replay(stream, mk, 2e5, service)
    rt = created[-1]
    m = stats.metrics
    parts = rt.metrics.parts
    for f in RuntimeMetrics.counter_fields():
        assert getattr(m, f) == sum(getattr(p, f) for p in parts), f
    assert m.latency.n == sum(p.latency.n for p in parts)
    reg = fleet_registry(rt, per_shard=True)
    assert reg.counter("ingest.pkts_total") == m.pkts_total
    assert reg.counter("dispatch.batches") == m.batches
    assert sum(reg.counter(f"shard{i}.ingest.pkts_total")
               for i in range(rt.n_shards)) == m.pkts_total
    # merge permutation-invariance on the real fleet blocks (sample tails
    # concatenate in merge order, so compare those as multisets)
    fwd = MetricsRegistry.merge([p.to_registry() for p in parts]).snapshot()
    rev = MetricsRegistry.merge(
        [p.to_registry() for p in parts[::-1]]).snapshot()
    fs, rs = fwd.pop("samples"), rev.pop("samples")
    assert fwd == rev
    assert {k: sorted(v) for k, v in fs.items()} == \
        {k: sorted(v) for k, v in rs.items()}


# ---------------------------------------------------------------------------
# tracer: bounded ring, sampling, lifecycle spans
# ---------------------------------------------------------------------------


def test_ring_never_exceeds_capacity():
    tr = Tracer(capacity=8)
    for i in range(100):
        tr.span("s", float(i), 0.5)
    assert len(tr) == 8
    assert tr.total == 100
    assert tr.dropped == 92
    evs = tr.events()
    assert len(evs) == 8
    # oldest surviving event first, newest last (ring order preserved)
    assert [e["ts"] for e in evs] == [float(i) * 1e6 for i in range(92, 100)]


def test_sampling_deterministic_and_bounded():
    ids = np.arange(4000)
    tr = Tracer(sample=0.25, seed=1)
    keep = tr.sample_mask(ids)
    assert np.array_equal(keep, tr.sample_mask(ids))  # deterministic
    assert 0.15 < keep.mean() < 0.35
    assert Tracer(sample=0.0).sample_mask(ids).sum() == 0
    assert Tracer(sample=1.0).sample_mask(ids).all()


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.span("s", 0.0, 1.0)
    tr.span_many("s", np.arange(4.0), np.ones(4))
    tr.instant("i", 0.0)
    tr.flow_begin(np.arange(3), np.zeros(3))
    tr.flow_end(np.arange(3), np.ones(3))
    assert tr.total == 0
    assert tr.summary() is None


def test_chrome_export_shape(tmp_path):
    tr = Tracer(capacity=64)
    tr.span("ingest.block", 0.0, 1e-3, pid=1, tid=0)
    tr.flow_begin(np.array([7]), np.array([0.0]), pid=1)
    tr.flow_end(np.array([7]), np.array([2e-3]), pid=1)
    doc = json.loads(tr.save(tmp_path / "t.json").read_text())
    evs = doc["traceEvents"]
    assert {"M", "X", "b", "e"} <= {e["ph"] for e in evs}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["dur"] == pytest.approx(1e3)  # microseconds
    b = next(e for e in evs if e["ph"] == "b")
    assert b["cat"] == "flow" and b["id"] == 7


def test_trace_spans_nest_under_controlled_replay(ds, pipeline, pipeline_b,
                                                  stream, service):
    """One traced controlled replay with migrations and a mid-trace swap:
    every sampled flow's lifecycle must be well-formed (begin before every
    milestone before end) and stage spans non-negative on the right lanes."""
    svc_b = ServiceModel(
        pkt_accum_ns=1000.0, pkt_track_ns=250.0,
        bucket_ns={8: 4e4, 16: 5e4, 32: 7e4, 64: 1.2e5},
        gather_ns_per_flow=200.0, source="synthetic")
    cut = stream.n_events // 2
    cfg = ControlConfig(interval_pkts=512, imbalance_trigger=1.04,
                        swap=PipelineSwap(pipeline_b, svc_b, after_pkts=cut))
    obs = Observability(tracer=Tracer(capacity=1 << 15, sample=1.0),
                        drift=DriftMonitor())
    stats = controlled_replay(
        stream, lambda: fleet(pipeline, execute=True), stream.base_pps,
        service, session=ServeSession(control=cfg, obs=obs))
    assert stats.drops == 0
    assert stats.control["swaps"] == 1
    assert stats.control["rebalances"] > 0

    evs = obs.tracer.events()
    assert obs.tracer.dropped == 0  # capacity ample: nesting check is total
    begins, ends, marks = {}, {}, {}
    for e in evs:
        if e.get("cat") == "flow":
            if e["ph"] == "b":
                begins[e["id"]] = e["ts"]
            elif e["ph"] == "e":
                ends[e["id"]] = e["ts"]
            else:
                marks.setdefault(e["id"], []).append(e["ts"])
    # every flow that completed has one begin and one end, properly ordered
    assert set(ends) <= set(begins)
    assert len(ends) == len(stats.predictions)
    for fid, t_end in ends.items():
        assert begins[fid] <= t_end
        for t_mark in marks.get(fid, []):
            assert begins[fid] <= t_mark <= t_end
    # stage spans on the expected lanes, non-negative, swap visible
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    assert {e["name"] for e in xs if e["tid"] == 0} >= {"ingest.block"}
    infer_names = {e["name"] for e in xs if e["tid"] == 1}
    assert any(n.startswith("infer.") for n in infer_names)
    assert "infer.swap" in infer_names  # the quiesce flush was traced
    # control decisions appear as instants on the control lane
    insts = {e["name"] for e in evs if e["ph"] == "i"}
    assert "control.rebalance" in insts and "control.hot_swap" in insts

    # audit log covered every actuation the plane counted
    audit = obs.audit.summary()
    assert audit["rebalance"] == stats.control["rebalances"]
    assert audit["hot_swap"] == stats.control["swaps"]
    reb = obs.audit.of_kind("rebalance")[0]
    assert len(reb.before["shard_loads_ewma"]) == 4
    assert reb.after["imbalance"] < reb.before["imbalance"]


# ---------------------------------------------------------------------------
# audit log
# ---------------------------------------------------------------------------


def test_audit_validates_and_roundtrips(tmp_path):
    log = AuditLog()
    with pytest.raises(ValueError, match="unknown audit kind"):
        log.record("reboot", 0.0, "nope")
    log.record("rebalance", 1.0, "imbalance", {"moves": 3},
               before={"imbalance": 1.8}, after={"imbalance": 1.1})
    log.record("deploy", 2.0, "knee point", {"depth": 8})
    assert len(log) == 2
    assert [e.seq for e in log.events] == [0, 1]
    path = log.save(tmp_path / "audit.jsonl")
    back = AuditLog.load(path)
    assert [e.to_doc() for e in back.events] == \
        [e.to_doc() for e in log.events]
    assert back.summary() == {"events": 2, "rebalance": 1, "deploy": 1}


def test_deploy_and_make_swap_audit(ds, pipeline, stream, service):
    from repro.serve.deploy import BundlePoint, deploy, make_swap

    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                      "ack_cnt"), depth=8)
    point = BundlePoint(rep=rep, cost=1.0, perf=0.9, fidelity="measured",
                        aux={}, compile_meta={"fused": False},
                        forest_doc=None, pipeline=pipeline)
    log = AuditLog()
    session = ServeSession(audit=log)
    swap = make_swap(point, after_pkts=100, runtime=None, service=service,
                     session=session)
    assert swap.after_pkts == 100
    assert log.of_kind("swap_scheduled")[0].detail["after_pkts"] == 100
    rt = StreamingRuntime(pipeline, capacity=512, max_batch=32, execute=False)
    deploy(point, rt, 0.0, session=session)
    assert log.summary() == {"events": 2, "swap_scheduled": 1, "deploy": 1}


# ---------------------------------------------------------------------------
# drift signals
# ---------------------------------------------------------------------------


def test_streaming_moments_match_batch():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 3)) * [1.0, 5.0, 0.1] + [0.0, 2.0, -1.0]
    sm = StreamingMoments(3)
    for lo in range(0, 500, 64):
        sm.update(X[lo:lo + 64])
    assert sm.n == 500
    np.testing.assert_allclose(sm.mean, X.mean(axis=0), rtol=1e-9)
    np.testing.assert_allclose(sm.var(), X.var(axis=0, ddof=1), rtol=1e-9)


def test_drift_monitor_synthetic_regime_change():
    rng = np.random.default_rng(1)
    dm = DriftMonitor(min_batches=4)
    for _ in range(30):  # stationary: classes 0/1 at 70/30
        dm.note_predictions(rng.choice(2, size=64, p=[0.7, 0.3]))
    flat = dm.signal()["max_class_shift"]
    for _ in range(10):  # regime change: class 2 takes over
        dm.note_predictions(np.full(64, 2))
    moved = dm.signal()["class_mix_shift"]
    assert flat < 0.15
    assert moved > 0.5
    assert moved > 4 * max(flat, 1e-6)


def test_drift_scenario_fires_uniform_stays_flat(service):
    """End to end: the same replay instrumented with a DriftMonitor sees a
    moving class mix under the `drift` scenario and a comparatively flat
    one under `uniform` (the ISSUE's acceptance signal)."""
    def signal_for(scenario):
        d = make_scenario_dataset("app-class", scenario, n_flows=400,
                                  max_pkts=32, seed=3)
        rep = FeatureRep(("dur", "s_load", "s_bytes_mean"), depth=8)
        pipe = _pipe(d, rep)
        st = PacketStream.from_dataset(d, seed=0)
        obs = Observability(drift=DriftMonitor())
        replay(st, lambda: StreamingRuntime(pipe, capacity=2048,
                                            max_batch=32, execute=True),
               2e5, service, session=ServeSession(obs=obs))
        sig = obs.drift.signal()
        assert sig["n_flows"] == 400
        return sig

    uni = signal_for("uniform")
    dri = signal_for("drift")
    assert dri["max_class_shift"] > 2 * uni["max_class_shift"]
    assert dri["max_class_shift"] > 0.4
    assert uni["max_class_shift"] < 0.35
    # feature sketches were fed from the dispatch arena in both runs
    assert uni["n_batches"] > 0 and dri["n_batches"] > 0


# ---------------------------------------------------------------------------
# stage accounting + bundle plumbing
# ---------------------------------------------------------------------------


def test_stage_seconds_partition_busy_time(pipeline, stream, service):
    stats = replay(stream,
                   lambda: StreamingRuntime(pipeline, capacity=2048,
                                            max_batch=64, execute=False),
                   2e5, service)
    ss = stats.stage_seconds
    assert set(ss) == {"ingest", "infer", "flush"}
    assert all(v >= 0 for v in ss.values()) and sum(ss.values()) > 0
    assert sum(stats.stage_shares().values()) == pytest.approx(1.0)


def test_per_shard_stage_rows(pipeline, stream, service):
    stats = replay(stream, lambda: fleet(pipeline), 2e5, service)
    assert len(stats.per_shard) == 4
    for row in stats.per_shard:
        assert set(row["stage_seconds"]) == {"ingest", "infer", "flush"}
    agg = {k: sum(r["stage_seconds"][k] for r in stats.per_shard)
           for k in ("ingest", "infer", "flush")}
    for k, v in stats.stage_seconds.items():
        assert v == pytest.approx(agg[k])


def test_hot_swap_and_scale_out_carry_hooks(pipeline, pipeline_b):
    obs = Observability(tracer=Tracer(capacity=64), drift=DriftMonitor())
    rt = fleet(pipeline, n_shards=2)
    obs.attach(rt)
    rt.hot_swap(pipeline_b, now=0.0)
    for w in rt.shards:
        assert w.dispatcher.tracer is obs.tracer
        assert w.table.tracer is obs.tracer
        assert w.dispatcher.drift is obs.drift
    i = rt.add_worker()
    assert rt.shards[i].dispatcher.tracer is obs.tracer
    assert rt.shards[i].table.tracer is obs.tracer
    assert rt.shards[i].dispatcher.trace_pid == i


def test_snapshot_document(pipeline, stream, service):
    obs = Observability(tracer=Tracer(capacity=1 << 12, sample=0.5),
                        drift=DriftMonitor())
    created = []

    def mk():
        rt = fleet(pipeline, execute=False)
        created.append(rt)
        return rt

    stats = replay(stream, mk, 2e5, service,
                   session=ServeSession(
                       control=ControlConfig(interval_pkts=512), obs=obs))
    doc = obs.snapshot(created[-1])
    assert doc["registry"]["counters"]["ingest.pkts_total"] == \
        stats.metrics.pkts_total
    assert doc["trace"]["events"] > 0
    json.dumps(doc)  # artifact contract: snapshot is JSON-ready
