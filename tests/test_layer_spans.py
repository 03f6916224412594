"""Layer spans and counters of the served path (`Tracer.layer`, DESIGN.md
§11.2): what each span counts, how self time nests, that tracing never
changes a result, and that the spans share the profiler's clock with
annotations made around the program's calls.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.search_space import FeatureRep
from repro.serve.obs import Observability, Tracer
from repro.serve.obs import trace as trace_mod
from repro.serve.runtime import FlowStatus, PacketStream, StreamingRuntime
from repro.traffic import extract_features, make_dataset
from repro.traffic.models import train_traffic_model
from repro.traffic.pipeline import build_pipeline

DEPTH = 6
BLOCK = 512
READY = [int(FlowStatus.READY), int(FlowStatus.READY_EOF)]


@pytest.fixture(scope="module")
def stream():
    ds = make_dataset("app-class", n_flows=300, max_pkts=24, seed=9)
    return ds, PacketStream.from_dataset(ds, seed=1)


@pytest.fixture(scope="module")
def pipeline(stream):
    ds, _ = stream
    rep = FeatureRep(
        ("dur", "s_load", "s_bytes_mean", "d_iat_std", "ack_cnt"), depth=DEPTH)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="rf-fast", seed=0)
    return build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)


def _block(stream, lo, hi):
    fid = stream.fid[lo:hi]
    return (stream.key[fid], stream.base_t[lo:hi], stream.rel_ts32[lo:hi],
            stream.size[lo:hi], stream.direction[lo:hi], stream.ttl[lo:hi],
            stream.winsize[lo:hi], stream.flags_byte[lo:hi], stream.proto[fid],
            stream.s_port[fid], stream.d_port[fid], fid, stream.fin[lo:hi])


def _runtime(pipeline, tracer=None, **kw):
    # a small table and short batches: flows are dropped, and the pending
    # window resolves mid-block
    kw = {"capacity": 96, "max_batch": 16, "min_bucket": 8,
          "flush_timeout_s": 0.02, **kw}
    rt = StreamingRuntime(pipeline, **kw)
    if tracer is not None:
        Observability(tracer=tracer).attach(rt)
    return rt


# a table that drops nothing, 8-flow batches and the stream's clock run
# 50x faster: the queue fills before the flush timeout often enough that
# sub-blocks end at both bounds, and the READY plan runs
FAST = dict(capacity=1024, max_batch=8)
SPEED = 50.0


def _ingest(rt, stream, poll_every=4, speed=1.0):
    """Feed the stream in blocks, its clock `speed` times faster; returns
    its last time and the number of packets that made a flow READY."""
    E = stream.n_events
    n_ready = 0
    for k, lo in enumerate(range(0, E, BLOCK)):
        hi = min(lo + BLOCK, E)
        blk = list(_block(stream, lo, hi))
        blk[1] = blk[1] / speed
        st, _, _ = rt.ingest_packets(*blk)
        n_ready += int(np.isin(st, READY).sum())
        if k % poll_every == poll_every - 1:
            rt.poll(float(stream.base_t[hi - 1]) / speed)
    return float(stream.base_t[E - 1]) / speed, n_ready


def test_layer_items_match_the_runtime_counters(pipeline, stream):
    _, st = stream
    tr = Tracer(sample=0.0)
    rt = _runtime(pipeline, tr)
    m = rt.metrics
    pkts0, flows0 = m.pkts_total, m.flows_predicted
    end, n_ready = _ingest(rt, st)
    rt.drain(end + 1.0)
    spans = tr.layers()["spans"]
    assert spans["ingest"]["items"] == st.n_events
    assert spans["observe"]["items"] == m.pkts_total - pkts0
    assert spans["flush"]["items"] == m.flows_predicted - flows0
    assert spans["flush"]["calls"] == m.batches
    assert spans["gather"]["items"] == m.flows_predicted - flows0
    assert spans["resolve"]["items"] == m.flows_predicted - flows0
    assert spans["resolve.wait"]["calls"] == spans["resolve"]["calls"]
    assert spans["poll"]["calls"] == len(range(0, st.n_events, BLOCK)) // 4
    # a submit carries the padded arena: ten tensors of its bucket
    bucket_bytes = {b: b * (DEPTH * (4 * 4 + 1 + 8) + 4 * 4)
                    for b in (8, 16)}
    sizes = [bucket_bytes[r.bucket] for r in rt.dispatcher.records]
    assert spans["submit"]["items"] == sum(sizes)
    assert spans["submit"]["calls"] == len(sizes)
    slow = spans["observe.slow"]["items"]
    fast = spans["observe.fast"]["items"]
    assert slow + fast == spans["observe"]["items"]
    assert spans["observe.partition"]["calls"] == spans["observe"]["calls"]
    assert spans["ready"]["items"] == n_ready


def test_self_time_nests(pipeline, stream):
    _, st = stream
    tr = Tracer(sample=0.0)
    rt = _runtime(pipeline, tr)
    _ingest(rt, st, poll_every=10 ** 9)   # ingest_packets is the only root
    spans = tr.layers()["spans"]
    for row in spans.values():
        assert 0 <= row["self_ns"] <= row["total_ns"]
    # every span lies under `ingest`: the self times partition its total
    assert sum(r["self_ns"] for r in spans.values()) == spans["ingest"]["total_ns"]

    def tot(*names):
        return sum(spans[n]["total_ns"] for n in names if n in spans)

    assert tot("observe.partition", "observe.slow", "observe.fast") <= tot("observe")
    assert tot("observe", "ready") <= tot("ingest")
    assert tot("flush") <= tot("ready")
    assert tot("gather", "submit", "resolve") <= tot("flush")
    assert tot("resolve.wait") <= tot("resolve")


def test_subblock_cuts_count_every_sub_block_but_the_last(pipeline, stream):
    _, st = stream
    tr = Tracer(sample=0.0)
    rt = _runtime(pipeline, tr, **FAST)
    _ingest(rt, st, poll_every=10 ** 9, speed=SPEED)
    lay = tr.layers()
    c = lay["counters"]
    assert c["subblock.cut_room"] > 0 and c["subblock.cut_timeout"] > 0
    assert c["subblock.cut_room"] + c["subblock.cut_timeout"] == \
        lay["spans"]["observe"]["calls"] - lay["spans"]["ingest"]["calls"]


def test_plan_span_and_ready_potential_counter(pipeline, stream):
    """`ingest.plan` times each READY plan under `ingest`, and
    `subblock.ready_potential` counts the packets it marked in the
    sub-blocks it bounded: at least the READY packets there."""
    _, st = stream
    tr = Tracer(sample=0.0)
    rt = _runtime(pipeline, tr, **FAST)
    planned = []
    end = type(rt)._sub_block_end

    def calls():
        return tr.layers()["spans"].get("ingest.plan", {"calls": 0})["calls"]

    def spy(self, now, lo, *cols):
        n = calls()
        hi = end(self, now, lo, *cols)
        if calls() > n:
            planned.append((lo, hi))
        return hi

    rt._sub_block_end = spy.__get__(rt)
    n_ready = 0
    for lo in range(0, st.n_events, BLOCK):
        blk = list(_block(st, lo, min(lo + BLOCK, st.n_events)))
        blk[1] = blk[1] / SPEED
        del planned[:]
        ready = np.isin(rt.ingest_packets(*blk)[0], READY)
        n_ready += sum(int(ready[a:b].sum()) for a, b in planned)
    lay = tr.layers()
    c = lay["counters"]
    assert lay["spans"]["ingest.plan"]["calls"] >= c["subblock.cut_room"] > 0
    assert c["subblock.ready_potential"] >= n_ready > 0
    assert lay["spans"]["ingest.plan"]["total_ns"] <= \
        lay["spans"]["ingest"]["total_ns"]


def _outcome(rt):
    recs = [(r.flow_ids.tolist(), r.ready_ts.tolist(), r.flush_ts, r.bucket,
             r.n_real, r.reason, r.flush_idx, np.asarray(r.preds).tolist(),
             r.resolved_ts) for r in rt.dispatcher.records]
    return dict(rt.results), recs


@pytest.mark.parametrize("mode", ["disabled", "enabled"])
def test_tracing_changes_no_result(pipeline, stream, mode):
    _, st = stream
    base = _runtime(pipeline)
    base.drain(_ingest(base, st)[0] + 1.0)
    traced = _runtime(pipeline, Tracer(sample=1.0, enabled=mode == "enabled"))
    traced.drain(_ingest(traced, st)[0] + 1.0)
    assert _outcome(traced) == _outcome(base)
    assert base.metrics.to_registry().snapshot() == \
        traced.metrics.to_registry().snapshot()


def test_disabled_tracer_opens_no_span(pipeline, stream, monkeypatch):
    _, st = stream

    def refuse(*a, **k):
        raise AssertionError("a disabled tracer was asked for a span")

    tr = Tracer(enabled=False)
    monkeypatch.setattr(tr, "layer", refuse)
    monkeypatch.setattr(tr, "count", refuse)
    for kw, speed in (({}, 1.0), (FAST, SPEED)):   # the second plans
        rt = _runtime(pipeline, tr, **kw)
        rt.drain(_ingest(rt, st, speed=speed)[0] + 1.0)
    assert tr.layers() == {"spans": {}, "counters": {}}


def test_traced_submit_counts_the_served_forest(pipeline, stream):
    """Every submit adds the pipeline's forest counters (real nodes and
    one-hot lanes) while the tracer is enabled, and nothing while not."""
    _, st = stream
    counted = dataclasses.replace(pipeline, counts={"forest.nodes": 7,
                                                    "forest.lanes": 640})
    on, off = Tracer(sample=0.0), Tracer(sample=0.0, enabled=False)
    for tr in (on, off):
        rt = _runtime(counted, tr)
        rt.drain(_ingest(rt, st)[0] + 1.0)
    batches = on.layers()["spans"]["submit"]["calls"]
    assert batches == len(rt.dispatcher.records) > 0
    assert on.layers()["counters"]["forest.nodes"] == 7 * batches
    assert on.layers()["counters"]["forest.lanes"] == 640 * batches
    assert off.layers()["counters"] == {}


def test_forest_compaction_is_a_span_while_enabled(pipeline, stream):
    """A hot swap onto a traced runtime times the new forest's compaction
    on the runtime's tracer; a disabled tracer records nothing, and a
    forest that needs no compaction (already compact, or served by the jnp
    reference from its dense tables) opens no span."""
    from repro.core.forest import CompactForest
    from repro.serve.control.plane import PipelineSwap

    ds, _ = stream
    rep = FeatureRep(("dur", "s_load", "ack_cnt"), depth=DEPTH)
    forest, _ = train_traffic_model(extract_features(ds, rep.features, rep.depth),
                                    ds.label, model="rf-fast", seed=0)
    on, off = Tracer(sample=0.0), Tracer(sample=0.0, enabled=False)
    for tr in (on, off):
        PipelineSwap.build(rep, forest, runtime=_runtime(pipeline, tr),
                           warm_buckets=(8,))
    compact = CompactForest.from_dense(forest)
    PipelineSwap.build(rep, compact, runtime=_runtime(pipeline, on),
                       warm_buckets=())
    build_pipeline(rep, forest, rep.depth, use_kernel=False, tracer=on)
    row = on.layers()["spans"]["forest.compact"]
    assert row["calls"] == 1 and row["items"] == forest.n_trees
    assert row["total_ns"] > 0
    assert off.layers()["spans"] == {}


def test_lifecycles_close_once_at_resolve(pipeline, stream):
    _, st = stream
    tr = Tracer(capacity=1 << 16, sample=1.0)
    rt = _runtime(pipeline, tr)
    rt.drain(_ingest(rt, st)[0] + 1.0)
    assert tr.dropped == 0
    evs = [e for e in tr.events() if e.get("cat") == "flow"]
    begins = [e["id"] for e in evs if e["ph"] == "b"]
    ends = {e["id"]: e["ts"] for e in evs if e["ph"] == "e"}
    assert len(begins) == len([e for e in evs if e["ph"] == "e"]) > 0
    assert sorted(begins) == sorted(ends)
    for rec in rt.dispatcher.records:
        assert rec.resolved_ts is not None and rec.resolved_ts >= rec.flush_ts
        for fid in rec.flow_ids.tolist():
            assert ends[fid] == pytest.approx(rec.resolved_ts * 1e6)
    assert tr.chrome()["otherData"]["clock"] == "the caller's now"


def test_snapshot_carries_the_layer_table(pipeline, stream):
    _, st = stream
    obs = Observability(tracer=Tracer(sample=0.0))
    rt = _runtime(pipeline)
    obs.attach(rt)
    assert rt.table.tracer is obs.tracer
    _ingest(rt, st)
    doc = obs.snapshot(rt)
    assert doc["layers"]["spans"]["observe"]["items"] == st.n_events


def test_compiles_are_counted_while_enabled():
    from jax._src import dispatch

    assert trace_mod._COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT
    tr = Tracer(enabled=False)
    f = jax.jit(lambda x: x * 3 + 1)
    f(np.zeros(3, np.float32)).block_until_ready()
    assert trace_mod.COMPILES not in tr.layers()["counters"]
    tr.enabled = True
    f(np.zeros(5, np.float32)).block_until_ready()      # a new shape compiles
    f(np.zeros(5, np.float32)).block_until_ready()      # a cached one does not
    assert tr.layers()["counters"][trace_mod.COMPILES] == 1


def test_profiler_events_nest_in_annotations_around_the_call(pipeline, stream,
                                                             tmp_path):
    """The spans land on the profiler's host plane, on its clock: each
    ``cato.observe`` lies inside the ``bench.observe`` annotation a caller
    put around the same `observe_batch` call."""
    _, st = stream
    tr = Tracer(sample=0.0)
    rt = _runtime(pipeline, tr)
    inner = rt.table.observe_batch

    def annotated(*a):
        with jax.profiler.TraceAnnotation("bench.observe"):
            return inner(*a)

    rt.table.observe_batch = annotated
    jax.profiler.start_trace(str(tmp_path))
    try:
        for lo in range(0, 4 * BLOCK, BLOCK):
            rt.ingest_packets(*_block(st, lo, lo + BLOCK))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    ev = {"bench.observe": [], "cato.observe": []}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ev:
                        ev[e.name].append((int(e.start_ns), int(e.duration_ns)))
    outer, inner_ev = sorted(ev["bench.observe"]), sorted(ev["cato.observe"])
    assert len(inner_ev) == len(outer) == tr.layers()["spans"]["observe"]["calls"]
    for (s_o, d_o), (s_i, d_i) in zip(outer, inner_ev):
        assert s_o <= s_i and s_i + d_i <= s_o + d_o
