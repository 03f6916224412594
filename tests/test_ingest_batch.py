"""Vectorized ingest contracts: observe_batch ≡ observe, block flush timing
≡ per-packet flush timing, chunked replay ≡ the per-packet reference loop,
and staging-arena/donation safety under double-buffered dispatch.

These are the DESIGN.md §7 exactness guarantees: the fast path is a
performance rewrite, not a semantics change, so every comparison below is
equality (bitwise for table state and predictions), with latency allowed
float tolerance only where the vectorized Lindley recurrence reassociates
the scalar max-chain.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
except ImportError:  # seeded-sampling fallback, see tests/_hypothesis_shim.py
    from _hypothesis_shim import given, settings, strategies as hst

from repro.core.search_space import FeatureRep
from repro.serve.runtime import (
    FlowStatus,
    FlowTable,
    PacketStream,
    ReuseConfig,
    RuntimeMetrics,
    ServiceModel,
    ShardedRuntime,
    StreamingRuntime,
    replay,
)
from repro.serve.runtime.dispatch import _timeout_boundary
from repro.traffic import extract_features, make_dataset
from repro.traffic.models import train_traffic_model
from repro.traffic.pipeline import build_pipeline

DEPTH = 6


@pytest.fixture(scope="module")
def ds():
    return make_dataset("app-class", n_flows=300, max_pkts=24, seed=9)


@pytest.fixture(scope="module")
def stream(ds):
    return PacketStream.from_dataset(ds, seed=1)


@pytest.fixture(scope="module")
def pipeline(ds):
    rep = FeatureRep(
        ("dur", "s_load", "s_bytes_mean", "d_iat_std", "ack_cnt"), depth=DEPTH)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="rf-fast", seed=0)
    return build_pipeline(rep, forest, max_pkts=rep.depth, fused=True)


def _pkt_arrays(stream, lo, hi):
    fid = stream.fid[lo:hi]
    return dict(
        key=stream.key[fid], now=stream.base_t[lo:hi],
        rel_ts=stream.rel_ts32[lo:hi], size=stream.size[lo:hi],
        direction=stream.direction[lo:hi], ttl=stream.ttl[lo:hi],
        winsize=stream.winsize[lo:hi], flags_byte=stream.flags_byte[lo:hi],
        proto=stream.proto[fid], s_port=stream.s_port[fid],
        d_port=stream.d_port[fid], flow_id=fid, fin=stream.fin[lo:hi],
    )


def _drive_table(stream, *, capacity, pkt_depth, chunk, evict_at=()):
    """Feed the whole stream through a fresh table; chunk=0 -> scalar path."""
    ft = FlowTable(capacity, pkt_depth, idle_timeout_s=5.0,
                   metrics=RuntimeMetrics())
    E = stream.n_events
    evict_at = set(evict_at)
    if chunk == 0:
        for i in range(E):
            a = _pkt_arrays(stream, i, i + 1)
            ft.observe(int(a["key"][0]), float(a["now"][0]),
                       float(a["rel_ts"][0]), float(a["size"][0]),
                       int(a["direction"][0]), float(a["ttl"][0]),
                       float(a["winsize"][0]), int(a["flags_byte"][0]),
                       float(a["proto"][0]), float(a["s_port"][0]),
                       float(a["d_port"][0]), int(a["flow_id"][0]),
                       bool(a["fin"][0]))
            if i + 1 in evict_at:
                ft.evict_idle(float(a["now"][0]))
    else:
        for lo in range(0, E, chunk):
            hi = min(lo + chunk, E)
            a = _pkt_arrays(stream, lo, hi)
            ft.observe_batch(
                a["key"], a["now"], a["rel_ts"], a["size"], a["direction"],
                a["ttl"], a["winsize"], a["flags_byte"], a["proto"],
                a["s_port"], a["d_port"], a["flow_id"], a["fin"])
            for j in range(lo + 1, hi + 1):
                if j in evict_at:
                    ft.evict_idle(float(stream.base_t[j - 1]))
        # chunked eviction points must land on block boundaries to compare
    return ft


def _assert_tables_equal(a: FlowTable, b: FlowTable):
    assert (a.ctrl == b.ctrl).all()
    for f in ("ts", "size", "direction", "ttl", "winsize", "flags",
              "proto", "s_port", "d_port"):
        assert (getattr(a, f) == getattr(b, f)).all(), f
    assert a._free == b._free
    assert (a._buckets == b._buckets).all()
    assert a.metrics.summary() == b.metrics.summary()


@pytest.mark.parametrize("chunk", [1, 17, 256])
def test_observe_batch_state_equivalence(stream, chunk):
    """Full-stream table state is bitwise identical to the scalar loop for
    any chunking — payload, control block, hash index, free-list order,
    and metrics."""
    scalar = _drive_table(stream, capacity=512, pkt_depth=DEPTH, chunk=0)
    batch = _drive_table(stream, capacity=512, pkt_depth=DEPTH, chunk=chunk)
    _assert_tables_equal(scalar, batch)


def test_observe_batch_equivalence_under_overflow(stream):
    """A undersized table sheds flows; drop decisions (allocation order vs
    free-list state) must sequence exactly as the scalar path."""
    scalar = _drive_table(stream, capacity=24, pkt_depth=DEPTH, chunk=0)
    batch = _drive_table(stream, capacity=24, pkt_depth=DEPTH, chunk=64)
    assert scalar.metrics.drops_table > 0
    _assert_tables_equal(scalar, batch)


def test_observe_batch_equivalence_with_eviction(stream):
    """Idle eviction interleaved at chunk boundaries stays equivalent
    (evicted ACTIVE flows -> READY; PREDICTED reclaim; re-tenancy after)."""
    pts = (512, 1024, 2048)
    scalar = _drive_table(stream, capacity=256, pkt_depth=DEPTH, chunk=0,
                          evict_at=pts)
    batch = _drive_table(stream, capacity=256, pkt_depth=DEPTH, chunk=256,
                         evict_at=pts)
    _assert_tables_equal(scalar, batch)


def test_observe_batch_fin_close_and_retenancy_in_one_block():
    """The adversarial slow-path block: a flow completes, is marked
    PREDICTED, then within a single observe_batch block receives its
    bidirectional FIN close AND a re-tenancy of the same 5-tuple — the
    scalar interleaving (recycle before re-alloc) must be preserved."""
    def build(batch: bool):
        ft = FlowTable(4, pkt_depth=2, metrics=RuntimeMetrics())
        # fill to depth -> READY -> PREDICTED
        for i, t in enumerate((0.0, 0.1)):
            ft.observe(7, t, t, 100.0, i % 2, 64.0, 1000.0, 0x10,
                       6.0, 1.0, 2.0, 0, False)
        slot = ft._probe(7)[0]
        ft.mark_predicted(np.array([slot]))
        # block: FIN fwd, FIN rev (-> CLOSED, recycle), then the same key
        # returns (re-tenancy: must allocate a fresh tenancy, new flow_id)
        k = np.full(3, 7, np.uint64)
        t = np.array([0.2, 0.3, 0.4])
        dirn = np.array([0, 1, 0], np.uint8)
        fin = np.array([True, True, False])
        fids = np.array([0, 0, 1])
        args = (k, t, t.astype(np.float32), np.full(3, 99.0, np.float32),
                dirn, np.full(3, 64.0, np.float32),
                np.full(3, 1000.0, np.float32), np.full(3, 0x11, np.uint8),
                np.full(3, 6.0, np.float32), np.full(3, 1.0, np.float32),
                np.full(3, 2.0, np.float32), fids, fin)
        if batch:
            st, sl, acc = ft.observe_batch(*args)
        else:
            st = np.empty(3, np.uint8)
            sl = np.empty(3, np.int64)
            for i in range(3):
                s, q = ft.observe(int(k[i]), float(t[i]), float(t[i]), 99.0,
                                  int(dirn[i]), 64.0, 1000.0, 0x11, 6.0, 1.0,
                                  2.0, int(fids[i]), bool(fin[i]))
                st[i], sl[i] = int(s), q
        return ft, st, sl

    ft_s, st_s, sl_s = build(batch=False)
    ft_b, st_b, sl_b = build(batch=True)
    assert (st_s == st_b).all() and (sl_s == sl_b).all()
    _assert_tables_equal(ft_s, ft_b)
    assert st_s[1] == int(FlowStatus.CLOSED)          # bidirectional close
    assert st_s[2] == int(FlowStatus.TRACKED)          # fresh tenancy
    assert ft_b.ctrl["flow_id"][sl_b[2]] == 1


def test_ingest_packets_flush_timing_equivalence(pipeline, stream):
    """Block ingest fires the same flushes (order, reason, now, members)
    as the per-packet cadence, including timeout flushes triggered by
    packets that enqueue nothing."""
    def run(block: int):
        rt = StreamingRuntime(pipeline, capacity=1024, max_batch=32,
                              min_bucket=8, flush_timeout_s=0.02,
                              execute=False)
        E = stream.n_events
        if block == 0:
            for i in range(E):
                a = _pkt_arrays(stream, i, i + 1)
                rt.ingest_packet(
                    int(a["key"][0]), float(a["now"][0]), float(a["rel_ts"][0]),
                    float(a["size"][0]), int(a["direction"][0]),
                    float(a["ttl"][0]), float(a["winsize"][0]),
                    int(a["flags_byte"][0]), float(a["proto"][0]),
                    float(a["s_port"][0]), float(a["d_port"][0]),
                    int(a["flow_id"][0]), bool(a["fin"][0]))
        else:
            for lo in range(0, E, block):
                hi = min(lo + block, E)
                a = _pkt_arrays(stream, lo, hi)
                rt.ingest_packets(
                    a["key"], a["now"], a["rel_ts"], a["size"],
                    a["direction"], a["ttl"], a["winsize"], a["flags_byte"],
                    a["proto"], a["s_port"], a["d_port"], a["flow_id"],
                    a["fin"])
        rt.drain(float(stream.base_t[-1]) + 1.0)
        return rt.dispatcher.records

    want = run(0)
    got = run(200)
    assert len(want) == len(got)
    for w, g in zip(want, got):
        assert (w.bucket, w.n_real, w.reason, w.flush_ts) == \
            (g.bucket, g.n_real, g.reason, g.flush_ts)
        assert (w.flow_ids == g.flow_ids).all()
        assert (w.ready_ts == g.ready_ts).all()


def test_ingest_packets_equivalent_under_table_pressure(pipeline, stream):
    """Flush side effects land mid-block: with a tiny table and small
    max_batch, full flushes recycle closed flows' slots while the block is
    still streaming in — drop accounting and re-tenancy must still match
    the per-packet cadence exactly (the sub-block bound pins every flush
    to the packet that triggered it)."""
    def run(block: int):
        rt = StreamingRuntime(pipeline, capacity=16, max_batch=8,
                              min_bucket=8, flush_timeout_s=0.02,
                              execute=False)
        E = stream.n_events
        step = block if block else 1
        for lo in range(0, E, step):
            hi = min(lo + step, E)
            a = _pkt_arrays(stream, lo, hi)
            if block:
                rt.ingest_packets(
                    a["key"], a["now"], a["rel_ts"], a["size"],
                    a["direction"], a["ttl"], a["winsize"], a["flags_byte"],
                    a["proto"], a["s_port"], a["d_port"], a["flow_id"],
                    a["fin"])
            else:
                rt.ingest_packet(
                    int(a["key"][0]), float(a["now"][0]), float(a["rel_ts"][0]),
                    float(a["size"][0]), int(a["direction"][0]),
                    float(a["ttl"][0]), float(a["winsize"][0]),
                    int(a["flags_byte"][0]), float(a["proto"][0]),
                    float(a["s_port"][0]), float(a["d_port"][0]),
                    int(a["flow_id"][0]), bool(a["fin"][0]))
        rt.drain(float(stream.base_t[-1]) + 1.0)
        return rt

    want = run(0)
    got = run(256)
    assert want.metrics.drops_table > 0          # pressure actually happened
    assert want.metrics.summary() == got.metrics.summary()
    wrec, grec = want.dispatcher.records, got.dispatcher.records
    assert len(wrec) == len(grec)
    for w, g in zip(wrec, grec):
        assert (w.bucket, w.n_real, w.reason, w.flush_ts) == \
            (g.bucket, g.n_real, g.reason, g.flush_ts)
        assert (w.flow_ids == g.flow_ids).all()
    _assert_tables_equal(want.table, got.table)


def test_mid_block_flush_recycling_frees_slots_for_later_packets(pipeline):
    """The adversarial case for deferred flush side effects: flows close
    (bidirectional FIN) *before* the full flush that retires them, so
    `mark_predicted` recycles their slots mid-block — and later packets of
    the same block need those slots. Block ingest must admit exactly the
    flows the per-packet cadence admits."""
    depth = DEPTH  # pipeline pkt_depth

    def seq():
        pkts = []  # (key, fid, direction, fin)
        for f in range(4):          # flows A..D: depth pkts, then 2 FINs
            for p in range(depth):
                pkts.append((100 + f, f, p % 2, False))
            if f < 3:               # A,B,C close before the flush fires
                pkts.append((100 + f, f, 0, True))
                pkts.append((100 + f, f, 1, True))
        # D's depth-th packet above made the queue hit max_batch=4 -> full
        # flush; A,B,C had fin_mask==3, so their slots recycle there.
        for f in range(4, 7):       # E,F,G need the freed slots
            pkts.append((200 + f, f, 0, False))
        return pkts

    def run(block: bool):
        rt = StreamingRuntime(pipeline, capacity=4, max_batch=4,
                              min_bucket=4, flush_timeout_s=10.0,
                              execute=False)
        pkts = seq()
        n = len(pkts)
        key = np.array([p[0] for p in pkts], np.uint64)
        t = np.arange(n, dtype=np.float64) * 1e-4
        dirn = np.array([p[2] for p in pkts], np.uint8)
        fin = np.array([p[3] for p in pkts])
        fid = np.array([p[1] for p in pkts], np.int64)
        ones = np.ones(n, np.float32)
        if block:
            rt.ingest_packets(key, t, t.astype(np.float32), ones * 99, dirn,
                              ones * 64, ones * 1000,
                              np.full(n, 0x10, np.uint8), ones * 6, ones,
                              ones * 2, fid, fin)
        else:
            for i in range(n):
                rt.ingest_packet(int(key[i]), float(t[i]), float(t[i]), 99.0,
                                 int(dirn[i]), 64.0, 1000.0, 0x10, 6.0, 1.0,
                                 2.0, int(fid[i]), bool(fin[i]))
        return rt

    want = run(False)
    got = run(True)
    assert want.metrics.drops_table == 0     # scalar cadence admits E,F,G
    assert want.metrics.flows_seen == 7
    assert got.metrics.summary() == want.metrics.summary()
    _assert_tables_equal(want.table, got.table)


def test_chunked_replay_matches_per_packet_reference(pipeline, stream):
    """The production replay (vectorized admission + Lindley recurrence)
    reproduces a straight per-packet reference loop: same drops, same
    batches, same predictions, latency equal to float tolerance."""
    from collections import deque

    svc = ServiceModel.modeled(pipeline.rep, pipeline.forest)
    def mk(execute=True):
        return StreamingRuntime(
            pipeline, capacity=1024, max_batch=64, execute=execute)


    stats = replay(stream, mk, stream.base_pps, svc)

    # reference: the scalar driver (pre-vectorization semantics)
    rt = mk(True)
    m = rt.metrics
    busy_ingest = busy_infer = 0.0
    ring = deque()
    lat = []
    t_e = stream.base_t * 1.0  # offered = base rate -> no compression

    def on_batches(recs):
        nonlocal busy_ingest, busy_infer
        for rec in recs:
            busy_ingest += svc.submit_ns(rec.n_real) * 1e-9
            done = max(rec.flush_ts, busy_infer) + svc.batch_ns(rec.bucket) * 1e-9
            busy_infer = done
            lat.extend(done - rec.ready_ts)

    t = 0.0
    for i in range(stream.n_events):
        t = t_e[i]
        while ring and ring[0] <= t:
            ring.popleft()
        if len(ring) >= 4096:
            m.pkts_total += 1
            m.drops_ring += 1
            continue
        f = int(stream.fid[i])
        a0 = m.pkts_accumulated
        _, recs = rt.ingest_packet(
            int(stream.key[f]), t, float(stream.rel_ts32[i]),
            float(stream.size[i]), int(stream.direction[i]),
            float(stream.ttl[i]), float(stream.winsize[i]),
            int(stream.flags_byte[i]), float(stream.proto[f]),
            float(stream.s_port[f]), float(stream.d_port[f]), f,
            bool(stream.fin[i]))
        busy_ingest = max(t, busy_ingest) + svc.packet_ns(
            m.pkts_accumulated > a0) * 1e-9
        ring.append(busy_ingest)
        on_batches(recs)
        if (i + 1) % 512 == 0:
            on_batches(rt.poll(t))
    on_batches(rt.drain(t + rt.dispatcher.flush_timeout_s))

    assert stats.drops == m.drops
    assert stats.metrics.batches == m.batches
    assert stats.metrics.flows_predicted == m.flows_predicted
    assert stats.predictions == dict(rt.results)
    assert stats.latency_p99_s == pytest.approx(
        float(np.percentile(lat, 99)), rel=1e-9)


def test_replay_fallback_path_on_saturation(pipeline, stream):
    """Above saturation the admission bound fails, the per-packet fallback
    engages, and drops are counted — the bisection's upper bracket."""
    svc = ServiceModel.modeled(pipeline.rep, pipeline.forest)
    def mk(execute=True):
        return StreamingRuntime(
            pipeline, capacity=512, max_batch=64, execute=execute)

    # drive far past the ingest lane's modeled service rate so the ring
    # must overflow regardless of the calibrated constants
    sat_pps = 4e9 / max(svc.pkt_track_ns, 1e-3)
    hot = replay(stream, lambda: mk(False), max(sat_pps, stream.base_pps), svc,
                 ring_capacity=256)
    assert hot.drops > 0
    cool = replay(stream, lambda: mk(False), stream.base_pps, svc,
                  ring_capacity=256)
    assert cool.drops == 0


def test_arena_rotation_protects_pending_batches(pipeline, stream, ds):
    """Donation/zero-copy safety: with double-buffered dispatch the staging
    arenas rotate max_pending+1 deep, so overwriting the next batch cannot
    corrupt an in-flight one — streaming predictions stay bit-identical to
    the batch pipeline."""
    disp = StreamingRuntime(pipeline, capacity=64, max_batch=16).dispatcher
    arenas = [disp.gather(np.arange(4), 16) for _ in range(4)]
    ids = [id(a.ts) for a in arenas]
    assert len(set(ids[:3])) == 3          # max_pending+1 distinct arenas
    assert ids[3] == ids[0]                # then the rotation wraps

    svc = ServiceModel.modeled(pipeline.rep, pipeline.forest)
    stats = replay(
        stream,
        lambda execute=True: StreamingRuntime(
            pipeline, capacity=1024, max_batch=32, max_pending=2,
            execute=execute),
        stream.base_pps, svc)
    assert stats.drops == 0
    batch_preds = pipeline(ds.truncate(DEPTH))
    stream_preds = np.array([stats.predictions[i] for i in range(ds.n_flows)])
    assert (stream_preds == batch_preds).all()


# ---------------------------------------------------------------------------
# sub-block cuts at the READY packets that can fill the queue
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plain_pipeline(ds):
    # incremental features (reuse can engage) and no Pallas kernel: these
    # tests run many flushes, and what they check is the cadence
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                      "ack_cnt"), depth=DEPTH)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="tree-fast", seed=0)
    return build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)


def _flows(seed, *, n_flows, mean_len, n_keys=None, both_fin=0.0,
           one_fin=0.0, trail=0, span=2.0):
    """Interleaved packets of `n_flows` flows, delivery-ordered, as the
    keyword arguments of `ingest_packets`. Flow i uses key i % n_keys (so
    keys are reused by later flows); a `both_fin` share ends with a FIN
    each way and a `one_fin` share with one FIN, followed by `trail` more
    packets of the same key."""
    rng = np.random.default_rng(seed)
    n_keys = n_keys or n_flows
    keys = rng.integers(1, 2 ** 63, n_keys).astype(np.uint64)
    start = np.sort(rng.random(n_flows)) * span
    cols = {c: [] for c in ("key", "t", "rel", "dir", "fin", "fid")}
    for f in range(n_flows):
        n = 1 + int(rng.exponential(mean_len))
        u = rng.random()
        fin = np.zeros(n, bool)
        dirn = rng.integers(0, 2, n)
        if u < both_fin and n >= 2:
            fin[-2:] = True
            dirn[-2:] = rng.permutation(2)
        elif u < both_fin + one_fin:
            fin[-1] = True
        if fin.any() and trail:
            fin = np.append(fin, np.zeros(trail, bool))
            dirn = np.append(dirn, rng.integers(0, 2, trail))
            n += trail
        rel = np.cumsum(rng.exponential(span / 50, n))
        cols["key"].append(np.full(n, keys[f % n_keys]))
        cols["t"].append(start[f] + rel)
        cols["rel"].append(rel - rel[0])
        cols["dir"].append(dirn)
        cols["fin"].append(fin)
        cols["fid"].append(np.full(n, f))
    c = {k: np.concatenate(v) for k, v in cols.items()}
    o = np.argsort(c["t"], kind="stable")
    E = o.size
    return dict(
        key=c["key"][o], now=c["t"][o], rel_ts=c["rel"][o].astype(np.float32),
        size=rng.integers(40, 1500, E).astype(np.float32),
        direction=c["dir"][o].astype(np.uint8),
        ttl=rng.integers(30, 128, E).astype(np.float32),
        winsize=rng.integers(0, 65535, E).astype(np.float32),
        flags_byte=rng.integers(0, 256, E).astype(np.uint8),
        proto=np.full(E, 6.0, np.float32),
        s_port=(c["fid"][o] % 50000 + 1024).astype(np.float32),
        d_port=np.full(E, 443.0, np.float32),
        flow_id=c["fid"][o].astype(np.int64), fin=c["fin"][o],
    )


_COLS = ("key", "now", "rel_ts", "size", "direction", "ttl", "winsize",
         "flags_byte", "proto", "s_port", "d_port", "flow_id", "fin")


def _feed(rt, pk, block, lo=0, hi=None):
    """Ingest packets [lo, hi) in blocks of `block` (0: per packet, through
    `ingest_packet`); returns the per-packet statuses."""
    hi = len(pk["now"]) if hi is None else hi
    if block == 0:
        st = np.empty(hi - lo, np.uint8)
        for i in range(lo, hi):
            s, _ = rt.ingest_packet(
                int(pk["key"][i]), float(pk["now"][i]), float(pk["rel_ts"][i]),
                float(pk["size"][i]), int(pk["direction"][i]),
                float(pk["ttl"][i]), float(pk["winsize"][i]),
                int(pk["flags_byte"][i]), float(pk["proto"][i]),
                float(pk["s_port"][i]), float(pk["d_port"][i]),
                int(pk["flow_id"][i]), bool(pk["fin"][i]))
            st[i - lo] = int(s)
        return st
    out = []
    for b in range(lo, hi, block):
        e = min(b + block, hi)
        st, _, _ = rt.ingest_packets(*(pk[c][b:e] for c in _COLS))
        out.append(st)
    return np.concatenate(out)


def _records(recs):
    return [(r.flow_ids.tolist(), r.ready_ts.tolist(), r.flush_ts, r.bucket,
             r.n_real, r.reason) for r in recs]


def _assert_same_runtime(want: StreamingRuntime, got: StreamingRuntime):
    if want.table.reuse:
        want.table.flush_agg()
        got.table.flush_agg()
    assert _records(want.dispatcher.records) == _records(got.dispatcher.records)
    assert want.results.keys() == got.results.keys()
    for k, v in want.results.items():
        assert np.array_equal(v, got.results[k]), k
    _assert_tables_equal(want.table, got.table)


class _Spy(StreamingRuntime):
    """Counts `observe_batch` calls and the sub-block ends that the timeout
    bound set, and checks the invariant of the cut: every flush fires at
    the final packet of a sub-block."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.calls = 0
        self.timeout_cuts = 0
        self.ends: set = set()
        inner = self.table.observe_batch

        def counted(*args):
            self.calls += 1
            return inner(*args)

        self.table.observe_batch = counted

    def _ingest_packets(self, *cols):
        self.ends = set()
        statuses, acc, recs = super()._ingest_packets(*cols)
        for r in recs:
            if r.reason != "refresh":
                assert r.flush_idx in self.ends, "a flush inside a sub-block"
        return statuses, acc, recs

    def _sub_block_end(self, now, lo, key, direction, fin):
        hi = super()._sub_block_end(now, lo, key, direction, fin)
        self.ends.add(hi - 1)
        disp = self.dispatcher
        ref = disp._queue.head_ready() if len(disp._queue) else float(now[lo])
        if hi < len(now) and hi == _timeout_boundary(
                now, lo, len(now), ref, disp.flush_timeout_s) + 1:
            self.timeout_cuts += 1
        return hi


_CADENCE_CASES = {
    # long blocks, ~1 READY packet in 60: a handful of flushes a block
    "saturated": dict(
        flows=dict(n_flows=500, mean_len=60), block=4096,
        rt=dict(capacity=1024, max_batch=32, flush_timeout_s=10.0)),
    # closes from both sides, then the key returns (re-tenancy) after the
    # mid-block flush that retires the first tenancy
    "fin_both_sides_retenancy": dict(
        flows=dict(n_flows=400, mean_len=12, n_keys=150, both_fin=0.6,
                   one_fin=0.2, trail=3), block=1024,
        rt=dict(capacity=1024, max_batch=16, flush_timeout_s=0.05)),
    # flows reach depth, sit in the queue, close there (fin_mask == 3 while
    # READY), and get more packets after the flush that recycles them
    "closed_while_queued": dict(
        flows=dict(n_flows=300, mean_len=4, both_fin=0.9, trail=6),
        block=2048, rt=dict(capacity=1024, max_batch=64,
                            flush_timeout_s=10.0)),
    # a table far smaller than the live set: drops, and recycling that
    # frees slots mid-block
    "table_pressure": dict(
        flows=dict(n_flows=300, mean_len=15, n_keys=200, both_fin=0.5,
                   one_fin=0.3, trail=2), block=512,
        rt=dict(capacity=24, max_batch=8, flush_timeout_s=0.02)),
    # one-packet flows at depth 1: every packet is READY, so the plan
    # marks every packet and a block of room + 1 must be cut at the room-th
    "every_packet_ready": dict(
        flows=dict(n_flows=300, mean_len=0), block=9,
        rt=dict(capacity=64, max_batch=8, flush_timeout_s=10.0,
                pkt_depth=1, execute=False)),
    # reuse on: PREDICTED flows take the frozen fast path
    "reuse": dict(
        flows=dict(n_flows=300, mean_len=40, n_keys=250, both_fin=0.3,
                   one_fin=0.3, trail=2), block=2048,
        rt=dict(capacity=1024, max_batch=16, flush_timeout_s=0.05,
                reuse=ReuseConfig(drift_threshold=0.05,
                                  refresh_every=10 ** 6))),
}


@pytest.mark.parametrize("case", sorted(_CADENCE_CASES))
def test_block_ingest_matches_per_packet_cadence(plain_pipeline, case):
    """Block ingest under the READY-potential cut gives the per-packet
    cadence's statuses, flushes, classes, table and metrics, exactly."""
    c = _CADENCE_CASES[case]
    pk = _flows(11, **c["flows"])
    runs = {}
    for block in (0, c["block"]):
        rt = _Spy(plain_pipeline, min_bucket=8, **c["rt"])
        st = _feed(rt, pk, block)
        rt.drain(float(pk["now"][-1]) + 1.0)
        runs[block] = (st, rt)
    (st_w, want), (st_g, got) = runs[0], runs[c["block"]]
    assert (st_w == st_g).all()
    _assert_same_runtime(want, got)
    n_ready = int(np.isin(st_w, [int(FlowStatus.READY),
                                 int(FlowStatus.READY_EOF)]).sum())
    assert want.metrics.batches > 2 and n_ready > 0
    if case == "table_pressure":
        assert want.metrics.drops_table > 0
    if case in ("fin_both_sides_retenancy", "closed_while_queued"):
        assert (st_w == int(FlowStatus.CLOSED)).any() or \
            want.metrics.slots_recycled > 0
    if case == "reuse":
        assert got.last_frozen_mask is not None


def test_sharded_block_ingest_matches_per_packet_cadence(plain_pipeline):
    """Each shard's block ingest under the new cut equals its per-packet
    cadence on the packets steered to it."""
    pk = _flows(12, n_flows=400, mean_len=20, n_keys=300, both_fin=0.4,
                one_fin=0.3, trail=2)
    shard = (pk["key"] % np.uint64(3)).astype(np.int64)
    srt = ShardedRuntime(plain_pipeline, n_shards=3, capacity=300,
                         max_batch=16, min_bucket=8, flush_timeout_s=0.05)
    srt.shards = [_Spy(plain_pipeline, **srt._worker_kwargs)
                  for _ in srt.shards]
    for lo in range(0, len(pk["now"]), 1024):
        hi = min(lo + 1024, len(pk["now"]))
        srt.ingest_packets(*(pk[c][lo:hi] for c in _COLS), shard=shard[lo:hi])
    end = float(pk["now"][-1]) + 1.0
    srt.drain(end)
    for i, got in enumerate(srt.shards):
        want = StreamingRuntime(plain_pipeline, capacity=100, max_batch=16,
                                min_bucket=8, flush_timeout_s=0.05)
        sub = {c: pk[c][shard == i] for c in _COLS}
        _feed(want, sub, 0)
        want.drain(end)
        assert want.metrics.batches > 2
        _assert_same_runtime(want, got)


class _RoomCut(_Spy):
    """The former bound: at most `max_batch - len(queue)` packets a
    sub-block, as if every packet could make a flow READY."""

    def _sub_block_end(self, now, lo, key, direction, fin):
        disp = self.dispatcher
        B = len(now)
        ref = disp._queue.head_ready() if len(disp._queue) else float(now[lo])
        k = _timeout_boundary(now, lo, B, ref, disp.flush_timeout_s)
        hi = min(B, lo + disp.max_batch - len(disp._queue), k + 1)
        self.ends.add(hi - 1)
        return hi


def test_fin_free_active_blocks_cut_only_at_flushes(plain_pipeline):
    """FIN-free traffic on ACTIVE flows: the plan is exact, so a block makes
    at most one `observe_batch` call, plus one per flush and per timeout
    cut, where the former bound made one per `max_batch` packets."""
    pk = _flows(13, n_flows=600, mean_len=80, span=4.0)
    # seed the table: every flow's first packet, outside the spied blocks
    first = np.unique(pk["flow_id"], return_index=True)[1]
    rest = np.setdiff1d(np.arange(len(pk["now"])), first)
    seed = {c: pk[c][np.sort(first)] for c in _COLS}
    body = {c: pk[c][rest] for c in _COLS}
    rt = _Spy(plain_pipeline, capacity=1024, max_batch=32, min_bucket=8,
              flush_timeout_s=1.0, execute=False)
    _feed(rt, seed, 1 << 20)
    n_blocks = 0
    for lo in range(0, len(body["now"]), 4096):
        hi = min(lo + 4096, len(body["now"]))
        calls0, cuts0, recs0 = rt.calls, rt.timeout_cuts, \
            len(rt.dispatcher.records)
        _feed(rt, body, 4096, lo, hi)
        flushes = len(rt.dispatcher.records) - recs0
        assert rt.calls - calls0 <= 1 + flushes + rt.timeout_cuts - cuts0
        n_blocks += 1
    assert rt.metrics.flushes_full > n_blocks   # the queue filled mid-block
    assert rt.calls < len(body["now"]) / 32


@settings(max_examples=12, deadline=None)
@given(seed=hst.integers(min_value=0, max_value=2 ** 31 - 1))
def test_ready_cut_never_makes_more_calls_than_the_room_cut(plain_pipeline,
                                                            seed):
    """On random traffic (closes, re-tenancy, drops, timeouts), the new cut
    gives the former bound's outcome with no more `observe_batch` calls."""
    rng = np.random.default_rng(seed)
    pk = _flows(seed, n_flows=int(rng.integers(20, 200)),
                mean_len=float(rng.uniform(2, 30)),
                n_keys=int(rng.integers(10, 200)),
                both_fin=float(rng.uniform(0, 0.7)),
                one_fin=float(rng.uniform(0, 0.3)),
                trail=int(rng.integers(0, 4)),
                span=float(rng.uniform(0.05, 2.0)))
    kw = dict(capacity=int(rng.integers(8, 256)),
              max_batch=int(2 ** rng.integers(1, 6)), min_bucket=2,
              flush_timeout_s=float(rng.choice([0.005, 0.05, 10.0])),
              execute=False)
    block = int(rng.integers(16, 2048))
    runs = []
    for cls in (_RoomCut, _Spy):
        rt = cls(plain_pipeline, **kw)
        statuses = _feed(rt, pk, block)
        rt.drain(float(pk["now"][-1]) + 1.0)
        runs.append((statuses, rt))
    (st_old, old), (st_new, new) = runs
    assert (st_old == st_new).all()
    _assert_same_runtime(old, new)
    assert new.calls <= old.calls
