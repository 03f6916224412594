#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip.

    python chip_smoke.py

One process drives the system through the entry points a user calls, at
the widest model the repo serves (the iot-class use case: 28 classes, a
25-tree random forest):

1. ``make_dataset("iot-class")`` -> ``train_traffic_model(model="rf")`` ->
   ``build_pipeline(..., fused=True)``, warmed at every dispatch bucket;
2. the held-out half (2,500 flows) streams as a `PacketStream` through
   ``StreamingRuntime(execute=True).ingest_packets`` -> micro-batch
   dispatcher -> the fused extract+infer kernel;
3. one ``predict_agg`` batch (the aggregate kernel) and one 3-tenant
   ``build_multi_tenant_pipeline(fused=True)`` batch (the multi-forest
   kernel), so every served kernel runs on the chip.

Each result is checked against the float32 reference,
``build_pipeline(use_kernel=False)`` on the host CPU device: every flow
gets the same class, and probabilities agree within 1e-5. The fused
entry's compiled program must hold a Mosaic kernel (``tpu_custom_call``).
Times printed are host wall-clock seconds on the named device, for
information. The last line is ``{"ok": true, "device": {...}}``; any
failed check, or a first JAX device that is not a TPU, exits non-zero
without it.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ATOL = 1e-5
MAX_BATCH = 256
MIN_BUCKET = 8
INGEST_CHUNK = 4096
# packet times are the trace's delivery times compressed this many times
# (~38k packets/s offered instead of ~190), so flushes fill several buckets
SPEEDUP = 200.0
# one feature per emitter family: iat, median, handshake, flag counts,
# plus duration, load, counts, plain stats and metadata
FEATURES = ("dur", "s_load", "d_pkt_cnt", "s_bytes_mean", "d_bytes_med",
            "s_iat_mean", "d_iat_std", "tcp_rtt", "syn_ack", "ack_cnt",
            "psh_cnt", "s_ttl_max", "d_winsize_min", "proto", "d_port")
DEPTH = 20
# the other two tenants of the multi-tenant batch; no medians, so the
# first also has the aggregate (incremental) entry
TENANTS = ((("dur", "s_bytes_mean", "d_bytes_max", "s_iat_max", "ack_dat",
             "fin_cnt", "s_port"), 12, "tree"),
           (("d_load", "s_bytes_std", "d_iat_mean", "syn_cnt", "s_ttl_min"),
            16, "rf-fast"))


def _compare(name, probs, ref_probs, classes, ref_classes, failures):
    """Print one agreement line; record a failure on any mismatch."""
    mism = int(np.sum(np.asarray(classes) != np.asarray(ref_classes)))
    dp = float(np.max(np.abs(probs - ref_probs))) if probs.size else 0.0
    print(f"{name}: {len(ref_classes)} flows, class mismatches {mism}, "
          f"max |p - p_ref| {dp:.3g} (limit {ATOL:g})")
    if mism or not np.all(np.isfinite(probs)) or dp > ATOL \
            or probs.shape != ref_probs.shape:
        failures.append(name)


def run(device_label: str) -> list[str]:
    """Drive the served path on JAX's default device; returns the names
    of the checks that failed."""
    import jax

    from repro.core import FeatureRep
    from repro.core.forest import CompactForest
    from repro.kernels.fused_pipeline import fused_forest_infer
    from repro.serve import (FlowTable, PacketStream, StreamingRuntime,
                             build_multi_tenant_pipeline)
    from repro.traffic import extract_features, make_dataset
    from repro.traffic.extraction import stats_plan
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline

    failures: list[str] = []
    cpu = jax.devices("cpu")[0]
    ds = make_dataset("iot-class", n_flows=5000, seed=0)
    train, test = ds.split(test_frac=0.5, seed=0)
    reps = [FeatureRep(FEATURES, depth=DEPTH)] + [
        FeatureRep(f, depth=d) for f, d, _ in TENANTS]
    models = ["rf"] + [m for _, _, m in TENANTS]

    # training and every reference run on the host CPU device
    with jax.default_device(cpu):
        forests = [train_traffic_model(
            extract_features(train, r.features, r.depth), train.label,
            model=m, seed=0)[0] for r, m in zip(reps, models)]
        refs = [build_pipeline(r, f, max_pkts=r.depth, use_kernel=False)
                for r, f in zip(reps, forests)]
        view = test.truncate(DEPTH)
        p_ref = refs[0].probabilities(view)
        y_ref = refs[0].finalize(p_ref)
    rep, forest = reps[0], forests[0]
    print(f"model: iot-class rf, {forest.n_trees} trees of depth "
          f"{forest.depth}, {forest.n_out} classes; {len(FEATURES)} "
          f"features at packet depth {DEPTH}")

    # -- served path: stream -> StreamingRuntime -> fused kernel -----------
    served = build_pipeline(rep, forest, max_pkts=DEPTH, fused=True)
    buckets = [MIN_BUCKET << i
               for i in range((MAX_BATCH // MIN_BUCKET).bit_length())]
    t0 = time.perf_counter()
    served.warm(buckets)
    compile_s = time.perf_counter() - t0
    stream = PacketStream.from_dataset(test, seed=0)
    rt = StreamingRuntime(served, capacity=8192, max_batch=MAX_BATCH,
                          min_bucket=MIN_BUCKET, execute=True)
    fid = stream.fid
    now = stream.base_t / SPEEDUP
    t0 = time.perf_counter()
    for lo in range(0, stream.n_events, INGEST_CHUNK):
        s = slice(lo, lo + INGEST_CHUNK)
        f = fid[s]
        rt.ingest_packets(
            stream.key[f], now[s], stream.rel_ts32[s],
            stream.size[s], stream.direction[s], stream.ttl[s],
            stream.winsize[s], stream.flags_byte[s], stream.proto[f],
            stream.s_port[f], stream.d_port[f], f, stream.fin[s])
    rt.drain(float(now[-1]) + 1.0)
    serve_s = time.perf_counter() - t0
    m = rt.metrics
    print(f"buckets warmed {buckets}; buckets served "
          f"{sorted(b for b, _ in m.shapes_seen)}; batches {m.batches}")
    print(f"compile_s {compile_s:.3f} (warm {len(buckets)} buckets, host "
          f"wall clock, {device_label})")
    print(f"serve_s {serve_s:.3f} ({stream.n_flows} flows, "
          f"{stream.n_events} packets offered over {float(now[-1]):.3f} s of "
          f"trace time; host wall clock, {device_label})")
    missing = [i for i in range(test.n_flows) if i not in rt.results]
    if missing:
        print(f"served: {len(missing)} flows got no prediction")
        failures.append("served flows")
    y_served = np.asarray([rt.results.get(i, -1) for i in range(test.n_flows)])
    # the same flows through the fused kernel in batch mode give the
    # probabilities that the class check alone cannot see
    p_served = np.concatenate([
        served.probabilities(view.take(np.arange(lo, min(lo + MAX_BATCH,
                                                         test.n_flows))))
        for lo in range(0, test.n_flows, MAX_BATCH)])
    _compare("served stream (fused kernel)", p_served, p_ref, y_served,
             y_ref, failures)

    # -- the fused entry is a Mosaic kernel, not an interpreter -----------
    ex = view.take(np.arange(MAX_BATCH))
    compact = CompactForest.from_dense(forest)
    text = fused_forest_infer.lower(
        ex.ts, ex.size, ex.direction, ex.ttl, ex.winsize, ex.flags,
        ex.flow_len, ex.proto, ex.s_port, ex.d_port,
        compact.nodes, compact.leaf,
        plan=stats_plan(rep.features), depth=DEPTH,
        widths=compact.widths).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    print(f"fused entry compiled with tpu_custom_call: {has_kernel}")
    if not has_kernel:
        failures.append("tpu_custom_call")

    # -- aggregate kernel: predict_agg on running-statistic rows -----------
    rep_b, forest_b = reps[1], forests[1]
    served_b = build_pipeline(rep_b, forest_b, max_pkts=rep_b.depth,
                              fused=True)
    table = FlowTable(8192, rep_b.depth, track_agg=True)
    n_ev = min(stream.n_events, 20000)
    f = fid[:n_ev]
    table.observe_batch(
        stream.key[f], stream.base_t[:n_ev], stream.rel_ts32[:n_ev],
        stream.size[:n_ev], stream.direction[:n_ev], stream.ttl[:n_ev],
        stream.winsize[:n_ev], stream.flags_byte[:n_ev], stream.proto[f],
        stream.s_port[f], stream.d_port[f], f, stream.fin[:n_ev])
    table.flush_agg()
    slots = np.flatnonzero(table.ctrl["state"] != 0)[:MAX_BATCH]
    args = (table.agg[slots], table.proto[slots], table.s_port[slots],
            table.d_port[slots])
    p_agg = np.asarray(served_b.predict_agg(*args))
    with jax.default_device(cpu):
        p_agg_ref = np.asarray(refs[1].predict_agg(*args))
    _compare("predict_agg (aggregate kernel)", p_agg, p_agg_ref,
             served_b.finalize(p_agg), refs[1].finalize(p_agg_ref), failures)

    # -- multi-tenant kernel: 3 tenants, one launch ------------------------
    mt = build_multi_tenant_pipeline(reps, forests, fused=True)
    uview = test.truncate(mt.rep.depth).take(np.arange(MAX_BATCH))
    p_mt = mt.probabilities(uview)
    with jax.default_device(cpu):
        mt_ref = build_multi_tenant_pipeline(reps, forests, use_kernel=False)
        p_mt_ref = mt_ref.probabilities(uview)
    _compare("3-tenant batch (multi-forest kernel)", p_mt, p_mt_ref,
             mt.finalize(p_mt), mt_ref.finalize(p_mt_ref), failures)
    return failures


def main() -> int:
    from repro.compile_cache import enable

    cache_dir = enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 1
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    label = f"{dev.platform} {dev.device_kind}"
    print(f"device: {label}, {len(jax.devices())} chip(s)")
    failures = run(label)
    print(f"compile cache {cache_dir}: {cache['hits']} hits, "
          f"{cache['misses']} misses")
    if failures:
        print(f"chip_smoke: FAILED {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
