"""Streaming-runtime benchmark: measured zero-loss throughput (Fig. 5c).

Drives `fig5_serving_perf.run_replayed` — CATO Pareto points vs the
ALL/MI10/RFE10 baselines, each measured by offered-load replay through
`repro.serve.runtime` with bisection to the highest zero-drop rate — and
records the result as a machine-readable `results/BENCH_runtime.json`
datapoint (with a repo-root symlink alias for legacy readers) so the perf
trajectory is tracked across PRs.

With `--shards N` every point is measured against an RSS-steered
`ShardedRuntime` (DESIGN.md §8): rows carry a `shard` column — "agg" for
the aggregate zero-loss rate, 0..N-1 for the per-worker breakdown — and
`--min-speedup R --single PATH` gates the aggregate median against a
1-shard datapoint measured with the same config (the CI bench job uses
this to enforce that 4 workers actually buy >= 2x).

With `--scenario {uniform,zipf,burst,drift}` the replayed trace is one of
the adversarial workloads (`repro.traffic.synth.SCENARIOS`); rows carry a
`scenario` column so the perf trajectory covers non-uniform load. A
non-uniform scenario with `--shards N` measures every point twice —
static RETA vs the adaptive control plane — and `--skew-gate` asserts
the control plane earns its keep: strictly lower `load_imbalance` than
the static fleet and no lower median zero-loss pps (DESIGN.md §9).

With `--trace PATH` the benchmark instead runs ONE fully instrumented
replay (4-shard zipf under the control plane by default) and writes the
unified observability artifacts from that single run (DESIGN.md §11):
a Chrome-loadable trace at PATH (chrome://tracing / Perfetto), a
per-stage latency-breakdown table and merged fleet metrics snapshot
under `results/`, and the control plane's decision audit log as JSONL.
The snapshot's counter totals are asserted bit-identical to the
runtime's own `RuntimeMetrics` accounting before anything is written.

    python -m benchmarks.bench_runtime --smoke              # CI-sized
    python -m benchmarks.bench_runtime --smoke --shards 4   # sharded
    python -m benchmarks.bench_runtime --smoke --shards 4 \
        --scenario zipf --skew-gate                         # control plane
    python -m benchmarks.bench_runtime --trace results/trace_serving.json
    python -m benchmarks.bench_runtime                      # full figure
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

# legacy alias at the repo root: a symlink into results/ maintained by
# `benchmarks.common.write_datapoint` (the canonical artifact home)
BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_runtime.json"


def median_agg_pps(doc: dict, method: str = "CATO",
                   control: str | None = None) -> float:
    """Median aggregate zero_loss_pps of a method's rows.

    Rows predating the `shard` column count as aggregates (a single
    worker's only row *is* its aggregate). `control` filters
    static-vs-dynamic rows of a control-plane comparison run; None
    accepts any (plain runs have no control column)."""
    vals = [r["zero_loss_pps"] for r in doc["rows"]
            if r["method"] == method and r.get("shard", "agg") == "agg"
            and (control is None or r.get("control") == control)]
    if not vals:
        raise SystemExit(f"no {method} aggregate rows in benchmark document")
    return statistics.median(vals)


def run(smoke: bool = False, use_case: str = "app", verbose: bool = True,
        out_path: pathlib.Path | None = None, shards: int = 1,
        scenario: str = "uniform"):
    from .fig5_serving_perf import REPLAYED_HEADER as HEADER, run_replayed

    cfg = dict(
        use_case=use_case,
        iters=8 if smoke else 25,
        n_flows=600 if smoke else 1500,
        max_pkts=32 if smoke else 48,
        bisect_iters=7 if smoke else 10,
        cost_mode="measured",
        shards=shards,
        scenario=scenario,
        verbose=verbose,
    )
    if scenario != "uniform":
        # skewed scenarios need mass concentration: fewer flows, deeper
        # elephants (the held-out split still offers ~n_flows/5 flows)
        cfg["n_flows"] = 600 if smoke else 1000
        cfg["max_pkts"] = 160 if smoke else 256
        # a sharded scenario run measures static AND dynamic control rows
        cfg["control"] = shards > 1
    t0 = time.perf_counter()
    rows = run_replayed(**cfg)
    wall_s = time.perf_counter() - t0

    recs = [dict(zip(HEADER, r)) for r in rows]
    agg = [r for r in recs if r.get("shard", "agg") == "agg"]
    # headline ratios stay like-for-like: static rows only (a control
    # comparison run carries both static and dynamic measurements)
    agg_s = [r for r in agg if r.get("control", "static") == "static"]
    cato_best = max((r["zero_loss_gbps"] for r in agg_s if r["method"] == "CATO"),
                    default=0.0)
    gains = {
        r["method"]: round(cato_best / r["zero_loss_gbps"], 3)
        for r in agg_s
        if r["method"] != "CATO" and r["zero_loss_gbps"] > 0
    }
    out = {
        "bench": "runtime_zero_loss",
        "smoke": smoke,
        "config": {k: v for k, v in cfg.items() if k != "verbose"},
        "wall_s": round(wall_s, 2),
        "rows": recs,
        "cato_best_gbps": cato_best,
        "gain_vs_baseline": gains,
        "zero_drops_at_reported_rate": all(r["drops"] == 0 for r in agg),
    }
    from .common import write_datapoint

    path = write_datapoint(out, out_path, name=BENCH_PATH.name)
    if verbose:
        print(f"# wrote {path} (wall {wall_s:.1f}s, "
              f"CATO best {cato_best:.3f} Gbps, gains {gains})")
    return out


def run_traced(trace_path, shards: int = 4, scenario: str = "zipf",
               sample: float = 1.0, n_flows: int = 120, max_pkts: int = 256,
               offered_pps: float = 2e5, verbose: bool = True) -> dict:
    """One instrumented replay; every §11 artifact from a single run.

    Replays a skewed scenario through a control-plane-managed fleet with
    the full `Observability` bundle attached — flow-lifecycle and stage
    span tracing (at `sample` flow rate), drift sketches, fleet metrics
    registry, and the decision audit log — then writes:

    - the Chrome trace-event file at `trace_path`;
    - `results/trace_stage_breakdown.csv`: per-shard and fleet-level
      ingest / infer / flush service-time shares;
    - `results/obs_snapshot.json`: the merged fleet registry snapshot
      plus control, drift, audit, and trace summaries;
    - `results/audit_log.jsonl`: every rebalance / swap / scale decision
      with before/after load snapshots and rationale.

    Before writing, asserts the registry's counter totals bit-match the
    runtime's own merged `RuntimeMetrics` (the §11.1 exactness claim)
    and that the audit log saw every rebalance the plane counted.
    """
    import numpy as np

    from repro.core.search_space import FeatureRep
    from repro.serve import (
        ControlConfig, DriftMonitor, Observability, PacketStream,
        RuntimeMetrics, ServeSession, ServiceModel, ShardedRuntime, Tracer,
        fleet_registry, replay,
    )
    from repro.traffic import extract_features
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline
    from repro.traffic.synth import make_scenario_dataset

    from .common import RESULTS, emit

    t0 = time.perf_counter()
    ds = make_scenario_dataset("app-class", scenario, n_flows=n_flows,
                               max_pkts=max_pkts, seed=3)
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                      "ack_cnt"), depth=8)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="tree-fast", seed=0)
    pipe = build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)
    stream = PacketStream.from_dataset(ds, seed=0)
    # deterministic constants at realistic magnitudes (same rationale as
    # the control-plane tests): the trace should show plausible span
    # durations, not calibration jitter
    service = ServiceModel(
        pkt_accum_ns=800.0, pkt_track_ns=200.0,
        bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
        gather_ns_per_flow=200.0, source="synthetic",
    )
    obs = Observability(
        tracer=Tracer(capacity=1 << 16, sample=sample),
        drift=DriftMonitor(),
    )
    created = []

    def make_runtime():
        rt = ShardedRuntime(pipe, n_shards=shards, capacity=2048,
                            max_batch=64, execute=True)
        created.append(rt)
        return rt

    stats = replay(
        stream, make_runtime, offered_pps, service,
        session=ServeSession(
            control=ControlConfig(interval_pkts=512, imbalance_trigger=1.04),
            obs=obs),
    )
    rt = created[-1]

    # §11.1 exactness: the registry path must reproduce the runtime's own
    # accounting bit-for-bit before any artifact is trusted
    rebuilt = RuntimeMetrics.from_registry(fleet_registry(rt, per_shard=False))
    mismatch = [
        f for f in RuntimeMetrics.counter_fields()
        if getattr(rebuilt, f) != getattr(stats.metrics, f)
    ]
    if mismatch:
        raise SystemExit(
            f"registry snapshot does not bit-match RuntimeMetrics: {mismatch}")
    plane_summary = stats.control or {}
    audited = obs.audit.summary()
    if audited.get("rebalance", 0) != plane_summary.get("rebalances", 0):
        raise SystemExit(
            "audit log missed rebalances: "
            f"{audited.get('rebalance', 0)} audited vs "
            f"{plane_summary.get('rebalances', 0)} counted")

    trace_path = pathlib.Path(trace_path)
    obs.tracer.save(trace_path)
    obs.audit.save(RESULTS / "audit_log.jsonl")

    rows = [("agg", *(round(s, 4) for s in _shares(stats.stage_seconds)),
             round(sum(stats.stage_seconds.values()), 6))]
    for p in stats.per_shard:
        ss = p.get("stage_seconds", {})
        rows.append((p["shard"], *(round(s, 4) for s in _shares(ss)),
                     round(sum(ss.values()), 6)))
    emit(rows, ("shard", "share_ingest", "share_infer", "share_flush",
                "busy_s"), "trace_stage_breakdown")

    snapshot = obs.snapshot(rt)
    snapshot["control"] = plane_summary
    doc = {
        "bench": "traced_replay",
        "config": {"shards": shards, "scenario": scenario, "sample": sample,
                   "n_flows": n_flows, "max_pkts": max_pkts,
                   "offered_pps": offered_pps},
        "wall_s": round(time.perf_counter() - t0, 2),
        "drops": stats.drops,
        "stage_shares": stats.stage_shares(),
        "trace_file": str(trace_path),
        "snapshot": snapshot,
    }
    out = pathlib.Path(RESULTS) / "obs_snapshot.json"
    out.write_text(json.dumps(doc, indent=2) + "\n")
    if verbose:
        tr = obs.tracer.summary()
        print(f"# wrote {trace_path} ({tr['retained']} events, "
              f"{tr['dropped']} dropped), {out}, "
              f"results/audit_log.jsonl ({len(obs.audit)} decisions)")
        print(f"# registry bit-match OK; drops={stats.drops}; "
              f"stage shares {stats.stage_shares()}")
    return doc


REUSE_BENCH = "BENCH_runtime_zipf.json"


def run_reuse_gate(min_reuse_speedup: float = 0.0, smoke: bool = False,
                   shards: int = 4, out_path: pathlib.Path | None = None,
                   verbose: bool = True) -> dict:
    """A/B the drift-gated prediction-reuse fast path under zipf traffic
    (DESIGN.md §12) and write `results/BENCH_runtime_zipf.json`.

    Three measurements against one zipf trace and one 4-shard fleet
    configuration:

    - **off**: reuse disabled — the PR 6 serving path, calibrated with
      the honest warm tracker cost (`calibrate_warm=True`, not the
      legacy 0.25x guess, so the comparison cannot win by flattering
      the baseline);
    - **on**: reuse enabled (drift threshold 0.05, refresh every 64
      packets), same honest calibration — frozen packets charged the
      measured amortized fold cost, refreshes charged per drift check;
    - **parity**: an *executing* replay at drift threshold 0 (every
      refresh re-infers) whose per-flow predictions must be bit-identical
      to an executing reuse-off replay — the semantics guardrail that
      keeps the fast path an optimization, not a model change.

    `min_reuse_speedup` gates on/off zero-loss throughput (0 disables);
    both arms must also report zero drops at their reported rate.
    """
    import numpy as np

    from repro.core.search_space import FeatureRep
    from repro.serve import (
        PacketStream, ReuseConfig, ServiceModel, ShardedRuntime,
        find_zero_loss_rate, replay,
    )
    from repro.traffic import extract_features
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline
    from repro.traffic.synth import make_scenario_dataset

    t0 = time.perf_counter()
    # smoke shrinks the flow count, not the elephants: reuse pays off on
    # the post-classification tail of long flows, so max_pkts is the one
    # knob that must stay at full scale for the A/B to mean anything
    n_flows, max_pkts = (150, 4000) if smoke else (600, 4000)
    bisect_iters = 6 if smoke else 8
    drift_threshold, refresh_every = 0.1, 256
    ds = make_scenario_dataset("app-class", "zipf", n_flows=n_flows,
                               max_pkts=max_pkts, seed=3)
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                      "ack_cnt"), depth=8)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="tree-fast", seed=0)
    pipe = build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)
    stream = PacketStream.from_dataset(ds, seed=0)
    ring_capacity = max(64, min(6144, stream.n_events // 6))

    # prompt-classification config (both arms, so the A/B stays fair):
    # reuse only pays off once flows are classified and frozen, and at
    # zero-loss rates the whole trace spans ~0.1 virtual seconds — a
    # 64-flow batch with the default 50ms flush timeout would leave most
    # flows READY (tracked at full eager-aggregate cost) for the bulk of
    # the replay, measuring classification latency instead of reuse.
    def make_runtime(ru):
        def mk(execute):
            return ShardedRuntime(pipe, n_shards=shards, capacity=2048,
                                  max_batch=8, flush_timeout_s=2e-4,
                                  execute=execute, reuse=ru)
        return mk

    arms = {}
    for tag, ru in (
        ("off", None),
        ("on", ReuseConfig(enabled=True, drift_threshold=drift_threshold,
                           refresh_every=refresh_every)),
    ):
        mk = make_runtime(ru)
        # reps=5: the warm per-class constants decide the A/B verdict and
        # measure() keeps the best-of-reps minimum, so extra reps strictly
        # tighten the noise floor on shared machines
        service = ServiceModel.measure(mk(True), stream, n_pkt_sample=16000,
                                       reps=5, calibrate_warm=True)
        pps, stats = find_zero_loss_rate(
            stream, mk, service, iters=bisect_iters,
            ring_capacity=ring_capacity)
        m = stats.metrics
        arms[tag] = {
            "zero_loss_pps": round(pps, 1),
            "zero_loss_gbps": round(stats.offered_gbps, 4),
            "drops": stats.drops,
            "pkt_track_ns": round(service.pkt_track_ns, 1),
            "pkt_frozen_ns": (None if service.pkt_frozen_ns is None
                              else round(service.pkt_frozen_ns, 1)),
            "reuse_hits": m.reuse_hits,
            "refreshes": m.refreshes,
            "forced_reinfer": m.forced_reinfer,
        }
        if verbose:
            print(f"# zipf {shards}-shard reuse={tag}: "
                  f"{pps:,.0f} pps ({stats.offered_gbps:.3f} Gbps), "
                  f"drops={stats.drops}, track={service.pkt_track_ns:.0f}ns, "
                  f"frozen={service.pkt_frozen_ns}")

    # parity: threshold 0 forces re-inference at every refresh, and results
    # keep first-prediction-wins — predictions must be bit-identical to the
    # reuse-off executing replay
    svc = ServiceModel(pkt_accum_ns=800.0, pkt_track_ns=200.0,
                       bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
                       gather_ns_per_flow=200.0, pkt_frozen_ns=100.0,
                       source="synthetic")
    base = replay(stream, lambda: make_runtime(None)(True),
                  stream.base_pps, svc, ring_capacity=ring_capacity)
    thr0 = replay(stream, lambda: make_runtime(
        ReuseConfig(enabled=True, drift_threshold=0.0,
                    refresh_every=refresh_every))(True),
        stream.base_pps, svc, ring_capacity=ring_capacity)
    parity_ok = (
        set(base.predictions) == set(thr0.predictions)
        and all(np.array_equal(base.predictions[k], thr0.predictions[k])
                for k in base.predictions)
    )
    if verbose:
        print(f"# threshold-0 bit-parity: {parity_ok} "
              f"({len(base.predictions)} flows)")

    speedup = (arms["on"]["zero_loss_pps"]
               / max(arms["off"]["zero_loss_pps"], 1e-9))
    doc = {
        "bench": "runtime_zero_loss_reuse",
        "smoke": smoke,
        "config": {"scenario": "zipf", "shards": shards, "n_flows": n_flows,
                   "max_pkts": max_pkts, "events": stream.n_events,
                   "bisect_iters": bisect_iters,
                   "ring_capacity": ring_capacity,
                   "drift_threshold": drift_threshold,
                   "refresh_every": refresh_every},
        "wall_s": round(time.perf_counter() - t0, 2),
        "arms": arms,
        "reuse_speedup": round(speedup, 3),
        "threshold0_bit_identical": bool(parity_ok),
        "zero_drops_at_reported_rate": (arms["off"]["drops"] == 0
                                        and arms["on"]["drops"] == 0),
    }
    from .common import write_datapoint

    path = write_datapoint(doc, out_path, name=REUSE_BENCH)
    if verbose:
        print(f"# wrote {path} (wall {doc['wall_s']:.1f}s, "
              f"reuse speedup {speedup:.2f}x)")
    if not parity_ok:
        print("FAIL: threshold-0 predictions diverge from reuse-off",
              file=sys.stderr)
        raise SystemExit(1)
    if not doc["zero_drops_at_reported_rate"]:
        print("FAIL: drops at reported zero-loss rate", file=sys.stderr)
        raise SystemExit(1)
    if min_reuse_speedup > 0 and speedup < min_reuse_speedup:
        print(f"FAIL: reuse speedup {speedup:.2f}x < "
              f"{min_reuse_speedup:.2f}x floor", file=sys.stderr)
        raise SystemExit(1)
    if verbose and min_reuse_speedup > 0:
        print(f"OK: reuse speedup above {min_reuse_speedup:.2f}x floor")
    return doc


MULTITENANT_BENCH = "BENCH_multitenant.json"

# overlapping per-tenant feature plans (DESIGN.md §15.1): heavy shared
# prefix so the merged plan amortizes — the whole point of the A/B
_TENANT_REPS = (
    (("s_bytes_mean", "s_iat_mean", "s_load", "proto"), 8),
    (("s_bytes_mean", "s_iat_mean", "s_load", "dur", "s_bytes_max"), 12),
    (("s_bytes_mean", "s_iat_mean", "dur", "d_pkt_cnt"), 8),
    (("s_bytes_mean", "s_load", "ack_cnt", "psh_cnt"), 8),
)


def run_multitenant_gate(min_tenant_speedup: float = 0.0, smoke: bool = False,
                         tenants: int = 3,
                         out_path: pathlib.Path | None = None,
                         verbose: bool = True) -> dict:
    """A/B multi-tenant white-box serving under zipf traffic (DESIGN.md
    §15) and write `results/BENCH_multitenant.json`.

    Two arms at equal total worker count, one zipf trace:

    - **shared**: one N-shard fleet serving all N tenants through a
      single `MultiTenantPipeline` — the merged extraction plan runs
      once per flow, every tenant's forest reads its column subset;
    - **independent**: N separate 1-shard fleets, one per tenant, each
      replaying the *full* stream (every tenant must classify every
      flow). The arm's zero-loss rate is the min over tenants — the
      slowest fleet caps the rate the stream can be delivered at.

    Both arms are calibrated with `ServiceModel.measure` on their own
    runtime and bisected to the highest zero-drop rate. A parity leg
    (executing replays under a synthetic service model) asserts every
    tenant's shared-fleet predictions are bit-identical to its
    solo-served baseline — sharing is an optimization, not a model
    change. `min_tenant_speedup` gates shared/independent zero-loss
    throughput (0 disables); both arms must report zero drops.
    """
    import numpy as np

    from repro.core.search_space import FeatureRep
    from repro.serve import (
        PacketStream, ServiceModel, ShardedRuntime, build_multi_tenant_pipeline,
        find_zero_loss_rate, replay,
    )
    from repro.traffic import extract_features
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline
    from repro.traffic.synth import make_scenario_dataset

    if not 2 <= tenants <= len(_TENANT_REPS):
        raise SystemExit(
            f"--tenants must be in [2, {len(_TENANT_REPS)}], got {tenants}")
    t0 = time.perf_counter()
    n_flows, max_pkts = (150, 96) if smoke else (500, 160)
    bisect_iters = 6 if smoke else 8
    ds = make_scenario_dataset("app-class", "zipf", n_flows=n_flows,
                               max_pkts=max_pkts, seed=3)
    reps = [FeatureRep(f, depth=d) for f, d in _TENANT_REPS[:tenants]]
    forests = []
    for t, rep in enumerate(reps):
        X = extract_features(ds, rep.features, rep.depth)
        forests.append(
            train_traffic_model(X, ds.label, model="tree-fast", seed=t)[0])
    solo_pipes = [build_pipeline(r, f, max_pkts=r.depth, use_kernel=False)
                  for r, f in zip(reps, forests)]
    mt_pipe = build_multi_tenant_pipeline(reps, forests, use_kernel=False)
    stream = PacketStream.from_dataset(ds, seed=0)
    ring_capacity = max(64, min(6144, stream.n_events // 6))

    # prompt flushes both arms (small batches, tight timeout) so neither
    # arm's zero-loss rate is gated on classification latency
    def make_runtime(pipe, shards):
        def mk(execute):
            return ShardedRuntime(pipe, n_shards=shards, capacity=2048,
                                  max_batch=32, flush_timeout_s=2e-4,
                                  execute=execute)
        return mk

    def bisect(pipe, shards, tag):
        mk = make_runtime(pipe, shards)
        service = ServiceModel.measure(mk(True), stream, n_pkt_sample=16000,
                                       reps=5)
        pps, stats = find_zero_loss_rate(
            stream, mk, service, iters=bisect_iters,
            ring_capacity=ring_capacity)
        if verbose:
            print(f"# zipf {tag}: {pps:,.0f} pps "
                  f"({stats.offered_gbps:.3f} Gbps), drops={stats.drops}")
        return {"zero_loss_pps": round(pps, 1),
                "zero_loss_gbps": round(stats.offered_gbps, 4),
                "drops": stats.drops, "n_shards": shards}

    shared = bisect(mt_pipe, tenants, f"shared {tenants}-shard fleet")
    indep = [bisect(p, 1, f"independent tenant{t} 1-shard fleet")
             for t, p in enumerate(solo_pipes)]
    # the stream is offered to all N independent fleets at one rate, so
    # the slowest tenant's zero-loss rate is the arm's rate
    indep_pps = min(a["zero_loss_pps"] for a in indep)

    # parity: executing replays at the stream's native rate — tenant t's
    # lane of every fused prediction vector must equal its solo baseline
    svc = ServiceModel(pkt_accum_ns=800.0, pkt_track_ns=200.0,
                       bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
                       gather_ns_per_flow=200.0, source="synthetic")
    sh = replay(stream, lambda: make_runtime(mt_pipe, tenants)(True),
                stream.base_pps, svc, ring_capacity=ring_capacity)
    parity_ok, n_flows_checked = True, 0
    for t, pipe in enumerate(solo_pipes):
        solo = replay(stream, lambda: make_runtime(pipe, 1)(True),
                      stream.base_pps, svc, ring_capacity=ring_capacity)
        keys = sorted(sh.predictions)
        ok = (keys == sorted(solo.predictions)
              and np.array_equal(
                  np.asarray([sh.predictions[k][t] for k in keys]),
                  np.asarray([solo.predictions[k] for k in keys])))
        parity_ok &= ok
        n_flows_checked = len(keys)
        if verbose:
            print(f"# tenant{t} shared-vs-solo bit-parity: {ok}")

    speedup = shared["zero_loss_pps"] / max(indep_pps, 1e-9)
    doc = {
        "bench": "runtime_multitenant",
        "smoke": smoke,
        "config": {"scenario": "zipf", "tenants": tenants,
                   "n_flows": n_flows, "max_pkts": max_pkts,
                   "events": stream.n_events, "bisect_iters": bisect_iters,
                   "ring_capacity": ring_capacity,
                   "tenant_features": [list(r.features) for r in reps],
                   "tenant_depths": [r.depth for r in reps],
                   "union_features": len(mt_pipe.rep.features),
                   "merged_columns": len(mt_pipe.merged),
                   "solo_columns": sum(len(r.features) for r in reps)},
        "wall_s": round(time.perf_counter() - t0, 2),
        "arms": {
            "shared": shared,
            "independent": {"per_tenant": indep,
                            "zero_loss_pps": indep_pps,
                            "drops": sum(a["drops"] for a in indep)},
        },
        "tenant_speedup": round(speedup, 3),
        "per_tenant_bit_identical": bool(parity_ok),
        "flows_checked": n_flows_checked,
        "zero_drops_at_reported_rate": (
            shared["drops"] == 0 and all(a["drops"] == 0 for a in indep)),
    }
    from .common import write_datapoint

    path = write_datapoint(doc, out_path, name=MULTITENANT_BENCH)
    if verbose:
        print(f"# wrote {path} (wall {doc['wall_s']:.1f}s, "
              f"shared/independent speedup {speedup:.2f}x)")
    if not parity_ok:
        print("FAIL: shared-fleet predictions diverge from solo baselines",
              file=sys.stderr)
        raise SystemExit(1)
    if not doc["zero_drops_at_reported_rate"]:
        print("FAIL: drops at reported zero-loss rate", file=sys.stderr)
        raise SystemExit(1)
    if min_tenant_speedup > 0 and speedup < min_tenant_speedup:
        print(f"FAIL: multi-tenant speedup {speedup:.2f}x < "
              f"{min_tenant_speedup:.2f}x floor", file=sys.stderr)
        raise SystemExit(1)
    if verbose and min_tenant_speedup > 0:
        print(f"OK: multi-tenant speedup above {min_tenant_speedup:.2f}x floor")
    return doc


SELFTUNE_BENCH = "BENCH_selftune.json"


def _macro_f1(y_true, y_pred) -> float:
    """Macro-averaged F1 over the classes present in `y_true`/`y_pred`."""
    import numpy as np

    f1s = []
    for c in np.union1d(np.unique(y_true), np.unique(y_pred)):
        tp = float(np.sum((y_pred == c) & (y_true == c)))
        fp = float(np.sum((y_pred == c) & (y_true != c)))
        fn = float(np.sum((y_pred != c) & (y_true == c)))
        if tp + fp + fn == 0:
            continue
        f1s.append(2 * tp / max(2 * tp + fp + fn, 1e-9))
    return float(np.mean(f1s)) if f1s else 0.0


def run_selftune_gate(smoke: bool = False,
                      out_path: pathlib.Path | None = None,
                      verbose: bool = True) -> dict:
    """A/B the self-optimizing fleet on the drift scenario (DESIGN.md §13)
    and write `results/BENCH_selftune.json`.

    The drift scenario reorders flows by class rank, so an in-order
    arrival process sees the class mix slide across the trace. The
    deployed bundle is trained on the *pre-drift window only* (the first
    40% of packets) — the stale knee a fleet optimized yesterday would
    be serving today. Three controlled replays:

    - **frozen**: the stale bundle with the control plane but no
      reoptimizer — what PR 7's fleet would do;
    - **selftuned**: same bundle and stream, plus a `ReoptimizerPolicy`
      whose retune refits on the full corpus — the drift monitor must
      trigger mid-run, the policy must hot-swap the re-optimized knee,
      and post-drift flows must classify through the new pipeline;
    - **uniform control**: the identical policy on a uniform replay —
      zero episodes, or the trigger is noise-driven.

    Gates: >= 1 audited reopt episode on the drift arm, zero episodes
    on the uniform arm, zero drops everywhere (the swap may not lose a
    packet), and the self-tuned arm's macro-F1 over the post-drift
    segment (flows first seen in the trace's last third) strictly above
    the frozen arm's.
    """
    import numpy as np

    from repro.core.search_space import FeatureRep
    from repro.serve import (
        ControlConfig, DriftMonitor, Observability, PacketStream,
        ReoptOutcome, ReoptimizerConfig, ReoptimizerPolicy, ServeSession,
        ServiceModel, ShardedRuntime, replay,
    )
    from repro.serve.deploy import BundlePoint
    from repro.traffic import extract_features
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline
    from repro.traffic.synth import make_scenario_dataset

    t0 = time.perf_counter()
    n_flows, max_pkts, pps = (600, 32, 2e5)
    rep_a = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                        "ack_cnt"), depth=8)
    rep_b = FeatureRep(("dur", "s_load", "s_pkt_cnt", "d_bytes_med",
                        "psh_cnt"), depth=12)
    service = ServiceModel(pkt_accum_ns=800.0, pkt_track_ns=200.0,
                           bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
                           gather_ns_per_flow=200.0, source="synthetic")
    # threshold 0.35 sits between small-batch mix noise (~0.25 TV at
    # max_batch=16) and the drift excursion (>0.6); max_batch must be
    # small enough that micro-batches resolve (and feed the drift
    # monitor) mid-run rather than at drain
    policy_cfg = ReoptimizerConfig(class_threshold=0.35, min_dwell_pkts=256,
                                   cooldown_pkts=1 << 20, max_episodes=1)

    def fleet(pipe):
        return lambda: ShardedRuntime(pipe, n_shards=2, capacity=2048,
                                      max_batch=16, execute=True)

    def stale_and_retuned(ds, stream):
        """The pre-drift-trained deployed bundle + a full-corpus retune."""
        first_pkt = np.full(ds.n_flows, stream.n_events)
        np.minimum.at(first_pkt, stream.fid, np.arange(stream.n_events))
        pre = np.nonzero(first_pkt < 0.4 * stream.n_events)[0]
        Xa = extract_features(ds, rep_a.features, rep_a.depth)
        fa, _ = train_traffic_model(Xa[pre], ds.label[pre],
                                    model="tree-fast", seed=0)
        stale = build_pipeline(rep_a, fa, max_pkts=rep_a.depth,
                               use_kernel=False)

        def retune(trigger):
            Xb = extract_features(ds, rep_b.features, rep_b.depth)
            fb, _ = train_traffic_model(Xb, ds.label, model="tree-fast",
                                        seed=0)
            pipe_b = build_pipeline(rep_b, fb, max_pkts=rep_b.depth,
                                    use_kernel=False)
            point = BundlePoint(rep=rep_b, cost=1.0, perf=0.95,
                                fidelity="measured", aux={},
                                compile_meta={"fused": False},
                                forest_doc=None, pipeline=pipe_b)
            return ReoptOutcome(point=point, service=service)

        return stale, retune, first_pkt

    def session(retune=None):
        s = ServeSession(obs=Observability(drift=DriftMonitor()),
                         control=ControlConfig(interval_pkts=256,
                                               rebalance=False))
        if retune is not None:
            s.reopt = ReoptimizerPolicy(retune, policy_cfg)
        return s

    ds = make_scenario_dataset("app-class", "drift", n_flows=n_flows,
                               max_pkts=max_pkts, seed=3)
    stream = PacketStream.from_dataset(ds, seed=0)
    stale, retune, first_pkt = stale_and_retuned(ds, stream)
    frozen = replay(stream, fleet(stale), pps, service, session=session())
    tuned_session = session(retune)
    tuned = replay(stream, fleet(stale), pps, service, session=tuned_session)

    # post-drift segment: flows first seen in the trace's last third
    post = np.nonzero(first_pkt >= (2 / 3) * stream.n_events)[0]
    f1 = {
        tag: _macro_f1(ds.label[post],
                       np.array([st.predictions[f] for f in post]))
        for tag, st in (("frozen", frozen), ("selftuned", tuned))
    }
    episodes = tuned.control["reopt"]["episodes"]
    reopt_events = tuned_session.resolve_audit().of_kind("reopt")
    if verbose:
        print(f"# drift 2-shard: post-drift macro-F1 frozen "
              f"{f1['frozen']:.3f} vs selftuned {f1['selftuned']:.3f}, "
              f"episodes={episodes}, "
              f"swap_at={tuned.control['swap_at_pkts']}, "
              f"drops={frozen.drops}/{tuned.drops}")

    # uniform control arm: same policy, stationary mix -> zero episodes
    ds_u = make_scenario_dataset("app-class", "uniform", n_flows=n_flows,
                                 max_pkts=max_pkts, seed=3)
    stream_u = PacketStream.from_dataset(ds_u, seed=0)
    stale_u, retune_u, _ = stale_and_retuned(ds_u, stream_u)
    uniform = replay(stream_u, fleet(stale_u), pps, service,
                     session=session(retune_u))
    if verbose:
        print(f"# uniform control arm: episodes="
              f"{uniform.control['reopt']['episodes']}, "
              f"drops={uniform.drops}")

    doc = {
        "bench": "selftune_drift",
        "smoke": smoke,
        "config": {"scenario": "drift", "shards": 2, "n_flows": n_flows,
                   "max_pkts": max_pkts, "events": stream.n_events,
                   "pps": pps, "class_threshold": 0.35,
                   "min_dwell_pkts": 256, "interval_pkts": 256,
                   "max_batch": 16},
        "wall_s": round(time.perf_counter() - t0, 2),
        "post_drift_f1": {k: round(v, 4) for k, v in f1.items()},
        "episodes": episodes,
        "swap_at_pkts": tuned.control["swap_at_pkts"],
        "reopt_audited": len(reopt_events),
        "uniform_episodes": uniform.control["reopt"]["episodes"],
        "drops": {"frozen": frozen.drops, "selftuned": tuned.drops,
                  "uniform": uniform.drops},
        "reopt_summary": tuned.control["reopt"],
    }
    from .common import write_datapoint

    path = write_datapoint(doc, out_path, name=SELFTUNE_BENCH)
    if verbose:
        print(f"# wrote {path} (wall {doc['wall_s']:.1f}s)")
    if episodes < 1 or len(reopt_events) < 1:
        print("FAIL: drift arm fired no audited reopt episode",
              file=sys.stderr)
        raise SystemExit(1)
    if doc["uniform_episodes"] != 0:
        print("FAIL: uniform arm fired a reopt episode (noise trigger)",
              file=sys.stderr)
        raise SystemExit(1)
    if frozen.drops or tuned.drops or uniform.drops:
        print("FAIL: drops during a gated replay (swap lost packets?)",
              file=sys.stderr)
        raise SystemExit(1)
    if not f1["selftuned"] > f1["frozen"]:
        print(f"FAIL: post-drift F1 selftuned {f1['selftuned']:.3f} not "
              f"above frozen {f1['frozen']:.3f}", file=sys.stderr)
        raise SystemExit(1)
    if verbose:
        print("OK: self-tuned fleet beats the frozen knee post-drift")
    return doc


SLO_BENCH = "BENCH_slo.json"


def run_slo_gate(smoke: bool = False, scenario: str = "zipf",
                 shards: int = 4,
                 out_path: pathlib.Path | None = None,
                 verbose: bool = True) -> dict:
    """Fixed-offered-load SLO smoke (DESIGN.md §14): per-stage latency
    decomposition + burn-rate verdicts, and write `results/BENCH_slo.json`.

    One probe replay measures the fleet's actual latency distribution
    (the sketches' p50/p99), then two controlled arms replay the same
    stream against *self-calibrated* targets:

    - **met**: target = 10x the probed p99 — attainment must be 1.0 and
      the run must produce zero audited ``"slo"`` events;
    - **violated**: target = half the probed *minimum* — unattainable by
      construction (service time floors every flow's total), so the
      tracker must breach and the control plane must audit >= 1
      ``"slo"`` event (edge-triggered: one per episode, not per step).

    Cross-cutting gates on the violated arm's recording: every stage
    sketch saw every charged flow, the integer-ns stage means sum to the
    end-to-end mean, the stage p99s bound the total's tail (Bonferroni,
    within the sketches' alpha), the exporter's JSONL series has one
    line per executed control step, and its Prometheus rendering
    validates. The SLO window is derived from the trace's virtual span
    (smoke traces cover well under a second of virtual time)."""
    import numpy as np

    from repro.core.search_space import FeatureRep
    from repro.serve import (
        ControlConfig, LatencyConfig, MetricsExporter, Observability,
        PacketStream, ServeSession, ServiceModel, ShardedRuntime, SLOConfig,
        SLOTracker, check_prometheus, controlled_replay, replay,
    )
    from repro.serve.obs import COMPONENTS
    from repro.traffic import extract_features
    from repro.traffic.models import train_traffic_model
    from repro.traffic.pipeline import build_pipeline
    from repro.traffic.synth import make_scenario_dataset

    from .common import RESULTS, write_datapoint

    t0 = time.perf_counter()
    n_flows, max_pkts = (400, 64) if smoke else (1200, 128)
    pps = 2e5
    alpha = 0.01
    rep = FeatureRep(("dur", "s_load", "s_bytes_mean", "s_iat_mean",
                      "ack_cnt"), depth=8)
    ds = make_scenario_dataset("app-class", scenario, n_flows=n_flows,
                               max_pkts=max_pkts, seed=3)
    X = extract_features(ds, rep.features, rep.depth)
    forest, _ = train_traffic_model(X, ds.label, model="tree-fast", seed=0)
    pipe = build_pipeline(rep, forest, max_pkts=rep.depth, use_kernel=False)
    stream = PacketStream.from_dataset(ds, seed=0)
    service = ServiceModel(pkt_accum_ns=800.0, pkt_track_ns=200.0,
                           bucket_ns={8: 3e4, 16: 4e4, 32: 6e4, 64: 1e5},
                           gather_ns_per_flow=200.0, source="synthetic")
    # the packet clock spans n_events/pps virtual seconds; ~12 windows
    # gives the slow burn several windows to integrate over
    window_s = (stream.n_events / pps) / 12.0

    def mk(created):
        def make():
            rt = ShardedRuntime(pipe, n_shards=shards, capacity=2048,
                                max_batch=64, execute=False)
            created.append(rt)
            return rt
        return make

    def merged_recorder(rt):
        recs = [s.metrics.latency_components for s in rt.shards]
        out = recs[0].fresh()
        for r in recs:
            out.merge_from(r)
        return out

    # -- probe: measure the distribution the targets calibrate against --
    probe_created: list = []
    probe_obs = Observability(latency=LatencyConfig(alpha=alpha))
    replay(stream, mk(probe_created), pps, service,
           session=ServeSession(obs=probe_obs))
    probe = merged_recorder(probe_created[-1]).sketches["total"]
    p50, p99 = probe.percentile(50), probe.percentile(99)
    # the controlled arms batch differently than the probe, but no flow
    # anywhere completes faster than its bucket's service time — half
    # the probed minimum is unattainable by construction
    vio_target = 0.5 * probe.percentile(0)

    def arm(target_s, jsonl_path):
        created: list = []
        slo = SLOTracker(SLOConfig(target_s=target_s, objective=0.99,
                                   window_s=window_s, slow_windows=4))
        obs = Observability(latency=LatencyConfig(alpha=alpha), slo=slo,
                            exporter=MetricsExporter(jsonl_path=jsonl_path))
        session = ServeSession(obs=obs,
                               control=ControlConfig(interval_pkts=512))
        stats = controlled_replay(stream, mk(created), pps, service,
                                  session=session)
        return stats, obs, created[-1]

    jsonl = RESULTS / "slo_timeseries.jsonl"
    jsonl.unlink(missing_ok=True)             # append-only within a run
    met_stats, met_obs, _ = arm(10.0 * p99, None)
    vio_stats, vio_obs, vio_rt = arm(vio_target, str(jsonl))

    rec = merged_recorder(vio_rt)
    stages = {c: {k: (round(v, 9) if isinstance(v, float) else v)
                  for k, v in rec.sketches[c].summary().items()}
              for c in COMPONENTS}
    total = rec.sketches["total"]
    parts_mean = sum(rec.sketches[c].mean_s
                     for c in ("queue_wait", "batch", "service"))
    stage_p99_sum = sum(rec.sketches[c].percentile(99)
                        for c in ("queue_wait", "batch", "service"))
    # per-charge ns rounding on each of 3 components
    mean_tol = 2e-9 + abs(total.mean_s) * 1e-6
    decomposition_ok = (
        len({rec.sketches[c].n for c in COMPONENTS}) == 1
        and abs(parts_mean - total.mean_s) <= mean_tol
        and total.percentile(97) <= stage_p99_sum * (1.0 + 4 * alpha))

    met_events = len(met_obs.audit.of_kind("slo"))
    vio_events = len(vio_obs.audit.of_kind("slo"))
    prom_problems = check_prometheus(vio_obs.exporter.prometheus())
    series_lines = len(jsonl.read_text().splitlines())

    def arm_doc(stats, obs, target_s):
        v = obs.slo.check(stream.n_events / pps)
        return {
            "target_s": round(target_s, 9),
            "attainment": round(obs.slo.attainment, 6),
            "breaches": obs.slo.breaches,
            "audited_slo_events": len(obs.audit.of_kind("slo")),
            "burn_slow": round(v.burn_slow, 3),
            "samples": obs.slo.samples,
            "drops": stats.drops,
            "latency_p99_s": round(stats.latency_p99_s, 9),
        }

    doc = {
        "bench": "slo_latency",
        "smoke": smoke,
        "config": {"scenario": scenario, "shards": shards,
                   "n_flows": n_flows, "max_pkts": max_pkts,
                   "events": int(stream.n_events), "pps": pps,
                   "alpha": alpha, "window_s": round(window_s, 9),
                   "interval_pkts": 512},
        "wall_s": round(time.perf_counter() - t0, 2),
        "probe": {"p50_s": round(p50, 9), "p99_s": round(p99, 9)},
        "stages": stages,
        "decomposition": {
            "stage_mean_sum_s": round(parts_mean, 9),
            "total_mean_s": round(total.mean_s, 9),
            "stage_p99_sum_s": round(stage_p99_sum, 9),
            "total_p99_s": round(total.percentile(99), 9),
            "consistent": decomposition_ok,
        },
        "arms": {"met": arm_doc(met_stats, met_obs, 10.0 * p99),
                 "violated": arm_doc(vio_stats, vio_obs, vio_target)},
        "exporter": {"steps": vio_obs.exporter.steps,
                     "jsonl": str(jsonl), "jsonl_lines": series_lines,
                     "prometheus_problems": prom_problems},
    }
    path = write_datapoint(doc, out_path, name=SLO_BENCH)
    if verbose:
        s = stages
        print(f"# {scenario} {shards}-shard @ {pps:,.0f} pps: total p99 "
              f"{s['total']['p99_s'] * 1e6:.1f}us = queue "
              f"{s['queue_wait']['p99_s'] * 1e6:.1f} + batch "
              f"{s['batch']['p99_s'] * 1e6:.1f} + service "
              f"{s['service']['p99_s'] * 1e6:.1f} (stage p99s, us)")
        print(f"# met arm: attainment {doc['arms']['met']['attainment']}, "
              f"{met_events} audited; violated arm: attainment "
              f"{doc['arms']['violated']['attainment']}, {vio_events} "
              f"audited, burn {doc['arms']['violated']['burn_slow']}x")
        print(f"# wrote {path} (+{series_lines}-line {jsonl.name}, "
              f"wall {doc['wall_s']:.1f}s)")

    if vio_events < 1:
        print("FAIL: violated arm produced no audited slo event",
              file=sys.stderr)
        raise SystemExit(1)
    if met_events != 0 or doc["arms"]["met"]["attainment"] != 1.0:
        print("FAIL: met arm breached a 10x-p99 target", file=sys.stderr)
        raise SystemExit(1)
    if not decomposition_ok:
        print("FAIL: stage decomposition inconsistent with the "
              "end-to-end total", file=sys.stderr)
        raise SystemExit(1)
    if prom_problems:
        for prob in prom_problems:
            print(f"FAIL: prometheus exposition: {prob}", file=sys.stderr)
        raise SystemExit(1)
    if series_lines != vio_obs.exporter.steps or series_lines < 1:
        print(f"FAIL: JSONL series has {series_lines} lines for "
              f"{vio_obs.exporter.steps} control steps", file=sys.stderr)
        raise SystemExit(1)
    if verbose:
        print("OK: stage decomposition consistent, breaches audited, "
              "exporter output validates")
    return doc


def _shares(stage_seconds: dict) -> tuple:
    total = sum(stage_seconds.values()) if stage_seconds else 0.0
    if total <= 0:
        return (0.0, 0.0, 0.0)
    return tuple(stage_seconds.get(k, 0.0) / total
                 for k in ("ingest", "infer", "flush"))


def check_speedup(sharded: dict, single_path: pathlib.Path,
                  min_speedup: float) -> int:
    """Gate: sharded aggregate median vs a same-config 1-shard datapoint."""
    single = json.loads(single_path.read_text())
    cfg_s = {k: v for k, v in sharded["config"].items() if k != "shards"}
    cfg_1 = {k: v for k, v in single["config"].items() if k != "shards"}
    if cfg_s != cfg_1:
        print("config mismatch: sharded and single runs are not comparable\n"
              f"  sharded: {cfg_s}\n  single:  {cfg_1}", file=sys.stderr)
        return 2
    base = median_agg_pps(single)
    now = median_agg_pps(sharded)
    speedup = now / base
    n = sharded["config"].get("shards", 1)
    print(f"1-shard median CATO zero_loss_pps: {base:,.0f}")
    print(f"{n}-shard median CATO zero_loss_pps: {now:,.0f}  "
          f"(speedup {speedup:.2f}x, floor {min_speedup:.2f}x)")
    if speedup < min_speedup:
        print(f"FAIL: {n}-shard speedup {speedup:.2f}x < {min_speedup:.2f}x",
              file=sys.stderr)
        return 1
    print("OK: sharded speedup above floor")
    return 0


def check_skew(doc: dict) -> int:
    """Gate: under a skewed scenario, the adaptive control plane must
    report strictly lower load_imbalance than the static RETA and no
    lower median zero-loss pps (both sides share one service
    calibration, so the comparison is same-constants by construction)."""
    agg = [r for r in doc["rows"]
           if r.get("shard") == "agg" and r["method"] == "CATO"]
    st = [r for r in agg if r.get("control") == "static"]
    dy = [r for r in agg if r.get("control") == "dynamic"]
    if not st or not dy:
        print("skew gate needs a control-plane comparison run "
              "(--scenario <skewed> with --shards > 1)", file=sys.stderr)
        return 2
    imb_st = statistics.median(r["imbalance"] for r in st)
    imb_dy = statistics.median(r["imbalance"] for r in dy)
    pps_st = statistics.median(r["zero_loss_pps"] for r in st)
    pps_dy = statistics.median(r["zero_loss_pps"] for r in dy)
    print(f"static  RETA: median imbalance {imb_st:.3f}, "
          f"median zero_loss_pps {pps_st:,.0f}")
    print(f"dynamic RETA: median imbalance {imb_dy:.3f}, "
          f"median zero_loss_pps {pps_dy:,.0f} "
          f"({pps_dy / pps_st:.2f}x static)")
    if imb_dy >= imb_st:
        print(f"FAIL: dynamic imbalance {imb_dy:.3f} not below static "
              f"{imb_st:.3f}", file=sys.stderr)
        return 1
    if pps_dy < pps_st:
        print(f"FAIL: dynamic median pps {pps_dy:,.0f} below static "
              f"{pps_st:,.0f}", file=sys.stderr)
        return 1
    print("OK: control plane beats static RETA under skew")
    return 0


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true", help="CI-sized run")
    p.add_argument("--use-case", default="app", choices=("app", "iot"))
    p.add_argument("--shards", type=int, default=1,
                   help="worker count (RSS-steered ShardedRuntime when > 1)")
    p.add_argument("--scenario", default="uniform",
                   choices=("uniform", "zipf", "burst", "drift"),
                   help="adversarial traffic scenario (non-uniform + shards "
                   "> 1 also measures the adaptive control plane)")
    p.add_argument("--skew-gate", action="store_true",
                   help="fail unless dynamic rebalancing beats the static "
                   "RETA under the chosen skewed scenario")
    p.add_argument("--out", default=None, help="output path (default: "
                   "results/BENCH_runtime.json + repo-root symlink alias)")
    p.add_argument("--single", default=None,
                   help="1-shard datapoint to compute sharded speedup against")
    p.add_argument("--min-speedup", type=float, default=0.0,
                   help="fail if sharded median speedup vs --single is below "
                   "this (0 disables)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="run one instrumented replay instead of the figure: "
                   "write a Chrome trace to PATH plus stage-breakdown, "
                   "metrics-snapshot, and audit-log artifacts in results/")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="flow sampling rate for --trace (default: all flows)")
    p.add_argument("--min-reuse-speedup", type=float, default=None,
                   metavar="R", help="run the prediction-reuse A/B gate "
                   "instead of the figure (DESIGN.md §12): measure zipf "
                   "zero-loss throughput with reuse off and on, assert "
                   "threshold-0 bit-parity + zero drops, fail if on/off "
                   "speedup < R (0 measures without gating); writes "
                   "results/BENCH_runtime_zipf.json")
    p.add_argument("--slo", action="store_true",
                   help="run the SLO latency gate instead of the figure "
                   "(DESIGN.md §14): probe the fleet's replayed latency "
                   "distribution, then controlled replays against a met "
                   "and a violated self-calibrated target — assert the "
                   "per-stage p99 decomposition is consistent with the "
                   "end-to-end total, >= 1 audited slo event when "
                   "violated and none when met, and the exporter's "
                   "Prometheus/JSONL output validates; writes "
                   "results/BENCH_slo.json + slo_timeseries.jsonl")
    p.add_argument("--tenants", type=int, default=None, metavar="N",
                   help="run the multi-tenant A/B gate instead of the "
                   "figure (DESIGN.md §15): one N-tenant shared fleet "
                   "(merged extraction plan, one fused multi-model launch) "
                   "vs N independent 1-shard fleets at equal total shards, "
                   "zero-loss bisection each arm plus a per-tenant "
                   "bit-parity leg; writes results/BENCH_multitenant.json")
    p.add_argument("--min-tenant-speedup", type=float, default=0.0,
                   metavar="R", help="fail the --tenants gate if the shared "
                   "fleet's zero-loss pps is below R x the independent "
                   "fleets' rate (0 measures without gating)")
    p.add_argument("--selftune", action="store_true",
                   help="run the self-optimizing-fleet gate instead of the "
                   "figure (DESIGN.md §13): drift-scenario controlled replay "
                   "with a drift-triggered reoptimizer vs the frozen knee — "
                   "assert >= 1 audited reopt episode, zero drops through "
                   "the hot-swap, strictly better post-drift macro-F1, and "
                   "zero episodes on a uniform control arm; writes "
                   "results/BENCH_selftune.json")
    args = p.parse_args()
    from repro.compile_cache import enable as enable_compile_cache

    enable_compile_cache()
    if args.slo:
        run_slo_gate(smoke=args.smoke,
                     scenario=args.scenario if args.scenario != "uniform"
                     else "zipf",
                     shards=args.shards if args.shards > 1 else 4,
                     out_path=args.out)
        raise SystemExit(0)
    if args.selftune:
        run_selftune_gate(smoke=args.smoke, out_path=args.out)
        raise SystemExit(0)
    if args.tenants is not None:
        run_multitenant_gate(min_tenant_speedup=args.min_tenant_speedup,
                             smoke=args.smoke, tenants=args.tenants,
                             out_path=args.out)
        raise SystemExit(0)
    if args.min_reuse_speedup is not None:
        run_reuse_gate(min_reuse_speedup=args.min_reuse_speedup,
                       smoke=args.smoke,
                       shards=args.shards if args.shards > 1 else 4,
                       out_path=args.out)
        raise SystemExit(0)
    if args.trace is not None:
        run_traced(args.trace,
                   shards=args.shards if args.shards > 1 else 4,
                   scenario=args.scenario if args.scenario != "uniform"
                   else "zipf",
                   sample=args.trace_sample)
        raise SystemExit(0)
    doc = run(smoke=args.smoke, use_case=args.use_case, out_path=args.out,
              shards=args.shards, scenario=args.scenario)
    if args.skew_gate:
        raise SystemExit(check_skew(doc))
    if args.single is not None:
        raise SystemExit(
            check_speedup(doc, pathlib.Path(args.single), args.min_speedup))
