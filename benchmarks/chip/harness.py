"""One run of one benchmark cell: set-up, an open-loop window on the wall
clock, drain, the correctness check, and the result line.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); every metric is read by its own
reader, ``metrics/<metric>.py``. The harness finds all three by name, so a
new cell, mix or metric is a new file.

The system under test is the program's served path: a `StreamingRuntime`
built with the program's defaults (only the table capacity is sized, from
the run's flows), fed through ``ingest_packets`` and ``poll``. The benchmark
makes the traffic, the forest and the reference itself (`gen`,
`forest_build`, `reference`); it wraps four methods of the runtime's
instances from here to time them and to keep the probabilities the timed
path produced. A traced run also attaches the program's own layer tracer
(`repro.serve.obs.Tracer`) over the window and keeps its layer table, so a
reader can take a metric from the program's ``cato.*`` spans and counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import forest_build
import gen
import reference
import tracefile
import work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

def load_cell(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())

    def reports(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return {"cell": w, "config": cfg, "mix": mix, "end_to_end": e2e,
            "per_layer": layer}


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# spans: timers (and profiler annotations when tracing) around the instance
# methods of the layers, installed from here
# ---------------------------------------------------------------------------

class Spans:
    """Per-layer wall time, calls and items, kept in memory."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.total: dict[str, list] = {}
        self.on = False
        self.submits: list = []     # (wall, n_real) per submit while on

    def wrap(self, obj, attr: str, name: str, items=None):
        """Time `obj.attr` (and annotate it when tracing) while spans are on;
        `items(args)` counts what one call handled."""
        fn = getattr(obj, attr)
        tot = self.total.setdefault(name, [0.0, 0, 0])

        def timed(*a, **k):
            if not self.on:
                return fn(*a, **k)
            t = time.perf_counter()
            with self.span(name):
                out = fn(*a, **k)
            tot[0] += time.perf_counter() - t
            tot[1] += 1
            if items is not None:
                tot[2] += items(a)
            return out

        setattr(obj, attr, timed)

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate and self.on:
            import jax

            with jax.profiler.TraceAnnotation(tracefile.SPAN_PREFIX + name):
                yield
        else:
            yield


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Result:
    """What one run measured; the metric readers take their numbers from it."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_s = 0.0
        self.pps_packets = 0
        self.ttc_s = np.zeros(0)
        self.queue_wait_s = np.zeros(0)
        self.result_wait_s = np.zeros(0)
        self.lateness_s = np.zeros(0)
        self.spans: dict = {}
        self.submits: list = []
        self.trace = None
        self.trace_window = None
        self.program = None         # the program's layer table, traced runs only
        self.shape = None
        self.peak = None
        self.kernel_match = ()
        self.chips = 1


def _check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"JAX sees {len(devs)} {devs[0].platform} device(s); the "
                     f"cell needs {chips} TPU chip(s)")
    return devs


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside this
    checkout (the path is part of the cache key), whatever the environment
    says, so two checkouts never share compiled programs and only a cell's
    first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CacheEvents:
    """Persistent-cache hits and misses seen while a run lives."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build_forest(cfg: dict, seed: int):
    """Template pool (the config's `tap_seed`), training flows and the
    grown forest (the run's seed) of one run."""
    feats = sorted(cfg["features"])
    P = int(cfg["packet_depth"])
    pool = gen.make_templates(cfg["use_case"], int(cfg["pool_flows"]),
                              gen.seed_rng(int(cfg["tap_seed"]), 1),
                              int(cfg["class_seed"]))
    tr = gen.make_templates(cfg["use_case"], int(cfg["train_flows"]),
                            gen.seed_rng(seed, 3), int(cfg["class_seed"]))
    x = window_features(feats, tr, np.arange(tr.n_flows),
                        np.minimum(tr.flow_len, P), P)
    f, th, lf = forest_build.grow(
        x.astype(np.float32), tr.label, n_trees=int(cfg["n_trees"]),
        depth=int(cfg["max_depth"]), n_classes=int(cfg["n_classes"]), seed=seed)
    return feats, pool, (f, th, lf)


def window_features(feats, tm, rows, count, P):
    return reference.features(
        feats, ts=tm.ts[rows, :P], size=tm.size[rows, :P],
        direction=tm.direction[rows, :P], ttl=tm.ttl[rows, :P],
        winsize=tm.winsize[rows, :P], flags=tm.flags[rows, :P], count=count,
        proto=tm.proto[rows], s_port=tm.s_port[rows], d_port=tm.d_port[rows])


def default_pipeline(feats, forest, cfg):
    """The served pipeline: the program's fused extract+infer kernel."""
    from repro.core.forest import DenseForest
    from repro.core.search_space import FeatureRep
    from repro.traffic.pipeline import build_pipeline

    f, th, lf = forest
    df = DenseForest(f, th, lf, int(cfg["max_depth"]), len(feats))
    P = int(cfg["packet_depth"])
    return build_pipeline(FeatureRep(tuple(feats), P), df, max_pkts=P, fused=True)


def drive(due, lo: int, block: int, seconds: float, t0: float, ingest, poll,
          after, clock=time.perf_counter):
    """Feed packets open-loop on the wall clock for `seconds`.

    Each turn maps wall time onto the stream clock (``t0`` at the window's
    start) and hands `ingest` every packet now due, at most `block` of
    them, starting at index `lo` of the due-time array; with nothing due it
    calls `poll(now)`. `after(now)` runs after every call. Stops feeding
    when the window ends: what is still undelivered is never ingested.
    Returns ``(next index, end of the packets whose call returned inside
    the window, start index of each call, window time of each call, wall
    time of the window's start)``.
    """
    n_total = len(due)
    i = returned = lo
    calls_lo, calls_t = [], []
    wall0 = clock()
    while True:
        w = clock() - wall0
        if w >= seconds:
            break
        now = t0 + w
        j = min(int(np.searchsorted(due, now, side="right")), i + block, n_total)
        if j > i:
            calls_lo.append(i)
            calls_t.append(w)
            ingest(i, j)
            i = j
            w_ret = clock() - wall0
            if w_ret <= seconds:
                returned = i
            after(t0 + w_ret)
        else:
            poll(now)
            after(t0 + clock() - wall0)
    return i, returned, np.asarray(calls_lo, np.int64), np.asarray(calls_t), wall0


def lateness(calls_lo, calls_t, due, end: int, t0: float) -> np.ndarray:
    """Per packet ingested in the window: the window time of its ingest call
    minus its due time (how late the generator ran)."""
    if not len(calls_lo):
        return np.zeros(0)
    sizes = np.diff(np.append(calls_lo, end))
    return np.repeat(calls_t, sizes) - (due[calls_lo[0]:end] - t0)


def window_waits(ready, flush, seen, t0: float, window_s: float):
    """Time-to-classification, queue wait and result wait of every flow that
    became ready inside the window, from per-batch arrays of each flow's
    ready time, its batch's flush time and the time its class was seen."""
    if not len(ready):
        return np.zeros(0), np.zeros(0), np.zeros(0)
    ready, flush, seen = (np.concatenate(a) for a in (ready, flush, seen))
    inw = (ready >= t0) & (ready < t0 + window_s)
    return seen[inw] - ready[inw], flush[inw] - ready[inw], seen[inw] - flush[inw]


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        pps: float | None = None, require_tpu: bool = True,
        control: bool = False, make_pipeline=default_pipeline,
        fault=None, log=print, overrides: dict | None = None,
        keep_trace: str | None = None,
        program_layers: bool = False) -> tuple[dict, Result]:
    """One run of one cell: the result line as a dict, and the `Result`
    its metrics were read from.

    `pps` overrides the mix's rate (the knee sweep); `keep_trace` writes
    100 ms of the traced window, with what the reducers read from it, as a
    test fixture; `program_layers` attaches the program's layer tracer in
    an untraced run too (`layer_probe.py`, to price the tracer). `make_pipeline`,
    `fault` and `overrides` exist for the tests, which drive a run on the
    CPU: `fault(rt)` may break the timed path after set-up, and `overrides`
    replaces configuration or mix keys (smaller pools, a shorter prefill).
    """
    t_proc = process_start_wall()
    spec = load_cell(cell_name)
    cfg, mix, w = dict(spec["config"]), dict(spec["mix"]), spec["cell"]
    for k, v in (overrides or {}).items():
        (cfg if k in cfg else mix)[k] = v
    import jax

    devs = _check_device(int(w["chips"])) if require_tpu else jax.devices()
    dev = devs[0]
    # set-up is timed from here: the chip's runtime start (seconds, and
    # varying by as many) is the platform's, not the served system's
    t_ready = time.time()
    marks = [("jax", t_ready - t_proc)]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.serve.runtime import StreamingRuntime

    if require_tpu:
        enable_compile_cache()
    cache = CacheEvents()
    P = int(cfg["packet_depth"])
    if float(mix["prefill_s"]) + seconds >= float(cfg["idle_timeout_s"]):
        raise ValueError("prefill_s + seconds must stay under the idle timeout")

    # -- set-up: forest, traffic, runtime, warm-up, prefill ---------------
    marks.append(("program", time.time() - t_proc))
    feats, pool, forest = build_forest(cfg, seed)
    marks.append(("forest", time.time() - t_proc))
    offered = float(pps) if pps else float(mix["rate_x_knee"]) * float(cfg["knee_pps"])
    tap = gen.build_tap(pool, seed=seed, tap_seed=int(cfg["tap_seed"]), pps=offered,
                        seconds=seconds, mix=mix, depth=P)
    marks.append(("traffic", time.time() - t_proc))
    pipe = make_pipeline(feats, forest, cfg)
    n_inst = len(tap.start)
    rt = StreamingRuntime(pipe, capacity=int(n_inst * 1.25) + 1024)
    disp = rt.dispatcher
    buckets, b = [], disp.min_bucket
    while b <= disp.max_batch:
        buckets.append(b)
        b *= 2
    pipe.warm(buckets)
    marks.append(("warm-up", time.time() - t_proc))

    spans = Spans(annotate=trace)
    captured: list = []
    orig_submit = pipe.predict_async

    def submit(ds):
        p = orig_submit(ds)
        captured.append(p)
        if spans.on:
            spans.submits.append((time.perf_counter(), work.real_flows(ds.flow_len)))
        return p

    pipe.predict_async = submit
    spans.wrap(pipe, "predict_async", "submit")
    spans.wrap(pipe, "finalize", "resolve")
    spans.wrap(disp, "gather", "gather")
    spans.wrap(rt.table, "observe_batch", "observe", items=lambda a: len(a[0]))
    tracer = None
    if trace or program_layers:
        from repro.serve.obs import Observability, Tracer

        # enabled exactly while the spans are on, that is over the window
        tracer = Tracer(sample=0.0, enabled=False)
        Observability(tracer=tracer).attach(rt)
    if fault is not None:
        fault(rt)

    recs = disp.records
    stamp: list = []                # stream-clock time each record was seen resolved

    def note_resolved(now):
        while len(stamp) < len(recs) and recs[len(stamp)].preds is not None:
            stamp.append(now)

    block = int(mix["block_pkts"])
    next_poll = 0.0
    for lo in range(0, tap.n_prefill, block):
        hi = min(lo + block, tap.n_prefill)
        rt.ingest_packets(*tap.block(lo, hi))
        now = float(tap.due[hi - 1])
        if now >= next_poll:
            rt.poll(now)
            next_poll = now + 1.0
        note_resolved(now)
    marks.append(("prefill", time.time() - t_proc))
    live0 = rt.table.n_active
    rec0 = len(recs)

    # -- window: open loop on the wall clock ------------------------------
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir, profiler_options=tracefile.capture_options())
    t0, due = tap.t0, tap.due
    n_total = len(due)

    def ingest(lo, hi):
        with spans.span("ingest"):
            rt.ingest_packets(*tap.block(lo, hi))

    def poll(now):
        with spans.span("poll"):
            rt.poll(now)

    spans.on = True
    if tracer is not None:
        tracer.enabled = True
    setup_s = time.time() - t_ready
    with spans.span("window"):
        i, returned, calls_lo, calls_t, wall0 = drive(
            due, tap.n_prefill, block, seconds, t0, ingest, poll, note_resolved)
    window_s = float(seconds)
    spans.on = False
    if tracer is not None:
        tracer.enabled = False
    if trace:
        jax.profiler.stop_trace()
    # the pending window and the ready queue resolve at the window's end
    end = t0 + time.perf_counter() - wall0
    disp.drain(end)
    note_resolved(t0 + time.perf_counter() - wall0)
    n_ingested = i
    live1 = rt.table.n_active
    rt.drain(t0 + time.perf_counter() - wall0)
    note_resolved(np.nan)
    try:
        mem_peak = int(dev.memory_stats()["peak_bytes_in_use"])
    except (TypeError, KeyError, AttributeError):
        mem_peak = 0

    # -- what the window measured -----------------------------------------
    r = Result()
    r.setup_s = setup_s
    r.window_s = window_s
    r.pps_packets = returned - tap.n_prefill
    r.lateness_s = lateness(calls_lo, calls_t, due, n_ingested, t0)
    rs = recs[rec0:]
    r.ttc_s, r.queue_wait_s, r.result_wait_s = window_waits(
        [rc.ready_ts for rc in rs], [np.full(rc.n_real, rc.flush_ts) for rc in rs],
        [np.full(rc.n_real, st) for rc, st in zip(rs, stamp[rec0:])], t0, window_s)
    r.spans = {k: list(v) for k, v in spans.total.items()}
    r.submits = spans.submits
    r.shape = work.Shape.of(cfg, forest[1])
    r.program = tracer.layers() if tracer is not None else None
    r.kernel_match = tuple(cfg["kernel_match"])
    r.chips = int(w["chips"])
    if trace:
        r.trace = tracefile.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        r.trace_window = tracefile.window(r.trace)
        # an unknown device kind is an error on the chip; CPU rehearsals
        # have no peaks and report no roofline shares
        r.peak = work.peak_for(dev.device_kind) if require_tpu else None
    lat = r.lateness_s
    log("set-up (s since process start): " + ", ".join(f"{k} {v:.3f}" for k, v in marks))
    log("flow shapes: " + ", ".join(
        f"{k} {v:.4g}" for k, v in gen.shape_stats(pool, P).items()))
    log(f"tap: offered {offered:.0f} pkts/s nominal, {(n_total - tap.n_prefill) / window_s:.0f} "
        f"pkts/s due in the window; {n_inst} flows, {tap.n_prefill} prefill packets")
    log(f"live flows: {live0} at window start, {live1} at window end "
        f"(table capacity {rt.table.capacity}); drops {rt.metrics.drops_table}")
    if lat.size:
        log(f"generator lateness: p50 {np.percentile(lat, 50) * 1e3:.3f} ms, "
            f"p99 {np.percentile(lat, 99) * 1e3:.3f} ms, max {lat.max() * 1e3:.3f} ms "
            f"over {lat.size} packets; {n_total - n_ingested} window packets never ingested")
    m = rt.metrics
    log(f"runtime: {len(recs) - rec0} batches in and after the window, flushes full "
        f"{m.flushes_full} timeout {m.flushes_timeout} drain {m.flushes_drain}; "
        f"setup_s {setup_s:.3f}; compile cache {cache.hits} hits, {cache.misses} misses")

    # -- correctness -----------------------------------------------------
    t_check = time.perf_counter()
    checks, n_cmp, n_bad, ctl = check(rt, captured, tap, pool, forest, feats, cfg,
                                      n_ingested, control)
    log(f"check: {n_cmp} flows against the reference in "
        f"{time.perf_counter() - t_check:.3f} s; drain after the window "
        f"{t_check - wall0 - seconds:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # -- the result line --------------------------------------------------
    names = [m["name"] for m in (spec["per_layer"] if trace else spec["end_to_end"])]
    units = {m["name"]: m["unit"] for m in spec["per_layer"] + spec["end_to_end"]}
    metrics = {}
    for name in names:
        v = metric_reader(name)(r)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": int(n_cmp), "failed": int(n_bad),
           "metrics": metrics, "device": device}
    if trace:
        lo, hi = r.trace_window
        busy = [tracefile.busy_ns(ops, lo, hi) for ops in r.trace["device"][:r.chips]]
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        ops0 = r.trace["device"][0] if r.trace["device"] else []
        out["breakdown"] = {
            "device_ops": tracefile.top_ops(ops0, lo, hi),
            "idle_gaps": tracefile.idle_by_host(ops0, r.trace["host"], lo, hi)}
    out["tap"] = {
        "offered_pps": offered, "due_pps": (n_total - tap.n_prefill) / window_s,
        "flows": n_inst, "live_start": live0, "live_end": live1,
        "lateness_p50_ms": float(np.percentile(lat, 50)) * 1e3 if lat.size else None,
        "lateness_p99_ms": float(np.percentile(lat, 99)) * 1e3 if lat.size else None,
        "never_ingested": n_total - n_ingested, "drops": int(rt.metrics.drops_table),
        "setup": dict(marks)}
    if ctl is not None:
        out["control"] = ctl
    if trace and keep_trace:
        save_fixture(keep_trace, r, spec)
    out["checks"] = checks
    return out, r


def check(rt, captured, tap, pool, forest, feats, cfg, n_ingested, control):
    """Compare every flow the timed path classified with the reference.

    Each flow's first prediction (the one ``results`` keeps) is compared: its
    probabilities as the timed path produced them, and its class as
    ``results`` holds it. The reference sees the flow's template packets up
    to the count a correct table holds: the packet depth, or every packet
    delivered when the flow was drained earlier.
    """
    recs = rt.dispatcher.records
    P = int(cfg["packet_depth"])
    limits = cfg["limits"]
    delivered = np.bincount(tap.inst[:n_ingested], minlength=len(tap.start))
    sent = np.flatnonzero(delivered > 0)
    results = rt.results
    n_missing = int(sum(1 for f in sent.tolist() if f not in results))
    probs = [np.asarray(p) for p in captured]
    fid_l, row_l = [], []
    if len(probs) != len(recs):
        raise RuntimeError(f"{len(probs)} submits for {len(recs)} batches")
    for k, rc in enumerate(recs):
        fid_l.append(np.asarray(rc.flow_ids, np.int64))
        row_l.append(probs[k][: rc.n_real])
    fids = np.concatenate(fid_l) if fid_l else np.zeros(0, np.int64)
    rows = np.concatenate(row_l) if row_l else np.zeros((0, int(cfg["n_classes"])))
    fids, first = np.unique(fids, return_index=True)
    served = rows[first].astype(np.float64)
    cls = np.array([int(results.get(int(f), -1)) for f in fids], np.int64)
    count = np.minimum(delivered[fids], P)
    tm_rows = tap.tmpl[fids]
    # one reference evaluation per distinct (template, packet count)
    key = tm_rows.astype(np.int64) * (P + 1) + count
    uk, inv = np.unique(key, return_inverse=True)
    x = window_features(feats, pool, uk // (P + 1), uk % (P + 1), P)
    f, th, lf = forest
    D = int(cfg["max_depth"])
    lo_u, hi_u = reference.prob_interval(x, f, th, lf, D)
    lo, hi = lo_u[inv], hi_u[inv]
    over, cg = reference.row_gaps(served, cls, lo, hi)
    g = {"prob_gap": float(over.max(initial=0.0)), "class_gap": float(cg.max(initial=0.0))}
    bad = (over > limits["prob_gap"]) | (cg > limits["class_gap"])
    checks = {"prob_gap": {"value": g["prob_gap"], "limit": limits["prob_gap"]},
              "class_gap": {"value": g["class_gap"], "limit": limits["class_gap"]},
              "missing": {"value": n_missing, "limit": limits["missing"]}}
    ctl = None
    if control:
        cp = reference.control_probs(x, f, th, lf, D)[inv]
        ctl = reference.gaps(cp, cp.argmax(axis=1), lo, hi)
    return checks, len(fids), int(bad.sum()) + n_missing, ctl


def save_fixture(path: str, r: Result, spec: dict) -> None:
    """100 ms of the traced window around its first kernel event, the
    submits of that span and the numbers this code reads from it."""
    w_lo, w_hi = r.trace_window
    first = tracefile.kernel_events(r.trace["device"][0], r.kernel_match, w_lo, w_hi)
    lo = max(w_lo, first[0][1] - 20_000_000) if first else w_lo
    hi = min(w_hi, lo + 100_000_000)
    small = Result()
    small.trace = tracefile.trim(r.trace, lo, hi)
    small.trace_window = (lo, hi)
    # the k-th submit span of the window is the k-th recorded submit
    starts = [st for name, st, _ in r.trace["host"]
              if name == tracefile.SPAN_PREFIX + "submit" and w_lo <= st < w_hi]
    small.submits = [sub for st, sub in zip(starts, r.submits) if lo <= st < hi]
    small.shape, small.peak, small.kernel_match = r.shape, r.peak, r.kernel_match
    ops0 = small.trace["device"][0]
    expect = {m["name"]: metric_reader(m["name"])(small) for m in spec["per_layer"]
              if m["source"] == "device_trace"}
    expect["busy_ns"] = tracefile.busy_ns(ops0, lo, hi)
    expect["top_ops"] = tracefile.top_ops(ops0, lo, hi)
    expect["idle_gaps"] = tracefile.idle_by_host(ops0, small.trace["host"], lo, hi)
    doc = {"trace": small.trace, "window": [lo, hi], "submits": small.submits,
           "shape": list(dataclasses.astuple(r.shape)), "peak": r.peak,
           "kernel_match": list(r.kernel_match), "expect": expect}
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(doc))
