"""One run of a benchmark cell with the program's layer tracer on.

    python3 benchmarks/chip/layer_probe.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``run.py``'s: the same set-up, window, drain and check, and the
same result line. A `repro.serve.obs.Tracer` is attached to the runtime
before the prefill and enabled exactly while the harness's own spans are
on, that is over the window. The last line of standard output is one more
JSON document:

- ``layers``: the program's layer table (`Tracer.layers()`: calls, items,
  total and self ns of each ``cato.*`` span) and its counters, the
  compiles inside the window among them;
- ``wraps``: the harness's timers around the same calls ([seconds, calls,
  items] by name), for comparison;
- ``quantities``: `quantities` of the layer table;
- with ``--trace 1``, ``idle_by_span``: the device's idle time in the
  window charged to the innermost ``cato.*`` span open at the time (time
  no span covers is ``generator``), and ``coverage``: the share of the idle
  time that the harness charges to its host spans that the program's spans
  cover.

Tracing costs host time while it is on: compare the ``pps`` of this
command with ``run.py``'s, on the same seeds, for what it costs.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import tracefile  # noqa: E402

LAYER_PREFIX = "cato."
# what the host was doing, most specific first (each span lies inside the
# ones after it that can hold it)
LAYER_ORDER = ("resolve.wait", "resolve", "submit", "gather", "flush", "ready",
               "observe.partition", "observe.slow", "observe.fast", "observe",
               "poll", "ingest")
# the harness's host spans whose idle time the program's spans should cover
COVERED = ("observe", "ingest", "submit", "resolve", "gather", "poll")


def quantities(layers: dict) -> dict:
    """Per-layer quantities of a layer table; a quantity whose span never
    ran is left out.

    - ``pkts_per_observe``: packets per `FlowTable.observe_batch` call;
    - ``slow_path_pct``: share of those packets that took the ordered
      scalar pass (``observe.slow``);
    - ``observe_slow_time_pct``: share of ``observe`` time spent there;
    - ``dispatch_ns_per_pkt``: self time of ``ingest``, ``ready``,
      ``flush`` and ``poll`` per ingested packet.
    """
    sp = layers["spans"]

    def get(name, key):
        return sp.get(name, {}).get(key, 0)

    out = {}
    if get("observe", "calls"):
        out["pkts_per_observe"] = get("observe", "items") / get("observe", "calls")
    if get("observe", "items"):
        out["slow_path_pct"] = 100.0 * get("observe.slow", "items") / get("observe", "items")
    if get("observe", "total_ns"):
        out["observe_slow_time_pct"] = (100.0 * get("observe.slow", "total_ns")
                                        / get("observe", "total_ns"))
    if get("ingest", "items"):
        out["dispatch_ns_per_pkt"] = sum(
            get(n, "self_ns") for n in ("ingest", "ready", "flush", "poll")
        ) / get("ingest", "items")
    return out


def program_events(log_dir: str) -> list:
    """The ``cato.*`` host events of the capture under `log_dir`, as
    [name, start_ns, dur_ns] sorted by start."""
    import jax

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events if e.name.startswith(LAYER_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def idle_by_span(ops, spans, lo: int, hi: int) -> dict:
    """Device idle time in [lo, hi) by the innermost ``cato.*`` span open
    (`LAYER_ORDER`), in seconds; time no span covers is ``generator``."""
    busy = tracefile.busy_intervals(ops, lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, int(e))
    if cur < hi:
        gaps.append((cur, hi))
    pts = [(s, 1, "#gap") for s, _ in gaps] + [(e, -1, "#gap") for _, e in gaps]
    for name, s, d in spans:
        kind = name[len(LAYER_PREFIX):]
        if kind in LAYER_ORDER:
            pts += [(s, 1, kind), (s + d, -1, kind)]
    pts.sort(key=lambda p: (p[0], p[1]))
    depth = dict.fromkeys(LAYER_ORDER + ("#gap",), 0)
    tot: dict = {}
    prev = None
    for t, step, kind in pts:
        if prev is not None and t > prev and depth["#gap"] > 0:
            what = next((k for k in LAYER_ORDER if depth[k] > 0), "generator")
            tot[what] = tot.get(what, 0) + (t - prev)
        depth[kind] += step
        prev = t
    return {k: v / 1e9 for k, v in sorted(tot.items(), key=lambda kv: -kv[1])}


def probe(cell: str, seed: int, seconds: float, trace: bool, **run_kw) -> tuple:
    """`harness.run` with the layer tracer on over the window; returns the
    result line and the probe's document."""
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.serve.obs import Observability, Tracer

    tracer = Tracer(sample=0.0, enabled=False)
    got: dict = {}

    class WindowSpans(harness.Spans):
        """The harness's spans; the tracer is on exactly while they are."""

        @property
        def on(self):
            return self._on

        @on.setter
        def on(self, value):
            self._on = value
            tracer.enabled = value

        def __init__(self, annotate):
            super().__init__(annotate)
            got["wraps"] = self.total

    def load(log_dir):
        tr = load_devices(log_dir)
        got["trace"], got["program"] = tr, program_events(log_dir)
        return tr

    def attach(rt):
        Observability(tracer=tracer).attach(rt)

    spans_cls, load_devices = harness.Spans, tracefile.load
    harness.Spans, tracefile.load = WindowSpans, load
    try:
        out = harness.run(cell, seed, seconds, trace, fault=attach, **run_kw)
    finally:
        harness.Spans, tracefile.load = spans_cls, load_devices
    lay = tracer.layers()
    doc = {"layers": lay, "wraps": {k: list(v) for k, v in got["wraps"].items()},
           "quantities": quantities(lay)}
    if trace:
        t = got["trace"]
        lo, hi = tracefile.window(t)
        ops = t["device"][0] if t["device"] else []
        idle = idle_by_span(ops, got["program"], lo, hi)
        host = dict(tracefile.idle_by_host(ops, t["host"], lo, hi, k=len(COVERED) + 1))
        want = sum(host.get(k, 0.0) for k in COVERED)
        doc["idle_by_span"] = idle
        doc["coverage"] = (sum(v for k, v in idle.items() if k != "generator") / want
                           if want else None)
    return out, doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        out, doc = probe(a.workload, a.seed, a.seconds, bool(a.trace),
                         log=lambda s: print(s, file=sys.stderr, flush=True))
    except harness.NoChip as e:
        print(f"layer_probe.py: {e}; no result", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
