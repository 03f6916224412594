"""One run of a benchmark cell with the program's layer tracer on.

    python3 benchmarks/chip/layer_probe.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``run.py``'s: the same set-up, window, drain and check, and the
same result line, with the program's layer tracer attached as in a traced
run (`harness.run`), with ``--trace 0`` too. The last line of standard
output is one more JSON document:

- ``layers``: the program's layer table (`Tracer.layers()`: calls, items,
  total and self ns of each ``cato.*`` span) and its counters, the
  compiles inside the window among them;
- ``wraps``: the harness's timers around the same calls ([seconds, calls,
  items] by name), for comparison;
- ``quantities``: `program.quantities` of the layer table;
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
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import program  # noqa: E402
import tracefile  # noqa: E402

# what the host was doing, most specific first (each span lies inside the
# ones after it that can hold it)
LAYER_ORDER = ("resolve.wait", "resolve", "submit", "gather", "flush", "ready",
               "observe.partition", "observe.slow", "observe.fast", "observe",
               "ingest.plan", "poll", "ingest")
# the harness's host spans whose idle time the program's spans should cover
COVERED = ("observe", "ingest", "submit", "resolve", "gather", "poll")


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
        kind = name[len(tracefile.PROGRAM_PREFIX):]
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
    """`harness.run` with the layer tracer on over the window; returns
    the result line and the probe's document."""
    out, r = harness.run(cell, seed, seconds, trace, program_layers=True, **run_kw)
    doc = {"layers": r.program, "wraps": r.spans,
           "quantities": program.quantities(r.program)}
    if trace:
        t = r.trace
        lo, hi = r.trace_window
        ops = t["device"][0] if t["device"] else []
        idle = idle_by_span(ops, t["program"], lo, hi)
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
