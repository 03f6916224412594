"""Profiler trace capture and its reduction to device busy time, kernel time,
top device operations and idle gaps named by the host span they fall in.

A captured trace is first normalised to plain lists, so that the reducers
run the same on a trace just recorded and on the small recorded fixture the
tests read:

    {"device":  [[[name, start_ns, dur_ns], ...] per chip],
     "host":    [[name, start_ns, dur_ns], ...],
     "program": [[name, start_ns, dur_ns], ...]}

Device events are those of the ``XLA Ops`` line of each ``/device:TPU:n``
plane, named by their HLO instruction (``%fused_forest_infer.1``: the event
names hold the whole instruction text, of which the name is the part before
`` = ``); host events are the benchmark's own ``TraceAnnotation`` spans
(names starting ``bench.``), program events the program's layer spans
(``cato.``, while its tracer is attached). All carry the profiler's own
clock.
"""
from __future__ import annotations

import glob
import os

import numpy as np

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "cato."
# what the host was doing, most specific first: a time point inside several
# spans is charged to the first of them in this order
HOST_ORDER = ("submit", "resolve", "gather", "observe", "poll", "ingest")


def capture_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(log_dir: str) -> dict:
    """Normalise the ``.xplane.pb`` that `jax.profiler` wrote under `log_dir`."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    device, host, program = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[e.name.split(" = ", 1)[0], int(e.start_ns),
                             int(e.duration_ns)] for e in line.events]
            device.append(sorted(ops, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = [e.name, int(e.start_ns), int(e.duration_ns)]
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(ev)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append(ev)
    return {"device": device, "host": sorted(host, key=lambda e: e[1]),
            "program": sorted(program, key=lambda e: e[1])}


def window(trace: dict) -> tuple[int, int]:
    """[start, end) of the ``bench.window`` span, in trace ns."""
    for name, s, d in trace["host"]:
        if name == SPAN_PREFIX + "window":
            return s, s + d
    raise ValueError("trace holds no bench.window span")


def _clip(ops, lo, hi) -> np.ndarray:
    if not ops:
        return np.zeros((0, 2), np.int64)
    a = np.array([[s, s + d] for _, s, d in ops], np.int64)
    a[:, 0] = np.maximum(a[:, 0], lo)
    a[:, 1] = np.minimum(a[:, 1], hi)
    return a[a[:, 1] > a[:, 0]]


def busy_intervals(ops, lo: int, hi: int) -> np.ndarray:
    """Union of the device operations' intervals within [lo, hi), merged."""
    a = _clip(ops, lo, hi)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, np.int64)


def busy_ns(ops, lo: int, hi: int) -> int:
    b = busy_intervals(ops, lo, hi)
    return int((b[:, 1] - b[:, 0]).sum()) if len(b) else 0


def is_kernel(name: str, patterns) -> bool:
    """An HLO instruction name such as ``%fused_forest_infer.1`` belongs to
    the kernel named by one of `patterns` (``%fused_forest_infer``)."""
    return any(name == p or name.startswith(p + ".") for p in patterns)


def kernel_events(ops, patterns, lo: int, hi: int) -> list:
    """Device events of the named kernel that start inside [lo, hi)."""
    return [e for e in ops if lo <= e[1] < hi and is_kernel(e[0], patterns)]


def top_ops(ops, lo: int, hi: int, k: int = 10) -> list:
    """The device operations that took most time in [lo, hi): [[name, s]]."""
    tot: dict[str, int] = {}
    for name, s, d in ops:
        if lo <= s < hi:
            tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]


def idle_by_host(ops, host, lo: int, hi: int, k: int = 10) -> list:
    """Device idle time in [lo, hi), charged to what the host was doing.

    Each stretch in which no device operation runs is split by the
    benchmark's host spans (`HOST_ORDER`, most specific first); time no span
    covers is charged to ``generator`` (the load generator's own loop).
    Returns [[what, seconds]] sorted by time, at most `k` entries.
    """
    busy = busy_intervals(ops, lo, hi)
    gaps = []
    cur = lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    # boundary sweep: +1/-1 per span kind, gaps as a separate layer
    pts = []
    for name, s, d in host:
        kind = name[len(SPAN_PREFIX):]
        if kind in HOST_ORDER:
            pts.append((s, 1, kind))
            pts.append((s + d, -1, kind))
    for s, e in gaps:
        pts.append((s, 1, "#gap"))
        pts.append((e, -1, "#gap"))
    pts.sort(key=lambda p: (p[0], p[1]))
    depth = {kind: 0 for kind in HOST_ORDER}
    depth["#gap"] = 0
    tot: dict[str, int] = {}
    prev = None
    for t, step, kind in pts:
        if prev is not None and t > prev and depth["#gap"] > 0:
            what = next((k_ for k_ in HOST_ORDER if depth[k_] > 0), "generator")
            tot[what] = tot.get(what, 0) + (t - prev)
        depth[kind] += step
        prev = t
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]


def trim(trace: dict, lo: int, hi: int) -> dict:
    """The part of a normalised trace that overlaps [lo, hi), with the
    ``bench.window`` span set to [lo, hi): a small fixture for the tests."""
    def keep(evs):
        return [e for e in evs if e[1] < hi and e[1] + e[2] > lo
                and e[0] != SPAN_PREFIX + "window"]
    return {"device": [keep(ops) for ops in trace["device"]],
            "host": [[SPAN_PREFIX + "window", lo, hi - lo]] + keep(trace["host"]),
            "program": keep(trace["program"])}
