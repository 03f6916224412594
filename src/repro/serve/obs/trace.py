"""Bounded ring-buffer span tracer, plus wall-clock layer spans
(DESIGN.md §11.2).

The ring holds events on the **caller's clock** — the `now` a runtime is
handed: wall time in live use, the replay's virtual two-lane
`_WorkerClock` seconds under replay — so a trace of a replay is exactly
as deterministic as the replay itself. Two span families live there:

- **worker stage spans** (Chrome ``ph: "X"`` complete events): per-block
  ingest service envelopes and per-batch inference service, charged by
  `_WorkerClock` on the lane that served them. ``pid`` is the shard,
  ``tid`` the lane (0 = ingest, 1 = inference, 2 = control).
- **flow lifecycle spans** (Chrome async ``b``/``n``/``e`` events keyed
  by flow id): ingest (first packet) → ready → flush (with reason) →
  emit. The emit edge is the batch's resolve (`BatchRecord.resolved_ts`)
  in live use, and the inference lane's completion edge under replay
  (`replay_clock`).

Storage is a preallocated numpy ring of `capacity` events — recording
never allocates per event on the vectorized path and never grows; once
the ring wraps, the oldest events fall off (``dropped`` counts them).
Flows are sampled at `sample` by a deterministic hash threshold on the
flow id, so a 1% trace keeps *whole* lifecycles, never partial ones, and
two replays of the same stream sample the same flows.

**Layer spans** are the served path's own timing, on the wall clock
(``time.perf_counter_ns``), and never enter the ring. A hook site opens
one with `layer(name, items)` while the tracer is enabled. Each span adds
calls, items, total ns and self ns (total less what its child spans
cover) to a per-name table and, while a profiler capture runs, enters
``jax.profiler.TraceAnnotation("cato.<name>")``, so the capture shows it
on the host plane, on the same clock as the device ops. `count(name, k)` adds to a counter;
XLA compiles are counted (``jax.compiles``) while the tracer is enabled.
`layers()` returns the table and the counters.

`chrome()` exports the Chrome trace-event JSON (``chrome://tracing`` /
Perfetto load it directly); timestamps are exported in microseconds.

Tracing is **off by default** everywhere: every hook site guards on
``tracer is not None`` (and ``tracer.enabled`` before opening a layer
span), so the untraced hot path pays one attribute test per site.
"""
from __future__ import annotations

import json
import pathlib
import weakref
from time import perf_counter_ns
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["COMPILES", "Tracer", "TID_INGEST", "TID_INFER", "TID_CONTROL",
           "TID_TENANT0"]

TID_INGEST = 0
TID_INFER = 1
TID_CONTROL = 2
# multi-tenant serving (DESIGN.md §15): per-tenant infer sub-lanes start
# here — tenant t's share of each fused batch lands on tid TID_TENANT0 + t
TID_TENANT0 = 3

_TID_NAMES = {TID_INGEST: "ingest lane", TID_INFER: "inference lane",
              TID_CONTROL: "control plane"}

# event phases, packed as u1
_PH_X, _PH_B, _PH_E, _PH_N, _PH_I = 0, 1, 2, 3, 4
_PH_CHR = {_PH_X: "X", _PH_B: "b", _PH_E: "e", _PH_N: "n", _PH_I: "i"}


# layer spans: profiler label prefix, and the counter of XLA compiles seen
# while a tracer is enabled (JAX's `jax._src.dispatch.BACKEND_COMPILE_EVENT`)
LAYER_PREFIX = "cato."
COMPILES = "jax.compiles"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        for tr in list(_LIVE):
            if tr.enabled:
                tr.count(COMPILES)


def _listen(tracer: "Tracer") -> None:
    """Count compiles for `tracer`; JAX's listener is registered once per
    process."""
    global _listening
    _LIVE.add(tracer)
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


class _Layer:
    """One layer span of a `Tracer` (`Tracer.layer`): a context manager,
    or `start()` / `stop(items)` where a ``with`` block does not fit."""

    __slots__ = ("_tracer", "name", "items", "_ann", "_parent", "_child",
                 "_t0")

    def __init__(self, tracer: "Tracer", name: str, items: int):
        self._tracer = tracer
        self.name = name
        self.items = items

    def start(self) -> "_Layer":
        tr = self._tracer
        self._parent = tr._open
        tr._open = self
        self._child = 0
        self._t0 = perf_counter_ns()
        # the annotation costs as much as the rest of the span: enter it
        # only while a profiler capture is running
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(LAYER_PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        return self

    def stop(self, items: Optional[int] = None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        dur = perf_counter_ns() - self._t0
        tr = self._tracer
        parent = tr._open = self._parent
        if parent is not None:
            parent._child += dur
        if items is not None:
            self.items = items
        row = tr._rows.get(self.name)
        if row is None:
            row = tr._rows[self.name] = [0, 0, 0, 0]
        row[0] += 1
        row[1] += self.items
        row[2] += dur
        row[3] += dur - self._child

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: uniform u64 from flow ids (sampling hash)."""
    x = np.asarray(x).astype(np.uint64, copy=True)
    x += np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class Tracer:
    def __init__(
        self,
        capacity: int = 1 << 16,
        sample: float = 1.0,
        enabled: bool = True,
        seed: int = 0,
    ):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if not 0.0 <= sample <= 1.0:
            raise ValueError("sample must be in [0, 1]")
        self.capacity = int(capacity)
        self.sample = float(sample)
        self.enabled = bool(enabled)
        # threshold comparison against the mixed id; seed shifts the hash
        # so distinct tracers can sample distinct flow subsets
        self._seed = np.uint64(seed)
        self._thresh = np.uint64(min(int(sample * float(2**64)), 2**64 - 1))
        self._sample_all = sample >= 1.0
        cap = self.capacity
        self._ph = np.zeros(cap, np.uint8)
        self._name = np.zeros(cap, np.int32)
        self._ts = np.zeros(cap, np.float64)    # seconds on the caller's clock
        self._dur = np.zeros(cap, np.float64)
        self._pid = np.zeros(cap, np.int32)
        self._tid = np.zeros(cap, np.int32)
        self._id = np.zeros(cap, np.int64)      # flow id for async events
        self._names: list[str] = []
        self._intern: dict[str, int] = {}
        self.total = 0                           # events ever recorded
        # set by the replay's worker clock: lifecycles close on it, at the
        # inference lane's completion edge, not at the dispatcher's resolve
        self.replay_clock = False
        # layer spans: name -> [calls, items, total ns, self ns]; counters;
        # the innermost open span
        self._rows: dict[str, list] = {}
        self._counters: dict[str, int] = {}
        self._open: Optional[_Layer] = None
        _listen(self)

    # -- bookkeeping ---------------------------------------------------------

    def __len__(self) -> int:
        return min(self.total, self.capacity)

    @property
    def dropped(self) -> int:
        """Events that fell off the ring (oldest-first)."""
        return max(0, self.total - self.capacity)

    def _name_id(self, name: str) -> int:
        i = self._intern.get(name)
        if i is None:
            i = len(self._names)
            self._names.append(name)
            self._intern[name] = i
        return i

    def _slots(self, k: int) -> np.ndarray:
        idx = (self.total + np.arange(k)) % self.capacity
        self.total += k
        return idx

    # -- sampling ------------------------------------------------------------

    def sample_mask(self, flow_ids: np.ndarray) -> np.ndarray:
        """Deterministic per-flow keep mask at the configured rate."""
        if self._sample_all:
            return np.ones(len(flow_ids), bool)
        if self.sample <= 0.0:
            return np.zeros(len(flow_ids), bool)
        return _mix64(np.asarray(flow_ids, np.int64) + np.int64(self._seed)) \
            < self._thresh

    # -- recording (vectorized; every method no-ops when disabled) -----------

    def span(self, name: str, ts: float, dur: float, *, pid: int = 0,
             tid: int = 0) -> None:
        if not self.enabled:
            return
        self.span_many(name, np.asarray([ts]), np.asarray([dur]),
                       pid=pid, tid=tid)

    def span_many(self, name: str, ts: np.ndarray, dur: np.ndarray, *,
                  pid: int = 0, tid: int = 0) -> None:
        """One ``X`` complete event per (ts, dur) pair."""
        if not self.enabled or len(ts) == 0:
            return
        idx = self._slots(len(ts))
        self._ph[idx] = _PH_X
        self._name[idx] = self._name_id(name)
        self._ts[idx] = ts
        self._dur[idx] = np.maximum(dur, 0.0)
        self._pid[idx] = pid
        self._tid[idx] = tid
        self._id[idx] = -1

    def instant(self, name: str, now_pkts: float, *, pid: int = 0,
                tid: int = 0) -> None:
        """One point event at `now_pkts` on the replay packet clock (the
        canonical unit definition lives in `repro.serve.control.plane`)."""
        if not self.enabled:
            return
        idx = self._slots(1)
        self._ph[idx] = _PH_I
        self._name[idx] = self._name_id(name)
        self._ts[idx] = now_pkts
        self._dur[idx] = 0.0
        self._pid[idx] = pid
        self._tid[idx] = tid
        self._id[idx] = -1

    def _flow_event(self, ph: int, name: str, ids: np.ndarray,
                    ts: np.ndarray, pid: int) -> None:
        if not self.enabled or len(ids) == 0:
            return
        idx = self._slots(len(ids))
        self._ph[idx] = ph
        self._name[idx] = self._name_id(name)
        self._ts[idx] = ts
        self._dur[idx] = 0.0
        self._pid[idx] = pid
        self._tid[idx] = TID_INGEST
        self._id[idx] = np.asarray(ids, np.int64)

    def flow_begin(self, ids: np.ndarray, ts: np.ndarray, *,
                   pid: int = 0) -> None:
        """Open one async lifecycle span per flow at its first-packet time."""
        self._flow_event(_PH_B, "flow", ids, ts, pid)

    def flow_mark(self, name: str, ids: np.ndarray, ts: np.ndarray, *,
                  pid: int = 0) -> None:
        """Milestone inside open lifecycles (ready / flush.reason / ...)."""
        self._flow_event(_PH_N, name, ids, ts, pid)

    def flow_end(self, ids: np.ndarray, ts: np.ndarray, *,
                 pid: int = 0) -> None:
        """Close lifecycles at the prediction-emit edge."""
        self._flow_event(_PH_E, "flow", ids, ts, pid)

    # -- layer spans and counters (wall clock; never in the ring) ------------

    def layer(self, name: str, items: int = 0) -> _Layer:
        """A layer span over `items` (packets, flows, bytes...). Hook sites
        open one only while the tracer is enabled."""
        return _Layer(self, name, items)

    def count(self, name: str, k: int = 1) -> None:
        self._counters[name] = self._counters.get(name, 0) + k

    def layers(self) -> dict:
        """The layer table and the counters, as plain numbers."""
        return {
            "spans": {name: {"calls": r[0], "items": r[1], "total_ns": r[2],
                             "self_ns": r[3]}
                      for name, r in self._rows.items()},
            "counters": dict(self._counters),
        }

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict]:
        """Ring contents in record order as Chrome trace-event dicts."""
        n = len(self)
        if n == 0:
            return []
        if self.total <= self.capacity:
            order = np.arange(n)
        else:  # wrapped: oldest surviving event first
            order = (self.total + np.arange(self.capacity)) % self.capacity
        out = []
        for i in order:
            ph = int(self._ph[i])
            ev = {
                "name": self._names[int(self._name[i])],
                "ph": _PH_CHR[ph],
                "ts": float(self._ts[i]) * 1e6,   # Chrome wants microseconds
                "pid": int(self._pid[i]),
                "tid": int(self._tid[i]),
            }
            if ph == _PH_X:
                ev["dur"] = float(self._dur[i]) * 1e6
            elif ph == _PH_I:
                ev["s"] = "t"
            else:  # async lifecycle event
                ev["cat"] = "flow"
                ev["id"] = int(self._id[i])
            out.append(ev)
        return out

    def chrome(self) -> dict:
        """Full Chrome trace-event document (with lane/shard labels)."""
        meta = []
        pids = sorted({int(p) for p in
                       self._pid[: len(self)].tolist()}) if len(self) else []
        tids = sorted({int(t) for t in self._tid[: len(self)].tolist()}) \
            if len(self) else []
        for pid in pids:
            meta.append({"ph": "M", "name": "process_name", "pid": pid,
                         "args": {"name": f"shard {pid}"}})
            for tid, label in _TID_NAMES.items():
                meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                             "tid": tid, "args": {"name": label}})
            for tid in tids:
                if tid >= TID_TENANT0:
                    meta.append({
                        "ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid,
                        "args": {"name": f"tenant {tid - TID_TENANT0} infer"},
                    })
        return {
            "traceEvents": meta + self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "the caller's now",
                "sample_rate": self.sample,
                "events_recorded": self.total,
                "events_dropped": self.dropped,
            },
        }

    def save(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.chrome()) + "\n")
        return path

    def summary(self) -> Optional[dict]:
        if self.total == 0:
            return None
        return {
            "events": self.total,
            "retained": len(self),
            "dropped": self.dropped,
            "capacity": self.capacity,
            "sample": self.sample,
        }
