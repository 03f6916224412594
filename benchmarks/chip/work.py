"""Required work of the fused extract+infer step, counted from a cell's shapes.

Counts what the algorithm needs for the flows that were served, not what the
current kernel does: no one-hot reads, no extra precision passes, no padding
rows. A kernel that drops any of those can therefore not read above 100% of
its roofline. For a configuration with packet depth P, F features, T trees of
depth D and K classes:

- bytes per real flow: the packet window read once (ts, size, ttl and winsize
  as float32, direction and the packed flag byte as one byte each, per
  packet), the four per-flow metadata floats (packet count, proto, ports),
  and the K float32 probabilities written;
- bytes per call: the forest tables read once (feature id and threshold per
  internal node, 4 bytes each; a K-float32 leaf distribution per leaf);
- operations per real flow: one reduction step per packet per feature
  column (P x F), one comparison per tree level (T x D), and one vote add
  per tree and class (T x K).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"

PKT_BYTES = 4 * 4 + 1 + 1
META_BYTES = 4 * 4


@dataclasses.dataclass(frozen=True)
class Shape:
    depth: int          # packets per flow window (P)
    n_features: int     # F
    n_trees: int        # T
    tree_depth: int     # D
    n_classes: int      # K

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(int(cfg["packet_depth"]), len(cfg["features"]),
                   int(cfg["n_trees"]), int(cfg["max_depth"]),
                   int(cfg["n_classes"]))


def bytes_per_flow(s: Shape) -> int:
    return s.depth * PKT_BYTES + META_BYTES + 4 * s.n_classes


def bytes_per_call(s: Shape) -> int:
    internal = 2 ** s.tree_depth - 1
    return s.n_trees * (internal * 8 + 2 ** s.tree_depth * s.n_classes * 4)


def ops_per_flow(s: Shape) -> int:
    return s.depth * s.n_features + s.n_trees * s.tree_depth + s.n_trees * s.n_classes


def call_work(s: Shape, n_real: int) -> tuple[int, int]:
    """(operations, bytes) that one call serving `n_real` flows requires,
    whatever batch bucket it was padded to."""
    return n_real * ops_per_flow(s), n_real * bytes_per_flow(s) + bytes_per_call(s)


def roofline_s(s: Shape, n_real: int, peak: dict) -> float:
    """Least time the chip could take for one call: the larger of its
    operations over peak FLOP/s and its bytes over peak bandwidth."""
    ops, byt = call_work(s, n_real)
    return max(ops / peak["flops_per_s"], byt / peak["hbm_bytes_per_s"])


def real_flows(flow_len) -> int:
    """Real flows in a submitted batch: padding rows hold no packets."""
    return int(np.count_nonzero(flow_len))


def peak_for(kind: str) -> dict:
    """The peaks row of a device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]
