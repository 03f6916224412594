"""Required work of the fused extract+infer step, counted from a cell's shapes.

Counts what the algorithm needs for the flows that were served, not what the
current kernel does: no one-hot reads, no extra precision passes, no padding
rows. A kernel that drops any of those can therefore not read above 100% of
its roofline. Padding is not work, and that holds for the forest too: the
dense level-order layout pads every tree to 2**max_depth slots, and a slot
below a leaf of the fitted tree is a pass-through split (threshold +inf)
that only repeats its parent's leaf, so only the fitted tree's own nodes
count. For a configuration with packet depth P, F features, K classes and a
grown forest of T trees with N real internal nodes (finite thresholds), N + T
real leaves and a deepest real level D:

- bytes per real flow: the packet window read once (ts, size, ttl and winsize
  as float32, direction and the packed flag byte as one byte each, per
  packet), the four per-flow metadata floats (packet count, proto, ports),
  and the K float32 probabilities written;
- bytes per call: the real forest tables read once (feature id and threshold
  per internal node, 4 bytes each; a K-float32 leaf distribution per leaf);
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
    n_internal: int     # N: real internal nodes over all trees
    tree_depth: int     # D: deepest real level
    n_classes: int      # K

    @property
    def n_leaves(self) -> int:
        """Real leaves: a binary tree has one more than its internal nodes."""
        return self.n_internal + self.n_trees

    @classmethod
    def of(cls, cfg: dict, threshold: np.ndarray) -> "Shape":
        """The shape of a configuration's forest as grown: `threshold` is
        its dense (T, 2**max_depth - 1) threshold table."""
        n_internal, deepest = real_nodes(threshold)
        return cls(int(cfg["packet_depth"]), len(cfg["features"]),
                   threshold.shape[0], n_internal, deepest, int(cfg["n_classes"]))


def real_nodes(threshold: np.ndarray) -> tuple[int, int]:
    """(real internal nodes, deepest real level) of a dense level-order
    forest: the slots with a finite threshold, and one level below the
    deepest of them (0 for a forest of bare leaves)."""
    _, slot = np.nonzero(np.isfinite(threshold))
    # slot i lies on level bit_length(i + 1) - 1
    deepest = int(slot.max() + 1).bit_length() if slot.size else 0
    return int(slot.size), deepest


def bytes_per_flow(s: Shape) -> int:
    return s.depth * PKT_BYTES + META_BYTES + 4 * s.n_classes


def bytes_per_call(s: Shape) -> int:
    return s.n_internal * 8 + s.n_leaves * s.n_classes * 4


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
