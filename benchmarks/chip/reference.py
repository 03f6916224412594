"""Plain float64 reference of what the served path computes, and its control.

Written from the documented semantics of the use cases' features (CATO,
arXiv 2402.06099, Appendix A Table 3) and of a dense level-order forest
(``x <= threshold`` goes left; a prediction is the mean of the trees' leaf
class distributions). It imports nothing of the program under test and reads
only what the benchmark made: the template packets, the forest arrays that
`forest_build` grew, and the packet count each flow had when it was served.

A feature computed in float32 on the device can land on the other side of a
threshold than its exact value when the two lie within rounding of each
other. The reference therefore walks both branches of such a split and
returns, per flow and class, the interval ``[lo, hi]`` of the probability a
correct float32 implementation may give. A comparison whose margin exceeds
`RTOL` of the operands' magnitude is decided exactly.
"""
from __future__ import annotations

import numpy as np

FLAG_NAMES = ("cwr", "ece", "urg", "ack", "psh", "rst", "syn", "fin")
_FLAG = {n: i for i, n in enumerate(FLAG_NAMES)}

# relative margin within which a float32 feature may fall on either side of
# a split threshold: float32 rounding of a 20-term reduction is ~1e-6
RTOL = 1e-5


def _masked(v, m, stat):
    c = m.sum(axis=1)
    has = c > 0
    cc = np.maximum(c, 1)
    if stat == "sum":
        return np.where(m, v, 0.0).sum(axis=1)
    if stat == "mean":
        return np.where(has, np.where(m, v, 0.0).sum(axis=1) / cc, 0.0)
    if stat == "min":
        return np.where(has, np.where(m, v, np.inf).min(axis=1), 0.0)
    if stat == "max":
        return np.where(has, np.where(m, v, -np.inf).max(axis=1), 0.0)
    if stat == "std":
        mean = np.where(m, v, 0.0).sum(axis=1) / cc
        d = np.where(m, v - mean[:, None], 0.0)
        return np.where(has, np.sqrt((d * d).sum(axis=1) / cc), 0.0)
    if stat == "med":
        s = np.sort(np.where(m, v, np.inf), axis=1)
        lo = np.take_along_axis(s, np.maximum((c - 1) // 2, 0)[:, None], 1)[:, 0]
        hi = np.take_along_axis(s, (c // 2)[:, None], 1)[:, 0]
        return np.where(has, 0.5 * (lo + hi), 0.0)
    raise ValueError(stat)


def features(names, *, ts, size, direction, ttl, winsize, flags, count,
             proto, s_port, d_port) -> np.ndarray:
    """(n, F) float64 features of packet windows holding `count` packets.

    ts, size, ttl, winsize: (n, P) values; direction (n, P) 0/1; flags
    (n, P) packed flag byte (bit k = FLAG_NAMES[k]); count (n,); proto and
    ports (n,). Columns follow `names`.
    """
    ts = ts.astype(np.float64)
    n, P = ts.shape
    valid = np.arange(P)[None, :] < np.asarray(count)[:, None]
    dmask = {"s": valid & (direction == 0), "d": valid & (direction == 1)}
    bit = {k: (flags >> i) & 1 > 0 for k, i in _FLAG.items()}
    dur = _masked(ts, valid, "max") - _masked(ts, valid, "min")
    vals = {"bytes": size.astype(np.float64), "ttl": ttl.astype(np.float64),
            "winsize": winsize.astype(np.float64)}
    meta = {"proto": proto, "s_port": s_port, "d_port": d_port}

    def iat(d):
        m = dmask[d]
        prev = np.maximum.accumulate(np.where(m, ts, -np.inf), axis=1)
        prev = np.concatenate([np.full((n, 1), -np.inf), prev[:, :-1]], axis=1)
        ok = m & np.isfinite(prev)
        return np.where(ok, ts - np.where(ok, prev, 0.0), 0.0), ok

    def first_ts(m):
        return _masked(ts, m, "min")

    cols = []
    for name in names:
        if name == "dur":
            c = dur
        elif name in meta:
            c = np.asarray(meta[name], np.float64)
        elif name in ("s_load", "d_load"):
            byt = _masked(vals["bytes"], dmask[name[0]], "sum")
            c = np.where(dur > 0, byt * 8.0 / np.maximum(dur, 1e-9), 0.0)
        elif name in ("s_pkt_cnt", "d_pkt_cnt"):
            c = dmask[name[0]].sum(axis=1).astype(np.float64)
        elif name in ("tcp_rtt", "syn_ack", "ack_dat"):
            syn, ack = bit["syn"], bit["ack"]
            t_syn = first_ts(valid & syn & ~ack)
            t_synack = first_ts(valid & syn & ack)
            t_ack = first_ts(valid & ack & ~syn)
            a, b = {"tcp_rtt": (t_ack, t_syn), "syn_ack": (t_synack, t_syn),
                    "ack_dat": (t_ack, t_synack)}[name]
            c = np.maximum(a - b, 0.0)
        elif name.endswith("_cnt") and name[:-4] in _FLAG:
            c = (valid & bit[name[:-4]]).sum(axis=1).astype(np.float64)
        else:
            d, fam, stat = name.split("_")
            v, m = iat(d) if fam == "iat" else (vals[fam], dmask[d])
            c = _masked(v, m, stat)
        cols.append(c)
    return np.stack(cols, axis=1)


def leaves(x, feature, threshold, depth, *, rtol=RTOL):
    """Leaf index per (flow, tree), and the flows/trees whose path met a
    split within `rtol` of its threshold: (leaf (n, T), ambiguous (n, T))."""
    n = x.shape[0]
    T = feature.shape[0]
    node = np.zeros((n, T), np.int64)
    amb = np.zeros((n, T), bool)
    tr = np.arange(T)[None, :]
    for _ in range(depth):
        f = feature[tr, node]
        th = threshold[tr, node].astype(np.float64)
        xv = np.take_along_axis(x, f, axis=1)
        fin = np.isfinite(th)
        amb |= fin & (np.abs(xv - th) <= rtol * np.maximum(np.abs(xv), np.abs(np.where(fin, th, 0.0))))
        node = 2 * node + 1 + (xv > th)
    return node - (2 ** depth - 1), amb


def _reachable(xrow, feature, threshold, depth, t, rtol):
    """Every leaf of tree t that a float32 evaluation of `xrow` may reach."""
    frontier = [0]
    for _ in range(depth):
        nxt = []
        for node in frontier:
            th = float(threshold[t, node])
            xv = float(xrow[feature[t, node]])
            if np.isfinite(th) and abs(xv - th) <= rtol * max(abs(xv), abs(th)):
                nxt += [2 * node + 1, 2 * node + 2]
            else:
                nxt.append(2 * node + 1 + (xv > th))
        frontier = nxt
    return [node - (2 ** depth - 1) for node in frontier]


def prob_interval(x, feature, threshold, leaf, depth, *, rtol=RTOL):
    """(lo, hi): per flow and class, the range of the forest's mean leaf
    distribution over every path a float32 evaluation may take."""
    T = feature.shape[0]
    lf, amb = leaves(x, feature, threshold, depth, rtol=rtol)
    vals = leaf[np.arange(T)[None, :], lf].astype(np.float64)     # (n, T, K)
    lo = vals.copy()
    hi = vals.copy()
    for i, t in zip(*np.nonzero(amb)):
        cand = leaf[t, _reachable(x[i], feature, threshold, depth, t, rtol)]
        lo[i, t] = cand.min(axis=0)
        hi[i, t] = cand.max(axis=0)
    return lo.sum(axis=1) / T, hi.sum(axis=1) / T


def control_probs(x, feature, threshold, leaf, depth) -> np.ndarray:
    """The reference one precision below float32: features, thresholds and
    leaf distributions rounded to bfloat16, every split taken exactly."""
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    xb = x.astype(np.float32).astype(bf).astype(np.float64)
    thb = threshold.astype(bf).astype(np.float64)
    lb = leaf.astype(bf).astype(np.float64)
    lf, _ = leaves(xb, feature, thb, depth, rtol=0.0)
    T = feature.shape[0]
    return lb[np.arange(T)[None, :], lf].mean(axis=1)


def row_gaps(probs, classes, lo, hi):
    """Per flow served, the two numbers `correct` compares:

    prob gap: the widest distance by which a served probability lies outside
      the reference's interval;
    class gap: the widest margin by which some class's least probability
      beats the most the served class can have (0 when the served class can
      be the reference's best; infinite when the flow has no class).
    """
    p = np.asarray(probs, np.float64)
    if not len(p):
        return np.zeros(0), np.zeros(0)
    over = np.maximum(np.maximum(p - hi, lo - p).max(axis=1), 0.0)
    c = np.asarray(classes, np.int64)
    cg = lo.max(axis=1) - hi[np.arange(len(c)), np.clip(c, 0, None)]
    cg = np.where(c < 0, np.inf, np.maximum(cg, 0.0))
    return over, cg


def gaps(probs, classes, lo, hi) -> dict:
    """The widest prob gap and class gap over the flows (`row_gaps`)."""
    over, cg = row_gaps(probs, classes, lo, hi)
    return {"prob_gap": float(over.max(initial=0.0)),
            "class_gap": float(cg.max(initial=0.0))}
