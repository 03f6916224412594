"""Histogram-based decision trees and random forests (numpy training).

This module backs two distinct users:

1. The CATO Optimizer's *surrogate model* (regression forests over the
   feature-representation search space, as in HyperMapper [50]).
2. The traffic-analysis *models themselves* (decision tree for app-class,
   random forest for iot-class, as in the paper's §4).

There is no sklearn in this environment, so training is implemented here:
level-wise (breadth-first) greedy splitting on quantile-binned features,
vectorized with ``np.bincount`` over (node, feature, bin) keys — the
LightGBM-style histogram algorithm.

Trees are trained and exchanged in a *dense complete level-order layout*
(`DenseForest`): a tree of ``max_depth`` D is a perfect binary tree with
``2**D - 1`` internal slots and ``2**D`` leaf slots. Traversal is pure index
arithmetic — ``node <- 2*node + 1 + (x[feat] > thresh)`` — with no pointer
chasing. Unused internal slots are pass-through (feature 0, threshold +inf:
always branch left); unused leaves replicate their parent's prediction.

The TPU kernels (`repro.kernels.tree_infer`) serve a `CompactForest`
instead, built on the host from a `DenseForest` or from CART node arrays:
only the trees' real nodes, one window of slots per level, so a forest's
size and traversal work follow its nodes and not ``2**D``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

__all__ = [
    "CompactForest",
    "DenseForest",
    "compact_apply_np",
    "train_tree",
    "train_forest",
    "forest_apply_np",
    "forest_predict_class",
    "forest_predict_value",
]


@dataclasses.dataclass
class DenseForest:
    """A forest in dense complete level-order layout.

    Attributes:
      feature:   (n_trees, 2**D - 1) int32   — split feature per internal node.
      threshold: (n_trees, 2**D - 1) float32 — split threshold (x <= t: left).
      leaf:      (n_trees, 2**D, n_out) float32 — leaf payload (class histogram
                 for classifiers, scalar mean for regressors with n_out == 1).
      depth:     D
      n_features: number of input features the trees were trained on.
      classes:   optional class labels (classification only).
    """

    feature: np.ndarray
    threshold: np.ndarray
    leaf: np.ndarray
    depth: int
    n_features: int
    classes: Optional[np.ndarray] = None

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def n_out(self) -> int:
        return self.leaf.shape[-1]

    def feature_importance(self) -> np.ndarray:
        """Split-count importance over features (cheap RFE driver)."""
        imp = np.zeros(self.n_features, dtype=np.float64)
        live = self.threshold < np.inf  # pass-through slots have +inf
        for t in range(self.n_trees):
            f = self.feature[t][live[t]]
            np.add.at(imp, f, 1.0)
        s = imp.sum()
        return imp / s if s > 0 else imp


# ---------------------------------------------------------------------------
# Compact (real-node) layout
# ---------------------------------------------------------------------------

LANES = 128      # a level's window and the leaf table round up to this
FEATURE_BITS = 8  # a node code's low bits: the feature id
MAX_CODE = 2 ** 24  # integers a float32 holds exactly


def _round_lanes(n: int) -> int:
    return max(LANES, -(-int(n) // LANES) * LANES)


@dataclasses.dataclass(frozen=True)
class CompactForest:
    """A forest by its real nodes, in the layout the TPU kernels read.

    Level d of every tree gets a window of ``widths[d]`` node slots (the
    most real nodes any tree has on that level, rounded up to 128 lanes);
    the windows lie side by side on the last axis of `nodes`. A slot holds
    two float32 fields:

    - ``nodes[t, 0, s]``: the threshold of an internal node (``x <= t``
      goes left); +inf in a leaf slot, so no flow turns right there;
    - ``nodes[t, 1, s]``: a code, ``256 * next + feature id``, exact in
      float32 (`MAX_CODE`). For an internal node ``next`` is the slot of
      its left child within level d+1's window (the right child is the
      next slot); for a leaf it is ``-1 - leaf id``, and a flow that
      reaches a leaf keeps that code through the remaining levels.

    Level ``depth`` (the deepest) holds only leaves and has no window: a
    tree's leaves there are numbered first, by slot, so the slot a flow
    ends on is its leaf id. `leaf` is ``(T, K, L)``: tree t's leaf
    distributions on the lanes, L the most leaves of any tree rounded up
    to 128. Slots and leaves past a tree's own are zero and never read.
    """

    nodes: np.ndarray            # (T, 2, sum(widths)) float32
    leaf: np.ndarray             # (T, K, L) float32
    widths: tuple                # node slots per level, multiples of 128
    n_internal: int              # real internal nodes over all trees
    classes: Optional[np.ndarray] = None

    @property
    def n_trees(self) -> int:
        return self.nodes.shape[0]

    @property
    def depth(self) -> int:
        return len(self.widths)

    @property
    def n_out(self) -> int:
        return self.leaf.shape[1]

    @property
    def lanes(self) -> int:
        """Node slots a traversal's one-hots read for one flow, over every
        tree and level."""
        return self.n_trees * sum(self.widths)

    @property
    def nbytes(self) -> int:
        return self.nodes.nbytes + self.leaf.nbytes

    @classmethod
    def from_dense(cls, forest: DenseForest) -> "CompactForest":
        """The real nodes of a dense forest. A pass-through slot
        (threshold +inf) always goes left, so it is replaced by its left
        subtree; what the dense traversal reaches at the last level is a
        leaf. Walks give the dense forest's results exactly."""
        trees = [_dense_tree_nodes(forest.feature[t], forest.threshold[t],
                                   forest.leaf[t], int(forest.depth))
                 for t in range(forest.n_trees)]
        return cls._build(trees, forest.n_out, forest.classes)

    @classmethod
    def from_cart(cls, trees, n_out: int, columns=None,
                  classes=None) -> "CompactForest":
        """A forest from CART node arrays, one object per tree with
        scikit-learn's ``tree_`` fields: ``children_left`` and
        ``children_right`` (-1 at a leaf), ``feature``, ``threshold``
        (float64, ``x <= t`` goes left) and ``value`` (per node, the class
        counts or fractions, shape ``(n_nodes, 1, k)`` or ``(n_nodes, k)``).
        A leaf's distribution is its normalised value, written to the
        output lanes `columns` (default ``0..k-1``); a threshold becomes
        the largest float32 at or below it, so a float32 feature goes left
        exactly when it does against the float64 one."""
        out = []
        for tr in trees:
            left = np.asarray(tr.children_left, np.int64)
            value = np.asarray(tr.value, np.float64).reshape(left.size, -1)
            value = value / np.maximum(value.sum(axis=1, keepdims=True), 1e-300)
            val = np.zeros((left.size, n_out), np.float32)
            val[:, np.arange(value.shape[1]) if columns is None else columns] = value
            t64 = np.asarray(tr.threshold, np.float64)
            t32 = t64.astype(np.float32)
            up = t32.astype(np.float64) > t64
            t32[up] = np.nextafter(t32[up], np.float32(-np.inf))
            inner = left >= 0
            out.append((left, np.asarray(tr.children_right, np.int64),
                        np.where(inner, tr.feature, 0),
                        np.where(inner, t32, np.float32(np.inf)), val))
        return cls._build(out, n_out, classes)

    @classmethod
    def _build(cls, trees, n_out: int, classes) -> "CompactForest":
        """Lay out trees given as (left, right, feature, threshold, value)
        node arrays, node 0 the root and -1 for a leaf's children."""
        levels = []
        for left, right, *_ in trees:
            lv = [np.zeros(1, np.int64)]
            while True:
                inner = lv[-1][left[lv[-1]] >= 0]
                if not inner.size:
                    break
                kids = np.empty(2 * inner.size, np.int64)
                kids[0::2], kids[1::2] = left[inner], right[inner]
                lv.append(kids)
            levels.append(lv)
        D = max(1, max(len(lv) - 1 for lv in levels))
        widths = tuple(_round_lanes(max(lv[d].size for lv in levels if d < len(lv)))
                       for d in range(D))
        n_leaf = [sum(int((l[x] < 0).sum()) for x in lv)
                  for (l, *_), lv in zip(trees, levels)]
        offs = np.concatenate([[0], np.cumsum(widths)])
        L = _round_lanes(max(n_leaf))
        F = 1 + max((int(f[l >= 0].max(initial=0)) for l, _, f, *_ in trees))
        if F > 2 ** FEATURE_BITS or max(max(widths), L) << FEATURE_BITS > MAX_CODE:
            raise ValueError(f"forest too large for its node codes: {F} features, "
                             f"{max(widths)} slots on a level, {L} leaves")
        nodes = np.zeros((len(trees), 2, int(offs[-1])), np.float32)
        leaf = np.zeros((len(trees), n_out, L), np.float32)
        n_internal = 0
        for t, ((left, _, feat, thr, val), lv) in enumerate(zip(trees, levels)):
            # leaves of level D take ids 0.. by slot, the others follow
            nxt = lv[D].size if len(lv) > D else 0
            if nxt:
                leaf[t, :, :nxt] = val[lv[D]].T
            for d in range(min(D, len(lv))):
                cur = lv[d]
                inner = left[cur] >= 0
                k = int(inner.sum())
                ids = nxt + np.arange(cur.size - k)
                leaf[t, :, ids] = val[cur[~inner]]
                nxt += ids.size
                n_internal += k
                code = np.empty(cur.size, np.int64)
                code[inner] = (2 * np.arange(k) << FEATURE_BITS) + feat[cur[inner]]
                code[~inner] = (-1 - ids) << FEATURE_BITS
                sl = slice(int(offs[d]), int(offs[d]) + cur.size)
                nodes[t, 0, sl] = np.where(inner, thr[cur], np.inf)
                nodes[t, 1, sl] = code
        return cls(nodes, leaf, widths, n_internal, classes)


def _pass_through(thr, g, lvl, depth):
    """Follow pass-through slots (threshold +inf: always left) down their
    left children, to the first slot on each path that splits or to its
    leaf slot on level `depth`."""
    while True:
        inner = np.flatnonzero(lvl < depth)
        skip = inner[thr[g[inner]] == np.inf]
        if not skip.size:
            return g, lvl
        g[skip] = 2 * g[skip] + 1
        lvl[skip] += 1


def _dense_tree_nodes(feat, thr, leaf, depth):
    """One dense tree's real nodes as breadth-first CART node arrays
    ``(left, right, feature, threshold, value)``, -1 for a leaf's
    children; a leaf's value is the dense leaf its path reaches."""
    left, right, fid, th, val = [], [], [], [], []
    g, lvl = _pass_through(thr, np.zeros(1, np.int64), np.zeros(1, np.int64), depth)
    n = 0
    while g.size:
        inner = lvl < depth
        k = int(inner.sum())
        ids = n + g.size + 2 * np.arange(k)    # the next level's ids
        lc = np.full(g.size, -1, np.int64)
        rc = lc.copy()
        lc[inner], rc[inner] = ids, ids + 1
        left.append(lc)
        right.append(rc)
        slot = np.where(inner, g, 0)
        fid.append(np.where(inner, feat[slot], 0))
        th.append(np.where(inner, thr[slot], np.float32(np.inf)))
        v = np.zeros((g.size, leaf.shape[-1]), np.float32)
        v[~inner] = leaf[g[~inner] - (2 ** depth - 1)]
        val.append(v)
        n += g.size
        kids = np.empty(2 * k, np.int64)
        kids[0::2], kids[1::2] = 2 * g[inner] + 1, 2 * g[inner] + 2
        g, lvl = _pass_through(thr, kids, np.repeat(lvl[inner] + 1, 2), depth)
    return tuple(np.concatenate(a) for a in (left, right, fid, th, val))


def compact_apply_np(forest: CompactForest, X: np.ndarray) -> np.ndarray:
    """Average leaf payload across trees, walking the compact layout as the
    kernels do. Returns (n, n_out); equals `forest_apply_np` on the dense
    forest it was compacted from."""
    X = np.asarray(X, dtype=np.float32)
    n = X.shape[0]
    rows = np.arange(n)
    offs = np.concatenate([[0], np.cumsum(forest.widths)]).astype(np.int64)
    acc = np.zeros((n, forest.n_out), dtype=np.float64)
    for t in range(forest.n_trees):
        rec = forest.nodes[t]
        state = np.zeros(n, np.int64)
        for d in range(forest.depth):
            live = np.flatnonzero(state >= 0)
            s = offs[d] + state[live]
            code = rec[1, s].astype(np.int64)
            go = X[rows[live], code & (2 ** FEATURE_BITS - 1)] > rec[0, s]
            state[live] = (code >> FEATURE_BITS) + go
        acc += forest.leaf[t][:, np.where(state < 0, -1 - state, state)].T
    return (acc / forest.n_trees).astype(np.float32)


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------

def _quantile_bins(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature bin edges from quantiles. Returns (n_feat, n_bins-1)."""
    qs = np.linspace(0, 100, n_bins + 1)[1:-1]
    edges = np.nanpercentile(X, qs, axis=0).T.astype(np.float32)  # (F, B-1)
    return edges


def _digitize(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Bin each feature column by its edges. Returns uint8 (n, F)."""
    n, F = X.shape
    out = np.empty((n, F), dtype=np.uint8)
    for f in range(F):
        out[:, f] = np.searchsorted(edges[f], X[:, f], side="left")
    return out


# ---------------------------------------------------------------------------
# Level-wise tree growth
# ---------------------------------------------------------------------------

def _grow_tree(
    binned: np.ndarray,        # (n, F) uint8
    edges: np.ndarray,         # (F, B-1) float32 bin upper-edges
    y_onehot: np.ndarray,      # (n, K) float32 — one-hot labels or y[:, None]
    max_depth: int,
    min_samples_leaf: int,
    feature_subsample: Optional[np.ndarray],  # candidate feature ids or None
    rng: np.random.Generator,
    classification: bool,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Grow one tree level-wise; return dense (feature, threshold, leaf)."""
    n, F = binned.shape
    K = y_onehot.shape[1]
    B = int(edges.shape[1]) + 1
    n_internal = 2 ** max_depth - 1
    n_leaves = 2 ** max_depth

    feat_arr = np.zeros(n_internal, dtype=np.int32)
    thr_arr = np.full(n_internal, np.inf, dtype=np.float32)
    leaf_arr = np.zeros((n_leaves, K), dtype=np.float32)

    # node assignment of each sample within the current level, offset-free:
    # at level d, nodes are numbered 0..2**d-1 (local); global internal index
    # of local node j at level d is (2**d - 1) + j.
    node = np.zeros(n, dtype=np.int64)
    active = np.ones(n, dtype=bool)  # samples in nodes that may still split

    cand_feats = (
        np.arange(F, dtype=np.int64) if feature_subsample is None else feature_subsample
    )

    y_idx_full = y_onehot.argmax(axis=1) if classification else None

    # Track per-node "is frozen" (became leaf early); frozen samples keep
    # propagating left so their final leaf is deterministic.
    for d in range(max_depth):
        base = 2 ** d - 1
        idx = np.nonzero(active)[0]
        if idx.size == 0:
            break
        # compact node renumbering: only populated nodes get histogram slots
        uniq, nd = np.unique(node[idx], return_inverse=True)
        width = uniq.size
        # per (node, feature, bin, class) histogram via ONE fused bincount:
        # keys: (((nd * Fc + fi) * B + bin) * K + class)
        Fc = cand_feats.size
        sub_binned = binned[idx][:, cand_feats]  # (m, Fc)
        key_base = (nd[:, None] * Fc + np.arange(Fc)[None, :]) * B + sub_binned
        size = width * Fc * B
        if classification:
            y_idx = y_idx_full[idx]  # (m,)
            keys_k = key_base * K + y_idx[:, None]
            hist_y = np.bincount(keys_k.ravel(), minlength=size * K).astype(
                np.float64
            ).reshape(width, Fc, B, K)
            hist_cnt = hist_y.sum(axis=-1)
        else:
            hist_cnt = np.bincount(key_base.ravel(), minlength=size).astype(
                np.float64
            ).reshape(width, Fc, B)
            w = np.repeat(y_onehot[idx, 0], Fc)
            hist_y = np.bincount(
                key_base.ravel(), weights=w, minlength=size
            ).reshape(width, Fc, B)[..., None]

        # cumulative left stats over bins (split at bin b => left: bins <= b)
        cnt_l = np.cumsum(hist_cnt, axis=2)                     # (W, Fc, B)
        y_l = np.cumsum(hist_y, axis=2)                         # (W, Fc, B, K)
        cnt_tot = cnt_l[:, :, -1:]                              # (W, Fc, 1)
        y_tot = y_l[:, :, -1:, :]
        cnt_r = cnt_tot - cnt_l
        y_r = y_tot - y_l

        with np.errstate(divide="ignore", invalid="ignore"):
            if classification:
                # gini impurity decrease ∝ sum_k l_k^2 / n_l + r_k^2 / n_r
                score = np.where(cnt_l > 0, (y_l ** 2).sum(-1) / cnt_l, 0.0) + np.where(
                    cnt_r > 0, (y_r ** 2).sum(-1) / cnt_r, 0.0
                )
            else:
                # variance reduction ∝ s_l^2 / n_l + s_r^2 / n_r
                score = np.where(cnt_l > 0, y_l[..., 0] ** 2 / cnt_l, 0.0) + np.where(
                    cnt_r > 0, y_r[..., 0] ** 2 / cnt_r, 0.0
                )

        # forbid splits producing undersized children or at the last bin
        ok = (cnt_l >= min_samples_leaf) & (cnt_r >= min_samples_leaf)
        ok[:, :, -1] = False
        score = np.where(ok, score, -np.inf)

        flat = score.reshape(width, -1)
        best = np.argmax(flat, axis=1)                          # (W,)
        best_score = flat[np.arange(width), best]
        best_f_local = best // B
        best_bin = best % B

        # parent score (no-split baseline)
        node_cnt = cnt_tot[:, 0, 0]
        node_y = y_tot[:, 0, 0, :]
        with np.errstate(divide="ignore", invalid="ignore"):
            if classification:
                parent = np.where(node_cnt > 0, (node_y ** 2).sum(-1) / node_cnt, 0.0)
            else:
                parent = np.where(node_cnt > 0, node_y[:, 0] ** 2 / node_cnt, 0.0)
        do_split = best_score > parent + 1e-12

        f_global = cand_feats[best_f_local]
        thr = edges[f_global, np.minimum(best_bin, B - 2)]
        # scatter compact results back to the level's dense slots
        feat_arr[base + uniq] = np.where(do_split, f_global, 0)
        thr_arr[base + uniq] = np.where(do_split, thr, np.inf)

        # route samples: x goes right iff bin > split_bin *and* node split
        nd_split = do_split[nd]
        go_right = nd_split & (
            binned[idx, f_global[nd]] > best_bin[nd]
        )
        node[idx] = uniq[nd] * 2 + go_right
        # samples in non-split nodes keep flowing left (pass-through)

    # leaves: final node at depth max_depth
    full = node  # every sample ends at depth == number of completed levels
    # If loop broke early, propagate remaining levels as pass-through (left).
    leaf_idx = full
    cnt = np.bincount(leaf_idx, minlength=n_leaves).astype(np.float64)
    for k in range(K):
        leaf_arr[:, k] = np.bincount(
            leaf_idx, weights=y_onehot[:, k], minlength=n_leaves
        )
    nz = cnt > 0
    leaf_arr[nz] /= cnt[nz, None]
    # empty leaves inherit nearest populated ancestor value via parent fill
    if (~nz).any():
        # fill upward: average over populated sibling or global mean
        global_mean = y_onehot.mean(axis=0)
        # walk each empty leaf up through its pass-through chain: since
        # pass-through routes left, an empty leaf's nearest populated
        # relative is its left-walk sibling subtree; fall back to global mean.
        fill = leaf_arr[nz].mean(axis=0) if nz.any() else global_mean
        leaf_arr[~nz] = fill
    return feat_arr, thr_arr, leaf_arr


def train_tree(
    X: np.ndarray,
    y: np.ndarray,
    *,
    max_depth: int = 8,
    min_samples_leaf: int = 1,
    n_bins: int = 32,
    classification: bool = True,
    rng: Optional[np.random.Generator] = None,
) -> DenseForest:
    """Train a single decision tree (no bootstrap, all features)."""
    return train_forest(
        X,
        y,
        n_trees=1,
        max_depth=max_depth,
        min_samples_leaf=min_samples_leaf,
        n_bins=n_bins,
        classification=classification,
        bootstrap=False,
        max_features=None,
        rng=rng,
    )


def train_forest(
    X: np.ndarray,
    y: np.ndarray,
    *,
    n_trees: int = 100,
    max_depth: int = 8,
    min_samples_leaf: int = 1,
    n_bins: int = 32,
    classification: bool = True,
    bootstrap: bool = True,
    max_features: Optional[str | int] = "sqrt",
    rng: Optional[np.random.Generator] = None,
) -> DenseForest:
    """Train a random forest. X: (n, F) float; y: (n,) int labels or float."""
    rng = rng or np.random.default_rng(0)
    X = np.asarray(X, dtype=np.float32)
    n, F = X.shape
    if classification:
        classes, y_enc = np.unique(np.asarray(y), return_inverse=True)
        K = classes.size
        y_onehot = np.zeros((n, K), dtype=np.float32)
        y_onehot[np.arange(n), y_enc] = 1.0
    else:
        classes = None
        y_onehot = np.asarray(y, dtype=np.float64)[:, None]
        K = 1

    edges = _quantile_bins(X, n_bins)
    binned = _digitize(X, edges)

    if max_features is None:
        m_feat = F
    elif max_features == "sqrt":
        m_feat = max(1, int(np.sqrt(F)))
    else:
        m_feat = int(max_features)

    feats, thrs, leaves = [], [], []
    for t in range(n_trees):
        if bootstrap:
            sel = rng.integers(0, n, size=n)
        else:
            sel = np.arange(n)
        sub = rng.choice(F, size=m_feat, replace=False) if m_feat < F else None
        f, th, lf = _grow_tree(
            binned[sel],
            edges,
            y_onehot[sel],
            max_depth,
            min_samples_leaf,
            np.sort(sub) if sub is not None else None,
            rng,
            classification,
        )
        feats.append(f)
        thrs.append(th)
        leaves.append(lf)

    return DenseForest(
        feature=np.stack(feats).astype(np.int32),
        threshold=np.stack(thrs).astype(np.float32),
        leaf=np.stack(leaves).astype(np.float32),
        depth=max_depth,
        n_features=F,
        classes=classes,
    )


# ---------------------------------------------------------------------------
# Inference (numpy reference; the Pallas kernel mirrors this exactly)
# ---------------------------------------------------------------------------

def forest_apply_np(forest: DenseForest, X: np.ndarray) -> np.ndarray:
    """Average leaf payload across trees. Returns (n, n_out)."""
    X = np.asarray(X, dtype=np.float32)
    n = X.shape[0]
    acc = np.zeros((n, forest.n_out), dtype=np.float64)
    for t in range(forest.n_trees):
        node = np.zeros(n, dtype=np.int64)
        for _ in range(forest.depth):
            f = forest.feature[t][node]
            th = forest.threshold[t][node]
            node = 2 * node + 1 + (X[np.arange(n), f] > th)
        leaf = node - (2 ** forest.depth - 1)
        acc += forest.leaf[t][leaf]
    return (acc / forest.n_trees).astype(np.float32)


def forest_predict_class(forest: DenseForest, X: np.ndarray) -> np.ndarray:
    probs = forest_apply_np(forest, X)
    idx = probs.argmax(axis=1)
    return forest.classes[idx] if forest.classes is not None else idx


def forest_predict_value(forest: DenseForest, X: np.ndarray) -> np.ndarray:
    return forest_apply_np(forest, X)[:, 0]


def forest_predict_per_tree(forest: DenseForest, X: np.ndarray) -> np.ndarray:
    """Per-tree regression predictions, (n_trees, n). Surrogate uncertainty."""
    X = np.asarray(X, dtype=np.float32)
    n = X.shape[0]
    out = np.empty((forest.n_trees, n), dtype=np.float32)
    for t in range(forest.n_trees):
        node = np.zeros(n, dtype=np.int64)
        for _ in range(forest.depth):
            f = forest.feature[t][node]
            th = forest.threshold[t][node]
            node = 2 * node + 1 + (X[np.arange(n), f] > th)
        leaf = node - (2 ** forest.depth - 1)
        out[t] = forest.leaf[t][leaf, 0]
    return out
