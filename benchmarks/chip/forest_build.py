"""Grow a configuration's forest from the seed, in the dense level-order layout.

The forest is the deployment's model, so the benchmark makes it, the way a
model benchmark makes its weights: scikit-learn's CART grows it at the
configuration's fixed tree count and depth (no per-seed search, so every seed
compiles the same kernel shapes), on template flows that the seed draws, with
features that `reference.features` computes. The result is the layout the
served kernel reads: a perfect binary tree of `depth` levels per tree,
``x <= threshold`` going left; a leaf of the fitted tree that sits above the
last level becomes pass-through splits (threshold +inf) down to a leaf that
repeats its distribution.
"""
from __future__ import annotations

import numpy as np


def _thr32(t: np.ndarray) -> np.ndarray:
    """Largest float32 <= t: a float32 feature x then goes left exactly when
    x <= t, as the float64 threshold that CART fitted on float32 data says."""
    t32 = t.astype(np.float32)
    up = t32.astype(np.float64) > t
    t32[up] = np.nextafter(t32[up], np.float32(-np.inf))
    return t32


def dense_tree(tree, depth: int, classes: np.ndarray, feat, thr, leaf) -> None:
    """Write one fitted sklearn tree into its rows of the forest's arrays:
    `feat` and `thr` of shape (2**D-1,), every slot written, and `leaf` of
    shape (2**D, K), zero on entry (only the fitted classes' columns are
    written)."""
    left, right = tree.children_left, tree.children_right
    value = tree.value[:, 0, :].astype(np.float64)
    value = value / np.maximum(value.sum(axis=1, keepdims=True), 1e-300)
    cur = np.zeros(1, np.int64)
    for lvl in range(depth):
        base = 2 ** lvl - 1
        is_leaf = left[cur] < 0
        feat[base:base + cur.size] = np.where(is_leaf, 0, tree.feature[cur])
        thr[base:base + cur.size] = np.where(
            is_leaf, np.float32(np.inf), _thr32(tree.threshold[cur]))
        nxt = np.empty(2 * cur.size, np.int64)
        nxt[0::2] = np.where(is_leaf, cur, left[cur])
        nxt[1::2] = np.where(is_leaf, cur, right[cur])
        cur = nxt
    leaf[:, classes] = value[cur]


def fit(x: np.ndarray, y: np.ndarray, *, n_trees: int, depth: int, seed: int) -> list:
    """The fitted sklearn trees (`tree_` objects): `n_trees` CART trees of
    `max_depth` `depth` (one tree: no bootstrap, every feature; several: a
    random forest)."""
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.tree import DecisionTreeClassifier

    rs = int(seed) % (2 ** 32)
    if n_trees == 1:
        est = [DecisionTreeClassifier(max_depth=depth, random_state=rs).fit(x, y)]
    else:
        est = RandomForestClassifier(n_estimators=n_trees, max_depth=depth,
                                     random_state=rs, n_jobs=4).fit(x, y).estimators_
    return [e.tree_ for e in est]


def grow(x: np.ndarray, y: np.ndarray, *, n_trees: int, depth: int,
         n_classes: int, seed: int):
    """Fit the forest (`fit`) and return its dense arrays (feature
    (T, 2**D-1) int32, threshold float32, leaf (T, 2**D, K) float32), each
    tree written into its row: the host holds one forest, not a copy of it."""
    trees = fit(x, y, n_trees=n_trees, depth=depth, seed=seed)
    T = len(trees)
    feat = np.zeros((T, 2 ** depth - 1), np.int32)
    thr = np.full((T, 2 ** depth - 1), np.inf, np.float32)
    leaf = np.zeros((T, 2 ** depth, n_classes), np.float32)
    # a forest's trees see class indices 0..k-1 of the fitted classes_
    classes = np.unique(y)
    for t, tree in enumerate(trees):
        dense_tree(tree, depth, classes, feat[t], thr[t], leaf[t])
    return feat, thr, leaf
