"""The bfloat16 control of a configuration's forest, one dense tree at a time.

`harness.check`'s control (`run.py --control 1`) holds the whole dense forest
on the host twice over (float32 tables and a float64 copy of the leaves); at
100 trees of depth 20 that is about 36 GB. The reference's probability
interval and the control's probabilities are both means over trees, so this
tool writes each fitted tree alone into the dense layout
(`forest_build.dense_tree`), walks it with `reference.prob_interval` and
`reference.control_probs`, and averages over the trees: the check's control
numbers, a tree's memory at a time. The flows are the config's template pool,
each once, at the packet count a correct table classifies it at (the packet
depth, or the whole flow when shorter); the forest is the one a run of that
seed grows (`harness.build_forest`).

    python3 benchmarks/chip/control_by_tree.py --config iot-rf100-d20 --seed 1 --seed 2

prints one JSON line a seed: the control's `prob_gap` and `class_gap` as
`reference.gaps` reads them against the float64 interval, beside the
configuration's limits.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import forest_build  # noqa: E402
import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def by_tree(cfg: dict, seed: int):
    """(lo, hi, control): the float64 reference's interval and the bfloat16
    control's probabilities, (pool flows, classes) each, summed a tree at a
    time over the forest the run of `seed` grows."""
    feats = sorted(cfg["features"])
    P, D, K = int(cfg["packet_depth"]), int(cfg["max_depth"]), int(cfg["n_classes"])
    pool = gen.make_templates(cfg["use_case"], int(cfg["pool_flows"]),
                              gen.seed_rng(int(cfg["tap_seed"]), 1),
                              int(cfg["class_seed"]))
    tr = gen.make_templates(cfg["use_case"], int(cfg["train_flows"]),
                            gen.seed_rng(seed, 3), int(cfg["class_seed"]))
    xt = harness.window_features(feats, tr, np.arange(tr.n_flows),
                                 np.minimum(tr.flow_len, P), P)
    trees = forest_build.fit(xt.astype(np.float32), tr.label,
                             n_trees=int(cfg["n_trees"]), depth=D, seed=seed)
    x = harness.window_features(feats, pool, np.arange(pool.n_flows),
                                np.minimum(pool.flow_len, P), P)
    classes = np.unique(tr.label)
    lo = np.zeros((len(x), K))
    hi = np.zeros((len(x), K))
    ctl = np.zeros((len(x), K))
    for tree in trees:
        feat = np.zeros((1, 2 ** D - 1), np.int32)
        thr = np.full((1, 2 ** D - 1), np.inf, np.float32)
        leaf = np.zeros((1, 2 ** D, K), np.float32)
        forest_build.dense_tree(tree, D, classes, feat[0], thr[0], leaf[0])
        lo_t, hi_t = reference.prob_interval(x, feat, thr, leaf, D)
        lo += lo_t
        hi += hi_t
        ctl += reference.control_probs(x, feat, thr, leaf, D)
    T = len(trees)
    return lo / T, hi / T, ctl / T


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    a = ap.parse_args(argv)
    cfg = json.loads((Path(__file__).resolve().parent / "configs"
                      / f"{a.config}.json").read_text())
    for seed in a.seed:
        t0 = time.perf_counter()
        lo, hi, ctl = by_tree(cfg, seed)
        g = reference.gaps(ctl, ctl.argmax(axis=1), lo, hi)
        print(json.dumps({"config": a.config, "seed": seed, "flows": len(ctl),
                          "control": g, "limits": cfg["limits"],
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
