"""Random-forest inference Pallas kernel — the model stage of the pipeline.

TPU adaptation of the paper's SmartCore/Rust tree inference (DESIGN.md §3):
a TPU has no pointer chasing, so trees are served in the real-node layout
of `repro.core.forest.CompactForest` (one window of node slots per level,
each slot naming its left child's slot on the next level) and traversal is
unrolled over the (static) levels:

    slot <- next[slot] + (x[feature[slot]] > threshold[slot])

Mosaic lowers no gather over a vector, so `forest_votes` reads every
table by compare/select instead (one-hot over a level's window or the
feature axis, then a lane reduction) and reads leaf votes with a one-hot ×
leaf-table matmul. The one-hots span a tree's real nodes, not its padded
``2**depth`` slots. Trees run in a `lax.fori_loop`, so compile time does
not grow with the forest. `forest_votes` is the one traversal: this kernel
and the three fused kernels (`repro.kernels.fused_pipeline`) all call it.

The grid tiles (flow_block × tree_block); each step keeps a (bn, F) tile of
flows and a tree block's node/leaf tables in VMEM and accumulates the vote
sum into the output tile across tree blocks (the output block index only
depends on the flow axis, so Pallas keeps it resident while the tree axis
iterates — a reduction without HBM round-trips).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.forest import FEATURE_BITS

__all__ = ["forest_infer_kernel_call", "forest_votes"]


def forest_votes(x, n_ref, l_ref, *, t0: int, n_trees: int, widths: tuple):
    """Sum of leaf rows over trees ``[t0, t0 + n_trees)`` for each flow.

    `x` is the ``(bn, F)`` feature tile; `n_ref` and `l_ref` hold a
    `CompactForest`'s ``nodes`` ``(T, 2, S)`` and ``leaf`` ``(T, K, L)``,
    and `widths` its level windows. Per level, a one-hot over the level's
    window reads each flow's threshold and node code, and a one-hot over
    the feature axis reads the feature value; a flow whose code says leaf
    keeps it. A one-hot × leaf matmul at HIGHEST precision reads the votes
    exactly (a one-hot row times a float32 table, summed with zeros).
    Every read is exact, so the traversal takes the same branches as
    `ref.forest_infer_ref` on the dense forest.
    """
    bn, F = x.shape
    L = l_ref.shape[2]
    K = l_ref.shape[1]
    f_lane = lax.broadcasted_iota(jnp.int32, (bn, F), 1)
    l_lane = lax.broadcasted_iota(jnp.int32, (bn, L), 1)

    def tree(t, acc):
        state = jnp.zeros((bn, 1), jnp.int32)     # slot, or -1 - leaf id
        lo = 0
        for w in widths:
            rec = n_ref[t, :, lo:lo + w]
            at = lax.broadcasted_iota(jnp.int32, (bn, w), 1) == state
            thr = jnp.sum(jnp.where(at, rec[0:1], 0.0), axis=1, keepdims=True)
            code = jnp.sum(jnp.where(at, rec[1:2], 0.0), axis=1,
                           keepdims=True).astype(jnp.int32)
            fid = code & (2 ** FEATURE_BITS - 1)
            xv = jnp.sum(jnp.where(f_lane == fid, x, 0.0), axis=1, keepdims=True)
            step = (code >> FEATURE_BITS) + (xv > thr).astype(jnp.int32)
            state = jnp.where(state < 0, state, step)
            lo += w
        # a flow on the deepest level sits on its leaf id
        leaf_id = jnp.where(state < 0, -1 - state, state)
        at_leaf = (l_lane == leaf_id).astype(jnp.float32)
        return acc + lax.dot_general(
            at_leaf, l_ref[t], (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    return lax.fori_loop(t0, t0 + n_trees, tree,
                         jnp.zeros((bn, K), jnp.float32))


def _tree_kernel(x_ref, n_ref, l_ref, o_ref, *, widths: tuple, n_trees: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += forest_votes(x_ref[...], n_ref, l_ref, t0=0,
                               n_trees=n_ref.shape[0], widths=widths)

    @pl.when(j == pl.num_programs(1) - 1)
    def _mean():
        o_ref[...] = o_ref[...] / n_trees


def forest_infer_kernel_call(
    x: jax.Array,         # (N, F) float32
    nodes: jax.Array,     # (T, 2, S) float32, CompactForest.nodes
    leaf: jax.Array,      # (T, K, L) float32, CompactForest.leaf
    widths: tuple,
    *,
    block_n: int = 256,
    block_t: int = 8,
    interpret: bool = False,
) -> jax.Array:
    N, F = x.shape
    T, _, S = nodes.shape
    K, L = leaf.shape[1], leaf.shape[2]
    bn = min(block_n, N)
    bt = min(block_t, T)
    # pad both grid axes up to their block multiples so arbitrary batch and
    # forest sizes work (and the path has no asserts to lose under -O):
    # padded flows are zero rows whose output is sliced off; padded trees
    # have all-zero leaves and add nothing.
    rem_n = (-N) % bn
    if rem_n:
        x = jnp.pad(x, ((0, rem_n), (0, 0)))
    rem_t = (-T) % bt
    if rem_t:
        nodes = jnp.pad(nodes, ((0, rem_t), (0, 0), (0, 0)))
        leaf = jnp.pad(leaf, ((0, rem_t), (0, 0), (0, 0)))

    kern = functools.partial(_tree_kernel, widths=tuple(widths), n_trees=T)
    out = pl.pallas_call(
        kern,
        grid=((N + rem_n) // bn, (T + rem_t) // bt),
        in_specs=[
            pl.BlockSpec((bn, F), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 2, S), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bt, K, L), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, K), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + rem_n, K), jnp.float32),
        interpret=interpret,
    )(x, nodes, leaf)
    return out[:N]
