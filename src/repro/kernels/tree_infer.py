"""Random-forest inference Pallas kernel — the model stage of the pipeline.

TPU adaptation of the paper's SmartCore/Rust tree inference (DESIGN.md §3):
a TPU has no pointer chasing, so trees live in the *dense complete
level-order layout* produced by `repro.core.forest` and traversal is pure
index arithmetic, unrolled over the (static) depth:

    node <- 2*node + 1 + (x[feat[node]] > thresh[node])

Mosaic lowers no gather over a vector, so `forest_votes` reads every
table by compare/select instead (one-hot over the node or feature axis,
then a lane reduction) and reads leaf votes with a one-hot × leaf-table
matmul. Trees run in a `lax.fori_loop`, so compile time does not grow
with the forest. `forest_votes` is the one traversal: this kernel and the
three fused kernels (`repro.kernels.fused_pipeline`) all call it.

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

__all__ = ["forest_infer_kernel_call", "forest_votes", "kernel_layout",
           "pad_forest_blocks"]


def kernel_layout(feature, threshold, leaf):
    """Dense forest arrays -> the layout `forest_votes` reads.

    Node tables go from ``(T, NI)`` to ``(T, 1, NI)``, so a tree's row is
    read with a dynamic index on the leading axis (Mosaic refuses a
    dynamic unaligned sublane index under a lane window). Leaves go from
    ``(T, NL, K)`` to ``(T, K, NL)``, which puts the long leaf axis on the
    lanes instead of padding a few classes to 128 lanes in VMEM.
    """
    T = feature.shape[0]
    return (feature.reshape(T, 1, -1), threshold.reshape(T, 1, -1),
            jnp.swapaxes(leaf, 1, 2))


def pad_forest_blocks(feature, threshold, leaf, block_t: int):
    """Pad the tree axis to a `block_t` multiple with pass-through trees.

    Padding trees have +inf thresholds (every comparison goes left) and
    all-zero leaves, so they add exactly nothing to the vote sum; the
    caller divides that sum by the true tree count.
    Returns ``(feature, threshold, leaf, rem_t)``.
    """
    T = feature.shape[0]
    rem_t = (-T) % block_t
    if rem_t:
        feature = jnp.pad(feature, ((0, rem_t), (0, 0)))
        threshold = jnp.pad(threshold, ((0, rem_t), (0, 0)),
                            constant_values=jnp.inf)
        leaf = jnp.pad(leaf, ((0, rem_t), (0, 0), (0, 0)))
    return feature, threshold, leaf, rem_t


def forest_votes(x, f_ref, t_ref, l_ref, *, t0: int, n_trees: int,
                 depth: int):
    """Sum of leaf rows over trees ``[t0, t0 + n_trees)`` for each flow.

    `x` is the ``(bn, F)`` feature tile; `f_ref`/`t_ref`/`l_ref` hold the
    node feature ids, thresholds and leaves in `kernel_layout`. Per level,
    a one-hot over the node axis reads each flow's feature id and
    threshold — only the 128-lane
    aligned window that holds the level's nodes — and a one-hot over the
    feature axis reads the feature value. A one-hot × leaf matmul at
    HIGHEST precision reads the votes exactly (a one-hot row times a
    float32 table, summed with zeros). Every read is exact, so the
    traversal takes the same branches as `ref.forest_infer_ref`.
    """
    bn, F = x.shape
    NI = f_ref.shape[2]
    K, NL = l_ref.shape[1], l_ref.shape[2]
    f_lane = lax.broadcasted_iota(jnp.int32, (bn, F), 1)
    l_lane = lax.broadcasted_iota(jnp.int32, (bn, NL), 1)

    def tree(t, acc):
        node = jnp.zeros((bn, 1), jnp.int32)
        for d in range(depth):
            # level d's nodes are [2^d - 1, 2^(d+1) - 1)
            lo = (2 ** d - 1) // 128 * 128
            hi = min(NI, -(-(2 ** (d + 1) - 1) // 128) * 128)
            at = lax.broadcasted_iota(jnp.int32, (bn, hi - lo), 1) + lo == node
            fid = jnp.sum(jnp.where(at, f_ref[t, :, lo:hi], 0),
                          axis=1, keepdims=True)
            thr = jnp.sum(jnp.where(at, t_ref[t, :, lo:hi], 0.0),
                          axis=1, keepdims=True)
            xv = jnp.sum(jnp.where(f_lane == fid, x, 0.0),
                         axis=1, keepdims=True)
            node = 2 * node + 1 + (xv > thr).astype(jnp.int32)
        at_leaf = (l_lane == node - (2 ** depth - 1)).astype(jnp.float32)
        return acc + lax.dot_general(
            at_leaf, l_ref[t], (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    return lax.fori_loop(t0, t0 + n_trees, tree,
                         jnp.zeros((bn, K), jnp.float32))


def _tree_kernel(x_ref, f_ref, t_ref, l_ref, o_ref, *, depth: int,
                 n_trees: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += forest_votes(x_ref[...], f_ref, t_ref, l_ref, t0=0,
                               n_trees=f_ref.shape[0], depth=depth)

    @pl.when(j == pl.num_programs(1) - 1)
    def _mean():
        o_ref[...] = o_ref[...] / n_trees


def forest_infer_kernel_call(
    x: jax.Array,         # (N, F) float32
    feature: jax.Array,   # (T, NI) int32
    threshold: jax.Array, # (T, NI) float32
    leaf: jax.Array,      # (T, NL, K) float32
    depth: int,
    *,
    block_n: int = 256,
    block_t: int = 8,
    interpret: bool = False,
) -> jax.Array:
    N, F = x.shape
    T, NI = feature.shape
    NL, K = leaf.shape[1], leaf.shape[2]
    bn = min(block_n, N)
    bt = min(block_t, T)
    # pad both grid axes up to their block multiples so arbitrary batch and
    # forest sizes work (and the path has no asserts to lose under -O):
    # padded flows are zero rows whose output is sliced off; padded trees
    # are pass-through (+inf threshold, zero leaves) and add nothing.
    rem_n = (-N) % bn
    if rem_n:
        x = jnp.pad(x, ((0, rem_n), (0, 0)))
    feature, threshold, leaf, rem_t = pad_forest_blocks(
        feature, threshold, leaf, bt)

    kern = functools.partial(_tree_kernel, depth=depth, n_trees=T)
    out = pl.pallas_call(
        kern,
        grid=((N + rem_n) // bn, (T + rem_t) // bt),
        in_specs=[
            pl.BlockSpec((bn, F), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1, NI), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bt, 1, NI), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bt, K, NL), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, K), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + rem_n, K), jnp.float32),
        interpret=interpret,
    )(x, *kernel_layout(feature, threshold, leaf))
    return out[:N]
