"""Fused extract+infer Pallas kernel — the single-launch serving hot path.

The unfused pipeline runs two device launches per micro-batch: the XLA
extraction executable materializes the ``(N, F)`` feature matrix in HBM,
then the `tree_infer` Pallas kernel reads it back. This kernel fuses both
stages (DESIGN.md §7): the grid tiles the flow axis, each step loads one
``(bn, P)`` tile of every packet tensor into VMEM, computes the selected
feature columns *in registers* via the shared emitter
(`repro.traffic.extraction.emit_feature_columns`, specialized on the static
stats plan — the paper's conditional compilation, now inside Pallas), and
immediately runs the real-node forest traversal
(`repro.core.forest.CompactForest`) on the in-register feature tile. The
feature matrix never touches HBM.

The contract is the float32 reference (`build_pipeline(use_kernel=False)`):
equal predicted classes and probabilities within 1e-5. Feature columns
come from the same emitter and plan as the XLA extraction, but a kernel
body and an XLA program may round a reduction differently, so the
two paths are not bitwise equal. The traversal is `tree_infer.forest_votes`,
shared with the unfused kernel: exact table reads, votes summed tree by
tree, then divided by the tree count.

`fused_forest_infer` is the jit'd public entry; the packet tensors are
donated (``donate_argnums``) so XLA can reuse their device buffers across
micro-batches — together with the dispatcher's staging arenas this makes a
flush allocation-free on the host and reuse-friendly on the device.

Swap-safety (DESIGN.md §9.3): the jit cache keys on the static
``(plan, depth, widths, batch shape)`` tuple, so two pipeline
configurations can serve *concurrently* — during a zero-downtime
hot-swap the background-warmed replacement (`ServingPipeline.warm`)
and the still-serving old pipeline never evict or alias each other's
executables, and donation stays per-call (each configuration's arenas
rotate independently).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.forest import FEATURE_BITS
from repro.traffic.extraction import (
    emit_agg_features,
    emit_feature_columns,
    emit_merged_columns,
    pack_flags,
)

from .ops import resolve_interpret
from .tree_infer import forest_votes

__all__ = ["fused_agg_infer", "fused_forest_infer", "fused_pipeline_call",
           "fused_multi_forest_infer", "fused_multi_forest_call",
           "stack_multi_forests"]


def _tile(i):
    return (i, 0)


def _whole3(i):
    return (0, 0, 0)


def _pad_rows(rem_n, *arrays):
    """Zero-pad the flow axis of every array by `rem_n` rows (padding rows
    have flow_len 0, so every mask is empty)."""
    return [jnp.pad(a, ((0, rem_n),) + ((0, 0),) * (a.ndim - 1))
            for a in arrays]


def _packet_specs(bn, P):
    """BlockSpecs of the six (N, P) packet tensors and the meta tile."""
    return [pl.BlockSpec((bn, P), _tile) for _ in range(6)] + [
        pl.BlockSpec((bn, 4), _tile)]


# the compiler's default scoped VMEM on a v5e: room for the kernel's tiles
# and temporaries, beside the resident forest
_SCOPED_VMEM = 16 * 2 ** 20


def _vmem_bytes(shape) -> int:
    """Bytes of a float32 array in VMEM, whose last two axes are laid out
    in (8, 128) tiles."""
    *lead, s, lanes = shape
    return math.prod(lead) * (-(-s // 8) * 8) * (-(-lanes // 128) * 128) * 4


def _resident_forest(nodes, leaf):
    """BlockSpecs that keep a whole `CompactForest` (``nodes``, ``leaf``)
    resident in VMEM, and compiler params whose VMEM limit holds it
    double-buffered beside the kernel's default scope: the limit follows
    the forest's size."""
    forest = _vmem_bytes(nodes.shape) + _vmem_bytes(leaf.shape)
    return ([pl.BlockSpec(nodes.shape, _whole3), pl.BlockSpec(leaf.shape, _whole3)],
            pltpu.CompilerParams(vmem_limit_bytes=_SCOPED_VMEM + 2 * forest))


def _fused_kernel(
    ts_ref, size_ref, dir_ref, ttl_ref, win_ref, flags_ref, meta_ref,
    n_ref, l_ref, o_ref,
    *, plan, depth: int, widths: tuple,
):
    meta = meta_ref[...]        # (bn, 4) float32: flow_len, proto, s/d_port
    cols = emit_feature_columns(
        plan,
        ts=ts_ref[...], size=size_ref[...], direction=dir_ref[...],
        ttl=ttl_ref[...], winsize=win_ref[...], flags=flags_ref[...],
        flow_len=meta[:, 0], proto=meta[:, 1], s_port=meta[:, 2],
        d_port=meta[:, 3], depth=depth,
    )
    x = jnp.stack(cols, axis=1)                 # (bn, F) — in VMEM only
    n = n_ref.shape[0]
    o_ref[...] = forest_votes(x, n_ref, l_ref, t0=0, n_trees=n,
                              widths=widths) / n


def _agg_kernel(
    agg_ref, meta_ref, n_ref, l_ref, o_ref,
    *, plan, widths: tuple,
):
    """Incremental entry (DESIGN.md §12): feature columns from the compact
    per-flow aggregate block instead of the raw packet window — a
    ``(bn, AGG_WIDTH)`` tile replaces six ``(bn, P[, 8])`` packet tensors,
    so a refresh batch moves ~53 floats per flow regardless of how long
    the flow has lived."""
    meta = meta_ref[...]        # (bn, 3) float32: proto, s_port, d_port
    cols = emit_agg_features(
        plan, agg_ref[...], proto=meta[:, 0], s_port=meta[:, 1],
        d_port=meta[:, 2])
    x = jnp.stack(cols, axis=1)
    n = n_ref.shape[0]
    o_ref[...] = forest_votes(x, n_ref, l_ref, t0=0, n_trees=n,
                              widths=widths) / n


def fused_pipeline_call(
    ts, size, direction, ttl, winsize, flags, meta,
    nodes, leaf,
    *, plan, depth: int, widths: tuple,
    block_n: int = 256, interpret: bool = False,
):
    """Raw pallas_call: one launch over flow tiles, features never hit HBM.

    Expects float32 packet tensors, int32 `direction`, `flags` as the
    ``(N, P)`` int32 `pack_flags` bit mask, and
    ``meta = [flow_len, proto, s_port, d_port]`` as
    ``(N, 4)`` float32; `nodes`, `leaf` and `widths` are a
    `CompactForest`'s. Pads the flow axis to the block multiple; the
    whole forest stays resident, so the tree axis needs no padding.
    """
    N, P = ts.shape
    K = leaf.shape[1]
    bn = min(block_n, N)
    rem_n = (-N) % bn
    if rem_n:
        ts, size, direction, ttl, winsize, flags, meta = _pad_rows(
            rem_n, ts, size, direction, ttl, winsize, flags, meta)
    kern = functools.partial(
        _fused_kernel, plan=plan, depth=depth, widths=widths)
    specs, params = _resident_forest(nodes, leaf)
    out = pl.pallas_call(
        kern,
        grid=((N + rem_n) // bn,),
        in_specs=_packet_specs(bn, P) + specs,
        out_specs=pl.BlockSpec((bn, K), _tile),
        out_shape=jax.ShapeDtypeStruct((N + rem_n, K), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        # the kernel's instruction name in a device trace
        # (%fused_forest_infer.N); unnamed, it would follow the jit wrapper
        name="fused_forest_infer",
    )(ts, size, direction, ttl, winsize, flags, meta, nodes, leaf)
    return out[:N]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "depth", "widths", "block_n", "interpret"),
    donate_argnums=(0, 1, 2, 3, 4, 5),
)
def fused_forest_infer(
    ts, size, direction, ttl, winsize, flags,
    flow_len, proto, s_port, d_port,
    nodes, leaf,
    *, plan, depth: int, widths: tuple,
    block_n: int = 256, interpret: bool | None = None,
):
    """Jit'd fused pipeline entry: packets -> class probabilities, one launch.

    The packet tensors (args 0-5) are donated: each micro-batch's device
    buffers are released back to XLA as soon as the launch consumes them,
    so steady-state serving reuses a fixed set of device allocations.
    Accepts uint8 `direction` and ``(N, P, 8)`` uint8 or float32 `flags`
    (converted and bit-packed on device, keeping the host staging arena
    copy-free); `plan` comes from
    `repro.traffic.extraction.stats_plan`.
    """
    meta = jnp.stack(
        [flow_len.astype(jnp.float32), proto, s_port, d_port], axis=1)
    return fused_pipeline_call(
        ts, size, direction.astype(jnp.float32), ttl, winsize,
        pack_flags(flags), meta, nodes, leaf,
        plan=plan, depth=depth, widths=widths,
        block_n=block_n, interpret=resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# multi-tenant fused kernel (DESIGN.md §15)
# ---------------------------------------------------------------------------
# One Pallas launch serves every tenant of a fleet: the merged plan's
# feature columns are computed once over the in-VMEM packet tile, then each
# tenant's forest — stacked along the tree axis with a static offset, its
# node feature ids pre-remapped into merged-column space — traverses the
# same feature tile via the solo `forest_votes`. Leaf tables are stacked at
# the widest tenant's class count, so the resident forest grows with the
# trees and not with trees x tenants; a one-hot matmul moves each tenant's
# vote sums to its own lane span of one accumulator, with no lane-sliced
# store, and tenant t's lanes hold exactly the sum a solo launch computes.


def stack_multi_forests(forests, tenant_cols):
    """Stack N tenants' `CompactForest`s along the tree axis.

    Each forest's node feature ids are remapped through `tenant_cols[t]`
    into merged-column space, and its slots, leaves and classes are
    zero-padded to the fleet maximum (padding is never visited). Tenant t's
    classes sit at leaf rows ``[0, K_t)``. Returns ``(nodes, leaf,
    tenants)`` as host arrays, where ``tenants`` is the static per-tenant
    spec tuple ``(offset, n_trees, widths, k0, k0 + K_t)`` that the kernel
    specializes on: tenant t's probabilities are output lanes
    ``[k0, k0 + K_t)``.
    """
    mask = 2 ** FEATURE_BITS - 1
    s_max = max(int(f.nodes.shape[2]) for f in forests)
    l_max = max(int(f.leaf.shape[2]) for f in forests)
    k_max = max(f.n_out for f in forests)
    nodes, leafs, tenants = [], [], []
    off = k0 = 0
    for f, cols in zip(forests, tenant_cols):
        if max(cols) > mask:
            raise ValueError(f"{max(cols) + 1} merged feature columns; a node "
                             f"code holds {mask + 1}")
        code = f.nodes[:, 1].astype(np.int64)
        n = f.nodes.copy()
        n[:, 1] = (code & ~mask) | np.asarray(cols, np.int64)[code & mask]
        nodes.append(np.pad(n, ((0, 0), (0, 0), (0, s_max - n.shape[2]))))
        leafs.append(np.pad(f.leaf, ((0, 0), (0, k_max - f.n_out),
                                     (0, l_max - f.leaf.shape[2]))))
        tenants.append((off, f.n_trees, tuple(f.widths), k0, k0 + f.n_out))
        off += f.n_trees
        k0 += f.n_out
    return np.concatenate(nodes), np.concatenate(leafs), tuple(tenants)


def _multi_kernel(
    ts_ref, size_ref, dir_ref, ttl_ref, win_ref, flags_ref, meta_ref,
    n_ref, l_ref, o_ref,
    *, merged, tenants,
):
    meta = meta_ref[...]        # (bn, 4) float32: flow_len, proto, s/d_port
    cols = emit_merged_columns(
        merged,
        ts=ts_ref[...], size=size_ref[...], direction=dir_ref[...],
        ttl=ttl_ref[...], winsize=win_ref[...], flags=flags_ref[...],
        flow_len=meta[:, 0], proto=meta[:, 1], s_port=meta[:, 2],
        d_port=meta[:, 3],
    )
    x = jnp.stack(cols, axis=1)                 # (bn, F_union) — VMEM only
    bn, k_sum = o_ref.shape
    k_max = l_ref.shape[1]
    acc = jnp.zeros((bn, k_sum), jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (k_max, k_sum), 0)
    col = lax.broadcasted_iota(jnp.int32, (k_max, k_sum), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, k_sum), 1)
    n_trees = jnp.ones((1, k_sum), jnp.float32)
    for off, n, widths, k0, k1 in tenants:
        votes = forest_votes(x, n_ref, l_ref, t0=off, n_trees=n,
                             widths=widths)
        # one-hot (k_max, k_sum): vote lane k -> output lane k0 + k; exact
        # at HIGHEST (each output is one f32 vote sum plus zeros)
        place = (col == row + k0) & (row < k1 - k0)
        acc = acc + jnp.dot(votes, place.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        n_trees = jnp.where((lane >= k0) & (lane < k1), float(n), n_trees)
    o_ref[...] = acc / n_trees


def fused_multi_forest_call(
    ts, size, direction, ttl, winsize, flags, meta,
    nodes, leaf,
    *, merged, tenants,
    block_n: int = 256, interpret: bool = False,
):
    """Raw pallas_call: one launch, N tenants' prediction lanes.

    `nodes`/`leaf` are the tenant-stacked arrays from
    `stack_multi_forests`; the output is ``(N, sum of per-tenant n_out)``
    with tenant t's probabilities in its contiguous lane slice. Flow-axis
    padding matches `fused_pipeline_call` (zero rows: every mask empty).
    """
    N, P = ts.shape
    k_sum = tenants[-1][4]
    bn = min(block_n, N)
    rem_n = (-N) % bn
    if rem_n:
        ts, size, direction, ttl, winsize, flags, meta = _pad_rows(
            rem_n, ts, size, direction, ttl, winsize, flags, meta)
    kern = functools.partial(_multi_kernel, merged=merged, tenants=tenants)
    specs, params = _resident_forest(nodes, leaf)
    out = pl.pallas_call(
        kern,
        grid=((N + rem_n) // bn,),
        in_specs=_packet_specs(bn, P) + specs,
        out_specs=pl.BlockSpec((bn, k_sum), _tile),
        out_shape=jax.ShapeDtypeStruct((N + rem_n, k_sum), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(ts, size, direction, ttl, winsize, flags, meta, nodes, leaf)
    return out[:N]


@functools.partial(
    jax.jit,
    static_argnames=("merged", "tenants", "block_n", "interpret"),
    donate_argnums=(0, 1, 2, 3, 4, 5),
)
def fused_multi_forest_infer(
    ts, size, direction, ttl, winsize, flags,
    flow_len, proto, s_port, d_port,
    nodes, leaf,
    *, merged, tenants,
    block_n: int = 256, interpret: bool | None = None,
):
    """Jit'd multi-tenant fused entry: packets -> stacked per-tenant
    probability lanes, one launch. Donation and dtype conventions match
    `fused_forest_infer`; the jit cache keys on the static
    ``(merged, tenants, batch shape)`` tuple, so a multi-tenant bundle
    hot-swap coexists with whatever it replaces (DESIGN.md §9.3)."""
    meta = jnp.stack(
        [flow_len.astype(jnp.float32), proto, s_port, d_port], axis=1)
    return fused_multi_forest_call(
        ts, size, direction.astype(jnp.float32), ttl, winsize,
        pack_flags(flags), meta, nodes, leaf,
        merged=merged, tenants=tenants, block_n=block_n,
        interpret=resolve_interpret(interpret),
    )


def fused_agg_call(
    agg, meta, nodes, leaf,
    *, plan, widths: tuple,
    block_n: int = 256, interpret: bool = False,
):
    """Raw pallas_call for the aggregate entry: one launch over flow tiles
    of the compact ``(N, AGG_WIDTH)`` running-statistic block. Pads the
    flow axis with all-zero rows (a zero aggregate has every count at 0,
    so the emitter's masked reductions yield a defined all-zero feature
    row), exactly as the window entry does."""
    N, W = agg.shape
    K = leaf.shape[1]
    bn = min(block_n, N)
    rem_n = (-N) % bn
    if rem_n:
        agg, meta = _pad_rows(rem_n, agg, meta)
    kern = functools.partial(_agg_kernel, plan=plan, widths=widths)
    specs, params = _resident_forest(nodes, leaf)
    out = pl.pallas_call(
        kern,
        grid=((N + rem_n) // bn,),
        in_specs=[
            pl.BlockSpec((bn, W), _tile),           # aggregate block
            pl.BlockSpec((bn, 3), _tile),           # proto, s_port, d_port
        ] + specs,
        out_specs=pl.BlockSpec((bn, K), _tile),
        out_shape=jax.ShapeDtypeStruct((N + rem_n, K), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(agg, meta, nodes, leaf)
    return out[:N]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "widths", "block_n", "interpret"),
)
def fused_agg_infer(
    agg, proto, s_port, d_port,
    nodes, leaf,
    *, plan, widths: tuple,
    block_n: int = 256, interpret: bool | None = None,
):
    """Jit'd incremental pipeline entry: aggregate rows -> class
    probabilities, one launch. The refresh path is low-rate (one batch per
    `refresh_every` packets of frozen traffic), so inputs are not donated:
    the host-side staging block is reused synchronously by the dispatcher.
    """
    meta = jnp.stack([proto, s_port, d_port], axis=1)
    return fused_agg_call(
        agg.astype(jnp.float32), meta, nodes, leaf,
        plan=plan, widths=widths,
        block_n=block_n, interpret=resolve_interpret(interpret),
    )
