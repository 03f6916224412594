"""JAX feature-extraction engine, jit-specialized per feature representation.

The paper generates a conditionally-compiled Rust binary per representation
(Fig. 4): every operation is predicated on the features that need it, so the
artifact contains exactly the required work. The XLA-native equivalent is a
``jax.jit`` function whose *static* arguments are the feature tuple and the
connection depth: only the selected columns are computed, shared
sub-expressions (direction masks, parsed fields, packet-count denominators)
are emitted once and CSE'd, and everything else is dead-code-eliminated from
the compiled executable. ``extract_features`` is the public entry point.

A feature tuple lowers first to a **static stats plan** (`stats_plan`): a
tuple of per-feature op descriptors that is hashable and order-preserving.
The plan is the unit of specialization shared by both execution paths —
`_extract` (the standalone XLA extraction stage) and the fused Pallas
pipeline kernel (`repro.kernels.fused_pipeline`) trace the *same* emitter
(`emit_feature_columns`) over it, so both compute the same formulas; the
fused path matches the unfused one to float32 rounding (DESIGN.md §7).

All statistics are masked segmented reductions over dense
``(flows, max_pkts)`` tensors, written only with ops that Mosaic lowers
inside a Pallas kernel: no sort, cummax or gather (the median counts
ranks, the iat running max doubles lane shifts, handshake timestamps are
masked minima).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .synth import FLAG_NAMES, TrafficDataset

__all__ = [
    "extract_features",
    "extraction_fn",
    "pack_flags",
    "stats_plan",
    "emit_feature_columns",
    "emit_agg_features",
    "merge_stats_plans",
    "emit_merged_columns",
    "emit_merged_agg_features",
    "plan_is_incremental",
    "merged_plan_is_incremental",
    "agg_init",
    "AGG_WIDTH",
]

# python float, not a jnp scalar: weak-typed promotion lands on the same
# float32 value, and the fused Pallas kernel cannot capture array constants
_BIG = 3.4e38


def _masked_sum(v, m):
    return jnp.sum(jnp.where(m, v, 0.0), axis=1)


def _masked_mean(v, m):
    c = jnp.sum(m, axis=1)
    return jnp.where(c > 0, _masked_sum(v, m) / jnp.maximum(c, 1), 0.0)


def _masked_min(v, m):
    r = jnp.min(jnp.where(m, v, _BIG), axis=1)
    return jnp.where(jnp.any(m, axis=1), r, 0.0)


def _masked_max(v, m):
    r = jnp.max(jnp.where(m, v, -_BIG), axis=1)
    return jnp.where(jnp.any(m, axis=1), r, 0.0)


def _masked_std(v, m):
    # two-pass (subtract mean first): the one-pass E[x^2]-E[x]^2 form
    # catastrophically cancels in float32 for ~1e4-scale window sizes
    c = jnp.sum(m, axis=1)
    mean = _masked_sum(v, m) / jnp.maximum(c, 1)
    d = jnp.where(m, v - mean[:, None], 0.0)
    var = jnp.sum(d * d, axis=1) / jnp.maximum(c, 1)
    return jnp.where(c > 0, jnp.sqrt(var), 0.0)


def _masked_median(v, m):
    """Mean of the ``(c-1)//2``-th and ``c//2``-th smallest masked values.

    No sort (Mosaic lowers none): the r-th order statistic is the least
    value u with ``#{v <= u} > r``, counted pairwise over the P columns
    (a loop, so compile time does not grow with P). That is exactly the
    sorted array's entry r, ties included."""
    filled = jnp.where(m, v, _BIG)
    lane = jax.lax.broadcasted_iota(jnp.int32, filled.shape, 1)
    c = jnp.sum(m, axis=1, keepdims=True)
    lo_r = jnp.maximum((c - 1) // 2, 0)
    hi_r = c // 2

    def rank(i, lo_hi):
        lo, hi = lo_hi
        u = jnp.max(jnp.where(lane == i, filled, -_BIG), axis=1, keepdims=True)
        n_le = jnp.sum((filled <= u).astype(jnp.int32), axis=1, keepdims=True)
        return (jnp.minimum(lo, jnp.where(n_le > lo_r, u, _BIG)),
                jnp.minimum(hi, jnp.where(n_le > hi_r, u, _BIG)))

    big = jnp.full(c.shape, _BIG, filled.dtype)
    lo, hi = jax.lax.fori_loop(0, v.shape[1], rank, (big, big))
    return jnp.where(c > 0, 0.5 * (lo + hi), 0.0)[:, 0]


def _exclusive_running_max(a):
    """``out[:, j] = max(a[:, :j])`` (``-_BIG`` for j = 0): a running max
    over the static P columns by log-step doubling of lane shifts, which
    Mosaic lowers (it has no cummax). Max is exact, so this equals an
    exclusive ``lax.cummax``."""
    P = a.shape[1]

    def shift(x, s):
        if s >= P:
            return jnp.full_like(x, -_BIG)
        return jnp.concatenate(
            [jnp.full((x.shape[0], s), -_BIG, x.dtype), x[:, :P - s]], axis=1)

    run = shift(a, 1)
    s = 1
    while s < P:
        run = jnp.maximum(run, shift(run, s))
        s *= 2
    return run


_STATS = {
    "sum": _masked_sum,
    "mean": _masked_mean,
    "min": _masked_min,
    "max": _masked_max,
    "med": _masked_median,
    "std": _masked_std,
}

_FLAG_IDX = {n: i for i, n in enumerate(FLAG_NAMES)}


def pack_flags(flags):
    """``(rows, P, 8)`` 0/1 flag planes -> ``(rows, P)`` int32 bit mask,
    bit k set when flag ``FLAG_NAMES[k]`` is. The emitters read flags in
    this form: one lane-dense tile instead of an 8-lane last axis."""
    bits = jnp.asarray(flags).astype(jnp.int32)
    return jnp.sum(bits << jnp.arange(8, dtype=jnp.int32), axis=2)


# ---------------------------------------------------------------------------
# static stats plan
# ---------------------------------------------------------------------------

def stats_plan(names: Sequence[str]) -> tuple[tuple, ...]:
    """Lower a feature tuple to a static per-feature op plan.

    Each entry is a small hashable descriptor naming the op family and its
    static parameters; `emit_feature_columns` interprets it at trace time.
    Because the plan is a pure function of the feature names, both the
    standalone `_extract` jit and the fused Pallas kernel specialize on the
    same plan and therefore emit the same op graph (jit-as-conditional-
    compilation, now inside Pallas too).
    """
    plan: list[tuple] = []
    for name in names:
        if name == "dur":
            plan.append(("dur",))
        elif name in ("proto", "s_port", "d_port"):
            plan.append(("meta", name))
        elif name in ("s_load", "d_load"):
            plan.append(("load", name[0]))
        elif name in ("s_pkt_cnt", "d_pkt_cnt"):
            plan.append(("pkt_cnt", name[0]))
        elif name in ("tcp_rtt", "syn_ack", "ack_dat"):
            plan.append(("handshake", name))
        elif name.endswith("_cnt") and name[:-4] in _FLAG_IDX:
            plan.append(("flag_cnt", _FLAG_IDX[name[:-4]]))
        else:
            d, fam, stat = name.split("_")
            if d not in ("s", "d") or fam not in ("bytes", "iat", "winsize",
                                                  "ttl") or stat not in _STATS:
                raise ValueError(f"unknown feature {name!r}")
            plan.append(("stat", d, fam, stat))
    return tuple(plan)


def emit_feature_columns(
    plan: tuple[tuple, ...],
    *,
    ts, size, direction, ttl, winsize, flags, flow_len, proto, s_port, d_port,
    depth: int,
):
    """Trace the plan's feature columns over (rows, P) packet tensors.

    The single source of op emission for both execution paths: `_extract`
    calls it on full-batch tensors, the fused pipeline kernel on per-block
    VMEM tiles. `flags` is the `pack_flags` bit mask. Returns a list of
    float32 (rows,) columns in plan order.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, ts.shape, 1)
    valid = (idx < flow_len[:, None]) & (idx < depth)

    dir_mask = {
        "s": valid & (direction == 0),
        "d": valid & (direction == 1),
    }

    # shared sub-expressions are traced once per call: a Pallas kernel body
    # gets no common-subexpression pass before Mosaic compiles it
    @functools.cache
    def flag(k):
        return (flags >> k) & 1

    @functools.cache
    def dur():
        return _masked_max(ts, valid) - _masked_min(ts, valid)

    # directional inter-arrival times: ts_i - ts(previous pkt, same dir).
    # ts is monotone within a flow, so the previous same-direction timestamp
    # is an exclusive running max over masked timestamps.
    @functools.cache
    def dir_iat(d):
        m = dir_mask[d]
        prev = _exclusive_running_max(jnp.where(m, ts, -_BIG))
        has_prev = prev > -_BIG / 2
        iat = jnp.where(m & has_prev, ts - prev, 0.0)
        return iat, m & has_prev

    # first matching packet's ts: ts is monotone within a flow, so the
    # first match is the masked minimum (0.0 when nothing matches)
    @functools.cache
    def handshake_ts():
        syn = flag(_FLAG_IDX["syn"]) > 0
        ack = flag(_FLAG_IDX["ack"]) > 0
        return (_masked_min(ts, valid & syn & ~ack),
                _masked_min(ts, valid & syn & ack),
                _masked_min(ts, valid & ack & ~syn))

    fields = {"bytes": size, "winsize": winsize, "ttl": ttl}
    meta = {"proto": proto, "s_port": s_port, "d_port": d_port}

    cols = []
    for entry in plan:
        kind = entry[0]
        if kind == "dur":
            c = dur()
        elif kind == "meta":
            c = meta[entry[1]]
        elif kind == "load":
            byt = _masked_sum(size, dir_mask[entry[1]])
            c = jnp.where(dur() > 0, byt * 8.0 / jnp.maximum(dur(), 1e-9), 0.0)
        elif kind == "pkt_cnt":
            c = jnp.sum(dir_mask[entry[1]], axis=1).astype(jnp.float32)
        elif kind == "handshake":
            t_syn, t_synack, t_ack = handshake_ts()
            if entry[1] == "tcp_rtt":
                c = jnp.maximum(t_ack - t_syn, 0.0)
            elif entry[1] == "syn_ack":
                c = jnp.maximum(t_synack - t_syn, 0.0)
            else:
                c = jnp.maximum(t_ack - t_synack, 0.0)
        elif kind == "flag_cnt":
            c = jnp.sum(
                jnp.where(valid, flag(entry[1]), 0), axis=1
            ).astype(jnp.float32)
        else:  # ("stat", dir, family, stat)
            _, d, fam, stat = entry
            if fam == "iat":
                v, m = dir_iat(d)
            else:
                v, m = fields[fam], dir_mask[d]
            c = _STATS[stat](v, m)
        cols.append(c.astype(jnp.float32))
    return cols


# ---------------------------------------------------------------------------
# merged multi-tenant plans (DESIGN.md §15)
# ---------------------------------------------------------------------------
# PRETZEL-style white-box sharing: N tenants' stats plans union into ONE
# merged plan, extracted once per flow; each tenant reads its column subset
# through a static index map. A merged column is identified by the
# (op descriptor, connection depth) pair — two tenants at the same depth
# share every common op, while meta columns (proto/ports), which no window
# mask touches, share across all depths (stored with depth 0).


def merge_stats_plans(
    plans: Sequence[tuple[tuple, ...]], depths: Sequence[int]
) -> tuple[tuple[tuple, ...], tuple[tuple[int, ...], ...]]:
    """Union-dedup N tenants' static plans into one merged plan.

    Returns ``(merged, tenant_cols)``: ``merged`` is a hashable tuple of
    ``(entry, depth)`` pairs in first-seen order — the unit of
    specialization for the merged extraction executables, exactly like a
    solo plan — and ``tenant_cols[t][i]`` is the merged column that holds
    position ``i`` of tenant t's own plan. Both are static, so the per-
    tenant gather is a compile-time index map, not a runtime lookup.
    """
    if len(plans) != len(depths):
        raise ValueError("plans and depths must align")
    merged: list[tuple[tuple, int]] = []
    where: dict[tuple[tuple, int], int] = {}
    tenant_cols: list[tuple[int, ...]] = []
    for plan, depth in zip(plans, depths):
        cols = []
        for entry in plan:
            key = (entry, 0 if entry[0] == "meta" else int(depth))
            if key not in where:
                where[key] = len(merged)
                merged.append(key)
            cols.append(where[key])
        tenant_cols.append(tuple(cols))
    return tuple(merged), tuple(tenant_cols)


def emit_merged_columns(
    merged: tuple[tuple, ...],
    *,
    ts, size, direction, ttl, winsize, flags, flow_len, proto, s_port, d_port,
):
    """Trace a merged plan's columns over (rows, P) packet tensors.

    One `emit_feature_columns` call per distinct connection depth, with
    the packet window statically sliced to that depth first: a depth-n
    group then reduces over exactly the (rows, n) tensors a solo tenant's
    table would hold, so every merged column is bit-identical to its solo
    twin even when the shared table is wider (union depth). Returns
    float32 (rows,) columns in merged-plan order.
    """
    groups: dict[int, list[int]] = {}
    for i, (_, d) in enumerate(merged):
        groups.setdefault(int(d), []).append(i)
    out: list = [None] * len(merged)
    for d in sorted(groups):
        idxs = groups[d]
        plan = tuple(merged[i][0] for i in idxs)
        # depth-0 groups hold only meta columns; the window never matters
        dd = min(d, ts.shape[1]) if d else 1
        cols = emit_feature_columns(
            plan,
            ts=ts[:, :dd], size=size[:, :dd], direction=direction[:, :dd],
            ttl=ttl[:, :dd], winsize=winsize[:, :dd],
            flags=flags[:, :dd], flow_len=flow_len,
            proto=proto, s_port=s_port, d_port=d_port, depth=dd,
        )
        for i, c in zip(idxs, cols):
            out[i] = c
    return out


def emit_merged_agg_features(merged: tuple[tuple, ...], agg, *,
                             proto, s_port, d_port):
    """Aggregate twin of `emit_merged_columns` (DESIGN.md §12 + §15).

    Running statistics cover the flow's whole lifetime — connection depth
    never clips them — so a merged column's aggregate form is exactly its
    solo `emit_agg_features` column; one emitter call over the deduped
    entry tuple suffices. Returns columns in merged-plan order.
    """
    return emit_agg_features(
        tuple(e for e, _ in merged), agg,
        proto=proto, s_port=s_port, d_port=d_port)


def merged_plan_is_incremental(merged: tuple[tuple, ...]) -> bool:
    """True iff every merged column has an incremental (aggregate) form."""
    return plan_is_incremental(tuple(e for e, _ in merged))


# ---------------------------------------------------------------------------
# incremental aggregate state (DESIGN.md §12)
# ---------------------------------------------------------------------------
# Per-slot running statistics maintained by the flow table on every ingest:
# enough state to reproduce every incrementally-computable `stats_plan`
# column over the flow's WHOLE lifetime (the live view — deliberately not
# clipped to the dispatch window, which is what the classification path
# keeps using). Layout: one float64 row of AGG_WIDTH columns per slot.
#
# Per direction d in {0 (src), 1 (dst)} at base d*AGG_DIR_STRIDE:
#   CNT, then for each of bytes/winsize/ttl: SUM, MIN, MAX, M2 (sum of
#   squared deviations — Welford on the scalar path, Chan merge on the
#   block path), then the inter-arrival block (IAT_CNT, IAT_SUM, IAT_MIN,
#   IAT_MAX, IAT_M2 — the sum telescopes to LAST_TS - FIRST_TS, which is
#   what keeps it exact), then FIRST_TS/LAST_TS (LAST_TS doubles as the
#   previous same-direction timestamp for the next iat sample).
# Globals: TS_MIN/TS_MAX over all valid packets, first-match handshake
# timestamps (monotone ts => first == min, so they merge commutatively),
# and the 8 flag counters.
# Sentinels: min-style cells init to +_BIG, max-style to -_BIG; emission
# maps "never matched" back to the window emitter's 0.0-on-empty.

AGG_DIR_STRIDE = 20
AGG_CNT = 0
AGG_FAM_BASE = {"bytes": 1, "winsize": 5, "ttl": 9}   # +0 SUM +1 MIN +2 MAX +3 M2
AGG_IAT_CNT = 13
AGG_IAT_SUM = 14
AGG_IAT_MIN = 15
AGG_IAT_MAX = 16
AGG_IAT_M2 = 17
AGG_FIRST_TS = 18
AGG_LAST_TS = 19
AGG_TS_MIN = 40
AGG_TS_MAX = 41
AGG_HS_SYN = 42
AGG_HS_SYNACK = 43
AGG_HS_ACK = 44
AGG_FLAGS = 45
AGG_WIDTH = 53

_DIR_OF = {"s": 0, "d": 1}


def agg_init() -> np.ndarray:
    """Pristine per-slot aggregate row (the `_clear_slot` reset value)."""
    v = np.zeros(AGG_WIDTH, np.float64)
    for d in (0, 1):
        b = AGG_DIR_STRIDE * d
        for fb in AGG_FAM_BASE.values():
            v[b + fb + 1] = _BIG
            v[b + fb + 2] = -_BIG
        v[b + AGG_IAT_MIN] = _BIG
        v[b + AGG_IAT_MAX] = -_BIG
        v[b + AGG_FIRST_TS] = _BIG
        v[b + AGG_LAST_TS] = -_BIG
    v[AGG_TS_MIN] = _BIG
    v[AGG_TS_MAX] = -_BIG
    v[AGG_HS_SYN] = _BIG
    v[AGG_HS_SYNACK] = _BIG
    v[AGG_HS_ACK] = _BIG
    return v


AGG_INIT = agg_init()


def plan_is_incremental(plan: tuple[tuple, ...]) -> bool:
    """True iff every plan column is computable from the aggregate row.

    Medians are the one window statistic with no bounded incremental
    form — a plan containing one disables the reuse fast path entirely
    (the runtime falls back to full-window recomputation everywhere).
    """
    return all(not (e[0] == "stat" and e[3] == "med") for e in plan)


def emit_agg_features(plan: tuple[tuple, ...], agg, *, proto, s_port, d_port):
    """Trace the plan's feature columns over (rows, AGG_WIDTH) aggregates.

    The incremental twin of `emit_feature_columns`: same plan, same
    empty-mask semantics (0.0 when a direction/condition never matched),
    but reading the flow table's running statistics instead of the raw
    packet window. Works on numpy arrays (host drift checks, float64) and
    traced jax arrays (the incremental Pallas kernel and its unfused
    reference — both trace THIS emitter, which is what makes them
    bit-identical to each other). Returns float32 (rows,) columns in plan
    order. Raises on a non-incremental plan entry ("med").
    """
    xp = np if isinstance(agg, np.ndarray) else jnp

    def col(i):
        return agg[:, i]

    def dcol(d, i):
        return agg[:, AGG_DIR_STRIDE * d + i]

    cnt = {k: dcol(v, AGG_CNT) for k, v in _DIR_OF.items()}
    n_any = cnt["s"] + cnt["d"]
    dur = xp.where(n_any > 0, col(AGG_TS_MAX) - col(AGG_TS_MIN), 0.0)

    def fam_stat(d, fam, stat):
        di = _DIR_OF[d]
        if fam == "iat":
            c = dcol(di, AGG_IAT_CNT)
            cells = {"sum": AGG_IAT_SUM, "min": AGG_IAT_MIN,
                     "max": AGG_IAT_MAX}
            m2 = dcol(di, AGG_IAT_M2)
        else:
            c = cnt[d]
            fb = AGG_FAM_BASE[fam]
            cells = {"sum": fb, "min": fb + 1, "max": fb + 2}
            m2 = dcol(di, fb + 3)
        if stat == "sum":
            return dcol(di, cells["sum"])
        if stat == "mean":
            return xp.where(
                c > 0, dcol(di, cells["sum"]) / xp.maximum(c, 1.0), 0.0)
        if stat in ("min", "max"):
            return xp.where(c > 0, dcol(di, cells[stat]), 0.0)
        if stat == "std":
            var = m2 / xp.maximum(c, 1.0)
            return xp.where(c > 0, xp.sqrt(xp.maximum(var, 0.0)), 0.0)
        raise ValueError(f"stat {stat!r} has no incremental form")

    def hs(i):
        v = col(i)
        return xp.where(v < _BIG / 2, v, 0.0)

    meta = {"proto": proto, "s_port": s_port, "d_port": d_port}
    cols = []
    for entry in plan:
        kind = entry[0]
        if kind == "dur":
            c = dur
        elif kind == "meta":
            c = meta[entry[1]]
        elif kind == "load":
            byt = dcol(_DIR_OF[entry[1]], AGG_FAM_BASE["bytes"])
            c = xp.where(dur > 0, byt * 8.0 / xp.maximum(dur, 1e-9), 0.0)
        elif kind == "pkt_cnt":
            c = cnt[entry[1]]
        elif kind == "handshake":
            t_syn = hs(AGG_HS_SYN)
            t_synack = hs(AGG_HS_SYNACK)
            t_ack = hs(AGG_HS_ACK)
            if entry[1] == "tcp_rtt":
                c = xp.maximum(t_ack - t_syn, 0.0)
            elif entry[1] == "syn_ack":
                c = xp.maximum(t_synack - t_syn, 0.0)
            else:
                c = xp.maximum(t_ack - t_synack, 0.0)
        elif kind == "flag_cnt":
            c = col(AGG_FLAGS + entry[1])
        else:  # ("stat", dir, family, stat)
            _, d, fam, stat = entry
            c = fam_stat(d, fam, stat)
        cols.append(xp.asarray(c, xp.float32))
    return cols


@functools.partial(jax.jit, static_argnames=("names", "depth", "max_pkts"))
def _extract(
    ts, size, direction, ttl, winsize, flags, flow_len, proto, s_port, d_port,
    *, names: tuple[str, ...], depth: int, max_pkts: int,
):
    cols = emit_feature_columns(
        stats_plan(names),
        ts=ts, size=size, direction=direction, ttl=ttl, winsize=winsize,
        flags=pack_flags(flags), flow_len=flow_len, proto=proto, s_port=s_port,
        d_port=d_port, depth=depth,
    )
    return jnp.stack(cols, axis=1)


def extraction_fn(names: Sequence[str], depth: int, max_pkts: int):
    """Return the jit-specialized extraction callable for (names, depth).

    The returned function is the 'generated pipeline' — its compiled XLA
    executable contains only the ops needed for `names` at `depth`.
    """
    names = tuple(names)

    def run(ds: TrafficDataset):
        return _extract(
            ds.ts, ds.size, ds.direction, ds.ttl, ds.winsize,
            ds.flags, ds.flow_len, ds.proto, ds.s_port,
            ds.d_port, names=names, depth=int(depth), max_pkts=max_pkts,
        )

    return run


def extract_features(
    ds: TrafficDataset, names: Sequence[str], depth: int
) -> np.ndarray:
    """Extract feature matrix (n_flows, len(names)) at connection depth."""
    fn = extraction_fn(tuple(names), int(depth), ds.max_pkts)
    return np.asarray(fn(ds))
