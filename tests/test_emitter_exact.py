"""The Mosaic-lowerable emitter ops equal the formulas they replaced.

The iat running max (no `lax.cummax`), the rank-count median (no sort) and
the masked-min handshake timestamps (no argmax + gather) must return
exactly what a plain numpy copy of the sort / cummax / argmax formulas
returns, on random masks with ties, empty masks, one-packet windows and
flows whose packets all go one way.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.traffic.extraction import (
    emit_feature_columns,
    pack_flags,
    stats_plan,
)
from repro.traffic.synth import FLAG_NAMES

BIG = np.float32(3.4e38)
_F = {n: i for i, n in enumerate(FLAG_NAMES)}


def _np_masked_min(v, m):
    return np.where(m.any(1), np.where(m, v, BIG).min(1), 0).astype(np.float32)


def _np_masked_max(v, m):
    return np.where(m.any(1), np.where(m, v, -BIG).max(1), 0).astype(np.float32)


def _np_median(v, m):
    """The sort formula: mean of sorted entries (c-1)//2 and c//2."""
    srt = np.sort(np.where(m, v, BIG), axis=1)
    c = m.sum(1)
    rows = np.arange(len(v))
    lo = srt[rows, np.maximum((c - 1) // 2, 0)]
    hi = srt[rows, np.maximum(c // 2, 0)]
    with np.errstate(over="ignore"):        # empty rows: BIG + BIG
        return np.where(c > 0, np.float32(0.5) * (lo + hi), 0).astype(np.float32)


def _np_iat(ts, m):
    """The cummax formula: exclusive cumulative max of masked timestamps."""
    cm = np.maximum.accumulate(np.where(m, ts, -BIG), axis=1)
    prev = np.concatenate([np.full((len(ts), 1), -BIG, np.float32),
                           cm[:, :-1]], axis=1)
    has = prev > -BIG / 2
    return np.where(m & has, ts - prev, 0).astype(np.float32), m & has


def _np_first_ts(ts, cond):
    """The argmax formula: ts at the first matching packet."""
    i = np.argmax(cond, axis=1)
    return np.where(cond.any(1), ts[np.arange(len(ts)), i], 0)


def _flows(P, seed):
    """Random windows: tied sizes and timestamps, flow_len 0..P (0 is an
    empty mask), the first rows all-source and all-destination."""
    rng = np.random.default_rng(seed)
    n = 64
    ts = np.cumsum(rng.integers(0, 3, (n, P)) * 0.25, axis=1).astype(np.float32)
    size = rng.integers(40, 44, (n, P)).astype(np.float32)
    direction = rng.integers(0, 2, (n, P)).astype(np.uint8)
    direction[0] = 0
    direction[1] = 1
    flags = (rng.random((n, P, 8)) < 0.4).astype(np.uint8)
    flow_len = rng.integers(0, P + 1, n).astype(np.int32)
    flow_len[:4] = P
    return ts, size, direction, flags, flow_len


def _emit(names, ts, size, direction, flags, flow_len, depth):
    n, P = ts.shape
    z = jnp.zeros((n, P), jnp.float32)
    zn = jnp.zeros(n, jnp.float32)
    cols = emit_feature_columns(
        stats_plan(names), ts=jnp.asarray(ts), size=jnp.asarray(size),
        direction=jnp.asarray(direction), ttl=z, winsize=z,
        flags=pack_flags(flags), flow_len=jnp.asarray(flow_len),
        proto=zn, s_port=zn, d_port=zn, depth=depth)
    return [np.asarray(c) for c in cols]


def _masks(ts, direction, flow_len, depth):
    idx = np.arange(ts.shape[1])[None, :]
    valid = (idx < flow_len[:, None]) & (idx < depth)
    return valid, {"s": valid & (direction == 0), "d": valid & (direction == 1)}


CASES = [(1, 0), (2, 1), (7, 2), (16, 3), (16, 4)]


@pytest.mark.parametrize("P,seed", CASES)
def test_median_columns_equal_sort_formula(P, seed):
    ts, size, direction, flags, flow_len = _flows(P, seed)
    _, dm = _masks(ts, direction, flow_len, P)
    got = _emit(("s_bytes_med", "d_bytes_med", "s_iat_med", "d_iat_med"),
                ts, size, direction, flags, flow_len, P)
    want = [_np_median(size, dm["s"]), _np_median(size, dm["d"]),
            _np_median(*_np_iat(ts, dm["s"])), _np_median(*_np_iat(ts, dm["d"]))]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("P,seed", CASES)
def test_iat_columns_equal_cummax_formula(P, seed):
    ts, size, direction, flags, flow_len = _flows(P, seed)
    _, dm = _masks(ts, direction, flow_len, P)
    got = _emit(("s_iat_min", "s_iat_max", "d_iat_min", "d_iat_max"),
                ts, size, direction, flags, flow_len, P)
    want = []
    for d in ("s", "d"):
        v, m = _np_iat(ts, dm[d])
        want += [_np_masked_min(v, m), _np_masked_max(v, m)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("P,seed", CASES)
def test_handshake_columns_equal_argmax_formula(P, seed):
    ts, size, direction, flags, flow_len = _flows(P, seed)
    valid, _ = _masks(ts, direction, flow_len, P)
    syn, ack = flags[:, :, _F["syn"]] > 0, flags[:, :, _F["ack"]] > 0
    t_syn = _np_first_ts(ts, valid & syn & ~ack)
    t_synack = _np_first_ts(ts, valid & syn & ack)
    t_ack = _np_first_ts(ts, valid & ack & ~syn)
    got = _emit(("tcp_rtt", "syn_ack", "ack_dat"),
                ts, size, direction, flags, flow_len, P)
    want = [np.maximum(t_ack - t_syn, 0), np.maximum(t_synack - t_syn, 0),
            np.maximum(t_ack - t_synack, 0)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w.astype(np.float32))


def test_pack_flags_bit_k_is_flag_k():
    flags = np.random.default_rng(5).integers(0, 2, (9, 4, 8)).astype(np.uint8)
    packed = np.asarray(pack_flags(flags))
    for k in range(8):
        np.testing.assert_array_equal((packed >> k) & 1, flags[:, :, k])
