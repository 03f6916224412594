"""The generator repeats from its seed, and the window statistics count every
flow, every packet and the whole window."""
import hashlib
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import gen
import harness

MIX = {"arrivals": "poisson", "prefill_s": 3.0}
BIG_SEED = 2 ** 31 + 12345
TAP_SEED = 0


def _tap(seed):
    tm = gen.make_templates("iot-class", 500, gen.seed_rng(TAP_SEED, 1), class_seed=0)
    return tm, gen.build_tap(tm, seed=seed, tap_seed=TAP_SEED, pps=3000.0, seconds=2.0,
                             mix=MIX, depth=20)


def test_same_seed_same_pool_keys_and_due_times():
    (tm_a, a), (tm_b, b) = _tap(BIG_SEED), _tap(BIG_SEED)
    for f in ("ts", "size", "direction", "ttl", "winsize", "flags", "flow_len", "label"):
        assert np.array_equal(getattr(tm_a, f), getattr(tm_b, f))
    for f in ("due", "key", "inst", "pidx", "tmpl", "start"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    _, c = _tap(BIG_SEED + 1)
    assert not np.array_equal(a.key[:100], c.key[:100])
    assert len(np.unique(a.key[a.inst == a.inst])) == len(a.start)


def test_every_seed_offers_the_same_work_in_another_order():
    _, a = _tap(BIG_SEED)
    _, b = _tap(BIG_SEED + 1)
    assert np.array_equal(a.start, b.start)
    assert np.array_equal(np.sort(a.tmpl), np.sort(b.tmpl))
    assert not np.array_equal(a.tmpl, b.tmpl)
    uses = np.bincount(a.tmpl, minlength=500)
    assert uses.max() - uses.min() <= 1


def test_tap_order_and_prefill():
    tm, t = _tap(7)
    assert np.all(np.diff(t.due) >= 0)
    assert t.n_prefill == int(np.sum(t.due < t.t0))
    assert t.due[-1] < t.t0 + 2.0
    # within a flow, packets arrive in order
    order = np.lexsort((np.arange(len(t.inst)), t.inst))
    same = t.inst[order][1:] == t.inst[order][:-1]
    assert np.all(np.diff(t.pidx[order].astype(np.int64))[same] > 0)
    # before the window only the first 20 packets and each flow's last
    # pre-window packet are kept
    pre = np.arange(len(t.due)) < t.n_prefill
    late = pre & (t.pidx >= 20)
    assert np.bincount(t.inst[late], minlength=len(t.start)).max() <= 1


def test_mmpp_keeps_the_mean_rate_and_its_phases():
    mix = {"arrivals": "mmpp", "on_rate_x": 4.0, "on_s": 0.5, "on_share": 0.5,
           "phase_offset_s": 0.0}
    s = gen.flow_starts(gen.seed_rng(3, 2), 1000.0, 0.0, 400.0, mix, 0.0)
    assert abs(len(s) / 400.0 - 1000.0) < 30.0
    phase = np.mod(s, 4.0)
    assert abs(np.mean(phase < 0.5) - 0.5) < 0.02


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-5
        return self.t


def _drive(stall_at=None, stall_s=0.3, seconds=2.0, rate=20000.0, cost=6e-5):
    """A fake served path that takes `cost` s per packet (saturated at
    `rate`), and stalls once for `stall_s` at window time `stall_at`."""
    due = np.arange(int(rate * seconds * 1.2)) / rate
    clock = _Clock()
    seen = np.full(len(due), np.nan)
    state = {"lo": 0, "stalled": False}

    def ingest(lo, hi):
        clock.t += cost * (hi - lo)
        if stall_at is not None and not state["stalled"] and clock.t >= stall_at:
            clock.t += stall_s
            state["stalled"] = True
        state["lo"], state["hi"] = lo, hi

    def after(now):
        if "hi" in state:
            seen[state["lo"]:state["hi"]] = now
            state.pop("hi")

    i, returned, calls_lo, calls_t, _ = harness.drive(
        due, 0, 512, seconds, 0.0, ingest, lambda now: None, after, clock=clock)
    ttc = seen[:i] - due[:i]
    r = harness.Result()
    r.window_s = seconds
    r.pps_packets = returned
    r.ttc_s = ttc[due[:i] < seconds]
    return r, harness.lateness(calls_lo, calls_t, due, i, 0.0)


def _reader(name):
    path = Path(harness.__file__).parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_rate_is_all_packets_over_the_whole_window_and_a_stall_moves_both():
    pps, p99 = _reader("pps"), _reader("ttc_p99_ms")
    r0, late0 = _drive()
    r1, late1 = _drive(stall_at=1.0)
    # saturated at 1/cost packets/s: every packet that returned in the window,
    # over the whole window
    assert abs(pps(r0) - 1.0 / 6e-5) < 0.02 / 6e-5
    assert pps(r1) < pps(r0) - 0.1 / 6e-5
    assert p99(r1) > p99(r0) + 100.0
    assert late1.max() > late0.max() + 0.25


def test_tail_is_over_all_flows():
    r = harness.Result()
    ttc = np.full(1000, 0.001)
    ttc[1::50] = 0.5        # 2% slow flows, none at a multiple of ten
    r.ttc_s = ttc
    assert _reader("ttc_p99_ms")(r) == 500.0
    assert _reader("ttc_p50_ms")(r) == 1.0


def test_window_waits_keep_only_flows_ready_in_the_window():
    ready = [np.array([0.5, 1.5, 2.5]), np.array([9.9, 10.5])]
    flush = [np.full(3, 2.6), np.full(2, 10.6)]
    seen = [np.full(3, 2.7), np.full(2, 10.7)]
    ttc, q, res = harness.window_waits(ready, flush, seen, 1.0, 9.0)
    assert np.allclose(ttc, [1.2, 0.2, 0.8])
    assert np.allclose(q, [1.1, 0.1, 0.7])
    assert np.allclose(res, [0.1, 0.1, 0.1])


# sha256 of (dtype, shape, bytes) of every array of the tap, then (t0,
# n_prefill), from a 2,000-flow iot-class pool of tap seed 0 at 20,000
# pkts/s for 2 s: any change to the recipe or its sort shows here
TAP_DIGESTS = {
    ("uniform-sat", BIG_SEED): "7b7f3143586f7a533f262db4736eafa6e7b783f9b69fdeff979de46ef43ae165",
    ("burst-tail", 7): "d1d2a18a10d68ac5d4cd9920573d8c1c012dffe540a39213e9e35148f56ed981",
}
TAP_FIELDS = ("due", "inst", "pidx", "key", "rel_ts", "size", "direction", "ttl",
              "winsize", "flags", "proto", "s_port", "d_port", "flow_id", "fin",
              "tmpl", "start")


@pytest.mark.parametrize("mix_name, seed", sorted(TAP_DIGESTS))
def test_tap_is_bit_identical(mix_name, seed):
    mix = json.loads((Path(gen.__file__).parent / "traffic" / f"{mix_name}.json").read_text())
    tm = gen.make_templates("iot-class", 2000, gen.seed_rng(TAP_SEED, 1), class_seed=0)
    t = gen.build_tap(tm, seed=seed, tap_seed=TAP_SEED, pps=20000.0, seconds=2.0,
                      mix=mix, depth=20)
    h = hashlib.sha256()
    for f in TAP_FIELDS:
        a = getattr(t, f)
        h.update(a.dtype.str.encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr((t.t0, t.n_prefill)).encode())
    assert h.hexdigest() == TAP_DIGESTS[(mix_name, seed)]


@pytest.mark.parametrize("values", [0, 3, 1000], ids=["all_equal", "few_values", "many_values"])
def test_stable_sort_is_numpy_stable_argsort(values):
    x = np.random.default_rng(values).integers(0, values + 1, 50000).astype(np.float64)
    order, s = gen.stable_sort(x)
    assert np.array_equal(order, np.argsort(x, kind="stable"))
    assert np.array_equal(s, np.sort(x))
