"""Traffic generation for the chip benchmark: template flows and a packet tap.

One general generator reads every traffic mix. A mix is a JSON file under
``traffic/`` (rate as a multiple of the config's knee, flow-start process,
prefill span, ingest block cap); a configuration is a JSON file under
``configs/`` (use case, features, packet depth, forest shape). Nothing here
imports the program under test.

Flow shapes are a copy of the program's synthetic use cases
(``repro.traffic.synth.make_dataset``: handshake prefix, class-specific
message sizes and think-times, lognormal sizes and inter-arrivals, per-class
TTLs, windows and ports, FIN on the last packet of ~80% of flows). These
shapes are assumed, not fitted to a published trace: `shape_stats` reports
what they give. Class parameters come from the configuration's fixed
``class_seed``, so every run serves the same device population; the template
pool and the flow starts come from its fixed ``tap_seed``, so every run
offers the same work. The run's seed orders the templates over the starts
and draws the 5-tuple keys (and, in the harness, the forest).

A tap at R packets/s holds every flow that is alive, so flows keep their own
timestamps: flows start as a Poisson process (or the mix's two-state MMPP) at
``R / mean packets per flow``, and a packet is due at its flow's start plus
its flow-relative timestamp. A pool of template flows is tiled with fresh
keys, so set-up stays short and the reference runs once per template.
"""
from __future__ import annotations

import dataclasses

import numpy as np

FLAG_NAMES = ("cwr", "ece", "urg", "ack", "psh", "rst", "syn", "fin")
_F = {n: i for i, n in enumerate(FLAG_NAMES)}
MAX_PKTS = 128


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one purpose (`stream`) of one run seed."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorised splitmix64 finaliser over uint64 (mod 2**64 arithmetic)."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def flow_keys(seed: int, n: int) -> np.ndarray:
    """`n` distinct non-zero 64-bit 5-tuple keys for one run's flows."""
    base = splitmix64(np.uint64(int(seed) % (1 << 64)))
    keys = splitmix64(np.arange(n, dtype=np.uint64) ^ base)
    return np.where(keys == 0, np.uint64(1), keys)


@dataclasses.dataclass
class Templates:
    """Dense per-flow packet tensors, (n_flows, MAX_PKTS), as synth lays them out."""

    ts: np.ndarray         # float32 seconds since flow start
    size: np.ndarray       # float32 bytes
    direction: np.ndarray  # uint8, 0 = src->dst
    ttl: np.ndarray        # float32
    winsize: np.ndarray    # float32
    flags: np.ndarray      # uint8 packed flag byte, bit k = FLAG_NAMES[k]
    flow_len: np.ndarray   # int32 packets in the flow
    proto: np.ndarray      # float32
    s_port: np.ndarray     # float32
    d_port: np.ndarray     # float32
    label: np.ndarray      # int32 class

    @property
    def n_flows(self) -> int:
        return self.ts.shape[0]


def _class_params(K: int, rng: np.random.Generator, kind: str) -> dict:
    p = {}
    if kind == "app":
        p["ttl_s"] = rng.choice([64, 128], K) + rng.integers(-2, 3, K)
        p["ttl_d"] = rng.choice([54, 57, 60], K) + rng.integers(-2, 3, K)
        p["win_base"] = rng.choice([29200, 65535], K) * (
            1 + 0.05 * rng.standard_normal(K))
        p["d_port"] = np.full(K, 443)
    else:
        p["ttl_s"] = rng.choice([32, 64, 64, 128, 255], K) + rng.integers(-3, 4, K)
        p["ttl_d"] = rng.choice([32, 64, 128, 128, 255], K) + rng.integers(-3, 4, K)
        p["win_base"] = rng.choice([8192, 16384, 29200, 65535, 65535 // 2], K) * (
            1 + 0.1 * rng.standard_normal(K))
        p["d_port"] = rng.choice([443, 443, 443, 80, 8883, 1883, 5683], K)
    p["size_mu_s"] = rng.uniform(4.0, 7.2, K)
    p["size_mu_d"] = rng.uniform(4.3, 7.3, K)
    p["size_sigma"] = rng.uniform(0.1, 0.4, K)
    p["iat_mu"] = rng.uniform(-7.0, 1.0, K)
    p["iat_sigma"] = rng.uniform(0.15, 0.6, K)
    p["psh_prob"] = rng.uniform(0.05, 0.6, K)
    p["rst_prob"] = rng.uniform(0.0, 0.05, K)
    p["src_frac"] = rng.uniform(0.2, 0.8, K)
    p["hello_size"] = rng.uniform(120, 1100, K)
    p["len_mean"] = rng.uniform(6, 80, K) if kind == "iot" else rng.uniform(15, 160, K)
    n_msg = 6
    p["msg_seq"] = rng.uniform(80, 1400, (K, n_msg))
    p["round_pat"] = np.exp(rng.uniform(-6.5, -0.5, (K, n_msg)))
    return p


USE_CASES = {"iot-class": (28, "iot"), "app-class": (7, "app")}


def make_templates(use_case: str, n_flows: int, rng: np.random.Generator,
                   class_seed: int, label_noise: float = 0.02) -> Templates:
    """`n_flows` template flows of one use case (the synth recipe)."""
    K, kind = USE_CASES[use_case]
    prm = _class_params(K, np.random.default_rng(class_seed), kind)
    P = MAX_PKTS
    y = rng.integers(0, K, n_flows)
    flow_len = np.clip(3 + rng.exponential(prm["len_mean"][y]).astype(np.int64),
                       3, P).astype(np.int32)
    idx = np.arange(P)[None, :]
    in_flow = idx < flow_len[:, None]

    direction = (rng.random((n_flows, P)) > prm["src_frac"][y][:, None]).astype(np.uint8)
    direction[:, 0] = 0
    direction[:, 1] = 1
    direction[:, 2] = 0

    mu = np.where(direction == 0, prm["size_mu_s"][y][:, None],
                  prm["size_mu_d"][y][:, None])
    size = np.exp(mu + prm["size_sigma"][y][:, None]
                  * rng.standard_normal((n_flows, P)))
    size = np.clip(size, 40, 1500)
    size[:, 0] = 60 + rng.integers(0, 4, n_flows)
    size[:, 1] = 60 + rng.integers(0, 4, n_flows)
    size[:, 2] = 52 + rng.integers(0, 3, n_flows)
    jit_ = 1 + 0.06 * rng.standard_normal((n_flows, 6))
    size[:, 3:9] = np.clip(prm["msg_seq"][y] * jit_, 40, 1500)

    rtt = np.exp(rng.uniform(-5.5, -2.5, n_flows))
    iat = np.exp(prm["iat_mu"][y][:, None]
                 + prm["iat_sigma"][y][:, None] * rng.standard_normal((n_flows, P)))
    iat[:, 3:9] = prm["round_pat"][y] * (
        1 + 0.15 * np.abs(rng.standard_normal((n_flows, 6))))
    iat[:, 0] = 0.0
    iat[:, 1] = rtt
    iat[:, 2] = rtt * (1 + 0.1 * rng.random(n_flows))
    ts = np.cumsum(iat * in_flow, axis=1).astype(np.float32)

    ttl_s = prm["ttl_s"][y] + rng.integers(-1, 2, n_flows)
    ttl_d = prm["ttl_d"][y] + rng.integers(-1, 2, n_flows)
    ttl = np.where(direction == 0, ttl_s[:, None], ttl_d[:, None]).astype(np.float32)

    ramp = np.minimum(1.0, (idx + 1) / 8.0)
    winsize = (prm["win_base"][y][:, None] * ramp
               * (1 + 0.05 * rng.standard_normal((n_flows, P)))).astype(np.float32)

    fl = np.zeros((n_flows, P), np.uint8)
    fl[:, 0] |= 1 << _F["syn"]
    fl[:, 1] |= (1 << _F["syn"]) | (1 << _F["ack"])
    fl[:, 2:] |= 1 << _F["ack"]
    data = (idx >= 3) & in_flow
    psh = data & (rng.random((n_flows, P)) < prm["psh_prob"][y][:, None])
    rst = data & (rng.random((n_flows, P)) < prm["rst_prob"][y][:, None] * 0.1)
    fl |= (psh.astype(np.uint8) << _F["psh"]) | (rst.astype(np.uint8) << _F["rst"])
    has_fin = rng.random(n_flows) < 0.8
    last = np.minimum(flow_len - 1, P - 1)
    fl[np.arange(n_flows), last] |= (has_fin.astype(np.uint8) << _F["fin"])
    fl *= in_flow.astype(np.uint8)

    proto = np.full(n_flows, 6.0, np.float32)
    s_port = rng.integers(32768, 61000, n_flows).astype(np.float32)
    d_port = prm["d_port"][y].astype(np.float32)
    for arr in (size, ttl, winsize):
        arr *= in_flow
    ts = ts * in_flow
    flip = rng.random(n_flows) < label_noise
    y = np.where(flip, rng.integers(0, K, n_flows), y).astype(np.int32)
    return Templates(ts=ts.astype(np.float32), size=size.astype(np.float32),
                     direction=direction, ttl=ttl, winsize=winsize, flags=fl,
                     flow_len=flow_len, proto=proto, s_port=s_port,
                     d_port=d_port, label=y)


def shape_stats(tm: Templates, depth: int) -> dict:
    """Per-flow statistics of a template pool: what the assumed shapes give
    (packets, duration, mean packet size, the share of flows that send a
    FIN, and the share of packets past the packet depth, which only touch
    the flow's counters)."""
    n = tm.flow_len.astype(np.float64)
    dur = tm.ts[np.arange(tm.n_flows), tm.flow_len - 1].astype(np.float64)
    fins = ((tm.flags >> _F["fin"]) & 1).sum(axis=1)
    return {"pkts_mean": float(n.mean()), "pkts_median": float(np.median(n)),
            "short_share": float(np.mean(n < depth)),
            "past_depth_share": float(np.maximum(n - depth, 0).sum() / n.sum()),
            "dur_mean_s": float(dur.mean()), "dur_p99_s": float(np.percentile(dur, 99)),
            "bytes_mean": float(tm.size.sum() / n.sum()),
            "fin_share": float(np.mean(fins > 0))}


# ---------------------------------------------------------------------------
# flow starts
# ---------------------------------------------------------------------------

def flow_starts(rng: np.random.Generator, rate: float, t_lo: float,
                t_hi: float, mix: dict, t0: float) -> np.ndarray:
    """Flow start times in [t_lo, t_hi) at mean `rate` flows/s.

    ``mix["arrivals"]`` is ``"poisson"`` or ``"mmpp"``. The MMPP has an ON
    phase of ``on_s`` seconds at ``on_rate_x`` times the mean rate and an
    OFF phase sized so that ``on_share`` of the flows start in ON phases and
    the mean rate is kept. Phases have fixed lengths and the cycle starts at
    ``t0 + phase_offset_s``, so every seed sees the same phases in the
    window, with Poisson starts inside them.
    """
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        n_max = int((t_hi - t_lo) * rate * 1.2 + 10 * np.sqrt((t_hi - t_lo) * rate) + 100)
        s = t_lo + np.cumsum(rng.exponential(1.0 / rate, n_max))
        while s[-1] < t_hi:
            s = np.concatenate([s, s[-1] + np.cumsum(rng.exponential(1.0 / rate, n_max))])
        return s[s < t_hi]
    if kind != "mmpp":
        raise ValueError(f"unknown arrival process {kind!r}")
    on_x = float(mix["on_rate_x"])
    on_s = float(mix["on_s"])
    share = float(mix["on_share"])
    f_on = share / on_x                  # share of time spent in ON phases
    cycle = on_s / f_on
    off_rate = rate * (1.0 - share) / (1.0 - f_on)
    start = t0 + float(mix.get("phase_offset_s", 0.0))
    k0 = int(np.floor((t_lo - start) / cycle)) - 1
    out = []
    k = k0
    while start + k * cycle < t_hi:
        c = start + k * cycle
        for lo, hi, r in ((c, c + on_s, rate * on_x), (c + on_s, c + cycle, off_rate)):
            lo_, hi_ = max(lo, t_lo), min(hi, t_hi)
            if hi_ > lo_:
                n = rng.poisson(r * (hi_ - lo_))
                out.append(np.sort(rng.uniform(lo_, hi_, n)))
        k += 1
    return np.concatenate(out) if out else np.zeros(0)


# ---------------------------------------------------------------------------
# the tap: per-packet arrays in due-time order
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tap:
    """Every packet of one run, sorted by due time on the stream clock.

    Packets with ``due < t0`` are the prefill; the window starts at `t0`.
    `inst` and `pidx` name each packet's flow instance and its index in that
    flow; `tmpl[i]` is instance i's template row.
    """

    due: np.ndarray
    inst: np.ndarray
    pidx: np.ndarray
    key: np.ndarray
    rel_ts: np.ndarray
    size: np.ndarray
    direction: np.ndarray
    ttl: np.ndarray
    winsize: np.ndarray
    flags: np.ndarray
    proto: np.ndarray
    s_port: np.ndarray
    d_port: np.ndarray
    flow_id: np.ndarray
    fin: np.ndarray
    tmpl: np.ndarray       # (n_inst,) template row per flow instance
    start: np.ndarray      # (n_inst,) flow start on the stream clock
    t0: float
    n_prefill: int         # packets before the window

    def block(self, lo: int, hi: int) -> tuple:
        """Arguments of `StreamingRuntime.ingest_packets` for packets [lo, hi),
        with each packet's due time as its arrival clock."""
        s = slice(lo, hi)
        return (self.key[s], self.due[s], self.rel_ts[s], self.size[s],
                self.direction[s], self.ttl[s], self.winsize[s],
                self.flags[s], self.proto[s], self.s_port[s], self.d_port[s],
                self.flow_id[s], self.fin[s])


def build_tap(tm: Templates, *, seed: int, tap_seed: int, pps: float,
              seconds: float, mix: dict, depth: int) -> Tap:
    """Tile the template pool into the packet stream of one run.

    Every run seed gets the same work in another order: the flow starts are
    drawn from `tap_seed`, every template row starts the same number of
    times (the first ``n mod pool`` rows once more), and `seed` only
    decides which template starts when and draws the keys.

    Flows start over ``[t0 - prefill_s, t0 + seconds)``; a packet is due at
    its flow's start plus its flow-relative timestamp, and only packets due
    before the window's end exist. Before the window only the packets that
    set the flow table's state are kept: a flow's first `depth` packets
    (its payload, and the classification they trigger) and its last packet
    before `t0` (its last-seen time and FIN bits); packets between them
    only touch counters. This keeps the prefill short without changing what
    the table holds when the window starts.
    """
    mean_pkts = float(tm.flow_len.mean())
    rate = pps / mean_pkts
    prefill = float(mix["prefill_s"])
    t0 = prefill
    starts = flow_starts(seed_rng(tap_seed, 2), rate, 0.0, t0 + seconds, mix, t0)
    n_inst = len(starts)
    tmpl = seed_rng(seed, 2).permutation(
        np.resize(np.arange(tm.n_flows, dtype=np.int32), n_inst))
    lens = tm.flow_len[tmpl].astype(np.int64)
    inst = np.repeat(np.arange(n_inst, dtype=np.int32), lens)
    P = tm.ts.shape[1]
    # each packet's flat index into the (n_flows, P) template arrays:
    # its template row times P plus its index in the flow
    first = np.cumsum(lens) - lens
    flat = np.arange(lens.sum(), dtype=np.int64) - np.repeat(first - tmpl * np.int64(P), lens)
    pidx = flat % P
    due = starts[inst] + tm.ts.ravel()[flat]
    keep = due < t0 + seconds
    # before t0: the first `depth` packets and the last one before t0
    pre = due < t0
    nxt_pre = np.zeros_like(pre)
    nxt_pre[:-1] = pre[1:] & (inst[1:] == inst[:-1])
    last_pre = pre & ~nxt_pre
    keep &= ~pre | (pidx < depth) | last_pre
    kept = np.flatnonzero(keep)
    order, due = stable_sort(due[kept])
    kept = kept[order]
    inst, flat = inst[kept], flat[kept]
    pidx, trow = (flat % P).astype(np.int16), flat // P
    fb = tm.flags.ravel()[flat]
    keys = flow_keys(seed, n_inst)
    return Tap(
        due=due, inst=inst, pidx=pidx, key=keys[inst],
        rel_ts=tm.ts.ravel()[flat], size=tm.size.ravel()[flat],
        direction=tm.direction.ravel()[flat], ttl=tm.ttl.ravel()[flat],
        winsize=tm.winsize.ravel()[flat], flags=fb,
        proto=tm.proto[trow], s_port=tm.s_port[trow], d_port=tm.d_port[trow],
        flow_id=inst, fin=(fb >> _F["fin"]) & 1 > 0,
        tmpl=tmpl, start=starts, t0=t0,
        n_prefill=int(np.searchsorted(due, t0, side="left")),
    )


def stable_sort(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``np.argsort(x, kind="stable")`` and `x` in that order, by a faster
    unstable sort whose runs of equal values are then put back in index
    order."""
    order = np.argsort(x)
    s = x[order]
    tie = s[1:] == s[:-1]
    if tie.any():
        in_run = np.zeros(len(x), bool)
        in_run[1:] |= tie
        in_run[:-1] |= tie
        at = np.flatnonzero(in_run)
        sub = order[at]
        order[at] = sub[np.lexsort((sub, s[at]))]
    return order, s
