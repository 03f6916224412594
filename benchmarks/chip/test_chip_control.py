"""The correctness check: the reference agrees with the program's float32 path,
the bfloat16 control served in the program's place reads ``correct: false``,
and so does a run whose timed path is broken.

The runs here skip the harness's look for a chip and serve through the
program's unfused float32 path, which runs on the CPU without the kernel
interpreter; everything else is the run the chip makes, at a small size.
"""
import dataclasses

import numpy as np
import pytest

import gen
import harness
import reference

CELL = "iot-uniform-sat"
CELLS = ("iot-uniform-sat",)
SEED = 2 ** 31 + 99
SMALL = {"pool_flows": 1500, "train_flows": 2000, "prefill_s": 2.0}
_PIPES: dict = {}


def _xla_pipeline(feats, forest, cfg):
    """The program's float32 XLA path; one compiled pipeline per forest,
    handed out as a fresh instance so each run wraps its own methods."""
    from repro.core.forest import DenseForest
    from repro.core.search_space import FeatureRep
    from repro.traffic.pipeline import build_pipeline

    key = forest[1].tobytes()
    if key not in _PIPES:
        f, th, lf = forest
        P = int(cfg["packet_depth"])
        _PIPES[key] = build_pipeline(
            FeatureRep(tuple(feats), P),
            DenseForest(f, th, lf, int(cfg["max_depth"]), len(feats)),
            max_pkts=P, use_kernel=False)
    return dataclasses.replace(_PIPES[key])


def _reference_pipeline(feats, forest, cfg, *, bf16):
    """The reference in the program's place: the program's dispatcher hands
    it each staged batch, and it serves the float64 reference's
    probabilities or, with `bf16`, the bfloat16 control's."""
    f, th, lf = forest
    D = int(cfg["max_depth"])

    def serve(ds):
        flags = (ds.flags.astype(np.uint16) << np.arange(8, dtype=np.uint16)).sum(axis=-1)
        x = reference.features(
            feats, ts=ds.ts, size=ds.size, direction=ds.direction, ttl=ds.ttl,
            winsize=ds.winsize, flags=flags.astype(np.uint8), count=ds.flow_len,
            proto=ds.proto, s_port=ds.s_port, d_port=ds.d_port)
        if bf16:
            return reference.control_probs(x, f, th, lf, D).astype(np.float32)
        lo, hi = reference.prob_interval(x, f, th, lf, D)
        return (0.5 * (lo + hi)).astype(np.float32)

    return dataclasses.replace(_xla_pipeline(feats, forest, cfg), _fn=serve)


def _run(cell, fault=None, control=False, make_pipeline=_xla_pipeline):
    return harness.run(cell, SEED, 1.5, False, pps=4000.0, require_tpu=False,
                       control=control, make_pipeline=make_pipeline, fault=fault,
                       log=lambda s: None, overrides=SMALL)[0]


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_the_program_and_the_control_does_not(cell):
    from repro.traffic.extraction import extract_features
    from repro.traffic.synth import TrafficDataset

    spec = harness.load_cell(cell)
    cfg = dict(spec["config"], **{k: v for k, v in SMALL.items() if k in spec["config"]})
    feats, pool, forest = harness.build_forest(cfg, SEED)
    P = int(cfg["packet_depth"])
    rows = np.arange(pool.n_flows)
    count = np.minimum(pool.flow_len, P)
    x = harness.window_features(feats, pool, rows, count, P)
    flags8 = ((pool.flags[:, :P, None] >> np.arange(8)) & 1).astype(np.uint8)
    ds = TrafficDataset(ts=pool.ts[:, :P], size=pool.size[:, :P],
                        direction=pool.direction[:, :P], ttl=pool.ttl[:, :P],
                        winsize=pool.winsize[:, :P], flags=flags8,
                        flow_len=pool.flow_len, proto=pool.proto,
                        s_port=pool.s_port, d_port=pool.d_port, label=pool.label)
    xp = np.asarray(extract_features(ds, tuple(feats), P), np.float64)
    assert np.all(np.abs(xp - x) <= reference.RTOL / 4 * np.maximum(np.abs(x), 1e-30))
    pipe = _xla_pipeline(feats, forest, cfg)
    p = pipe.probabilities(ds)
    f, th, lf = forest
    lo, hi = reference.prob_interval(x, f, th, lf, int(cfg["max_depth"]))
    ok = reference.gaps(p, p.argmax(axis=1), lo, hi)
    limits = cfg["limits"]
    assert ok["prob_gap"] <= limits["prob_gap"] and ok["class_gap"] <= limits["class_gap"]
    cp = reference.control_probs(x, f, th, lf, int(cfg["max_depth"]))
    bad = reference.gaps(cp, cp.argmax(axis=1), lo, hi)
    assert bad["prob_gap"] > 3 * limits["prob_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(cell):
    out = _run(cell, control=True)
    assert out["correct"] is True
    assert out["attempted"] > 100 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "pps"}
    lim = out["checks"]["prob_gap"]["limit"]
    assert out["control"]["prob_gap"] > lim


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("bf16", [False, True], ids=["float64", "bfloat16_control"])
def test_reference_served_in_the_program_place_is_judged_by_its_precision(bf16, cell):
    out = _run(cell, make_pipeline=lambda *a: _reference_pipeline(*a, bf16=bf16))
    assert out["attempted"] > 100
    assert out["correct"] is (not bf16)
    if bf16:
        assert out["failed"] > 0
        assert out["checks"]["prob_gap"]["value"] > 3 * out["checks"]["prob_gap"]["limit"]


def _half_batch(rt):
    """Half of each batch left out: its rows reach the kernel empty."""
    pipe = rt.dispatcher.pipeline
    submit = pipe.predict_async

    def broken(ds):
        n = int(np.count_nonzero(ds.flow_len))
        ds.flow_len[n // 2:n] = 0
        return submit(ds)

    pipe.predict_async = broken


def _altered_answer(rt):
    """One answer altered where it is produced: the first flow of each batch
    gets the next class."""
    pipe = rt.dispatcher.pipeline
    finalize = pipe.finalize

    def broken(probs):
        out = np.array(finalize(probs))
        out[0] = (out[0] + 1) % probs.shape[1]
        return out

    pipe.finalize = broken


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_half_batch, _altered_answer],
                         ids=["half_batch_left_out", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, cell):
    out = _run(cell, fault=fault)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_no_chip_no_result(capsys):
    import run

    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""


def test_keys_are_distinct():
    k = gen.flow_keys(SEED, 200000)
    assert len(np.unique(k)) == k.size and not np.any(k == 0)
