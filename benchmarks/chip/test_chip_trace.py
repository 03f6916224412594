"""The trace reducers give, on a small recorded trace, the numbers recorded
beside it.

``fixtures/trace_v5e.json`` is 100 ms of the traced window of an
``iot-uniform-sat`` run on one TPU v5 lite chip, normalised by
`tracefile.load` (the program's ``cato.*`` spans under ``program``) and
trimmed by `tracefile.trim`, with the forest's shape as `work.Shape` counts
it, the submits of that span and what the metric readers returned from it
when it was recorded.
"""
import importlib.util
import json
from pathlib import Path

import pytest

import harness
import tracefile
import work

HERE = Path(__file__).resolve().parent
DOC = json.loads((HERE / "fixtures" / "trace_v5e.json").read_text())


def _result():
    r = harness.Result()
    r.trace = DOC["trace"]
    r.trace_window = tuple(DOC["window"])
    r.submits = [tuple(s) for s in DOC["submits"]]
    r.shape = work.Shape(*DOC["shape"])
    r.peak = DOC["peak"]
    r.kernel_match = tuple(DOC["kernel_match"])
    return r


def _read(name, r):
    spec = importlib.util.spec_from_file_location(name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(r)


@pytest.mark.parametrize("name", ["kernel_us_per_flow", "device_idle_pct",
                                  "fused_forest_infer_roofline", "step_mfu"])
def test_metric_reads_the_recorded_number(name):
    assert _read(name, _result()) == pytest.approx(DOC["expect"][name], rel=1e-12)


def test_breakdown_and_busy_time():
    lo, hi = DOC["window"]
    ops = DOC["trace"]["device"][0]
    assert tracefile.window(DOC["trace"]) == (lo, hi)
    assert tracefile.busy_ns(ops, lo, hi) == DOC["expect"]["busy_ns"]
    for got, want in ((tracefile.top_ops(ops, lo, hi), DOC["expect"]["top_ops"]),
                      (tracefile.idle_by_host(ops, DOC["trace"]["host"], lo, hi),
                       DOC["expect"]["idle_gaps"])):
        assert [n for n, _ in got] == [n for n, _ in want]
        assert [v for _, v in got] == pytest.approx([v for _, v in want], rel=1e-12)
    assert DOC["expect"]["top_ops"][0][0] == "%fused_forest_infer.1"


def test_shares_stay_under_their_peak():
    r = _result()
    assert 0 < _read("fused_forest_infer_roofline", r) <= 100
    assert 0 < _read("step_mfu", r) <= 100
    assert 0 <= _read("device_idle_pct", r) <= 100


def test_idle_is_charged_to_the_innermost_host_span():
    host = [["bench.window", 0, 100], ["bench.ingest", 10, 50],
            ["bench.observe", 20, 10], ["bench.submit", 70, 10]]
    ops = [["%fused_forest_infer.1", 0, 10], ["%copy.1", 60, 5]]
    got = dict(tracefile.idle_by_host(ops, host, 0, 100))
    assert got == pytest.approx({"observe": 10e-9, "ingest": 40e-9, "submit": 10e-9,
                                 "generator": 25e-9})
    assert tracefile.busy_ns(ops, 0, 100) == 15
    assert tracefile.is_kernel("%fused_forest_infer.1", ["%fused_forest_infer"])
    assert not tracefile.is_kernel("%copy.31", ["%fused_forest_infer"])


def test_program_spans_share_the_profiler_clock():
    # the program's cato.* spans were kept beside the harness's own, and
    # the device's idle time of the window is charged to them in full
    import layer_probe

    lo, hi = DOC["window"]
    ops, prog = DOC["trace"]["device"][0], DOC["trace"]["program"]
    names = {n for n, _, _ in prog}
    assert {"cato.observe", "cato.ingest", "cato.submit"} <= names
    assert all(n.startswith(tracefile.PROGRAM_PREFIX) for n in names)
    idle = layer_probe.idle_by_span(ops, prog, lo, hi)
    assert set(idle) <= set(layer_probe.LAYER_ORDER) | {"generator"}
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - tracefile.busy_ns(ops, lo, hi)) / 1e9, rel=1e-9)
