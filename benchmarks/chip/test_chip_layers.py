"""The program's layer spans, read through `layer_probe` and the metric
readers: on a run of the cell (on the CPU, as in ``test_chip_control.py``)
they count the same calls as the harness's wraps around them and time them
alike, only a traced run keeps their table, and the reducers give
hand-computed numbers on synthetic input.
"""
import pytest

import harness
import layer_probe
import program
from test_chip_control import CELL, SEED, SMALL, _xla_pipeline

RUN = dict(pps=4000.0, require_tpu=False, make_pipeline=_xla_pipeline,
           log=lambda s: None, overrides=SMALL)
PROGRAM_METRICS = ("observe_slow_time_pct", "dispatch_ns_per_pkt")


@pytest.fixture(scope="module")
def probed():
    return layer_probe.probe(CELL, SEED, 1.5, True, **RUN)


@pytest.fixture(scope="module")
def untraced():
    return harness.run(CELL, SEED, 1.5, False, **RUN)


@pytest.mark.parametrize("name", ["observe", "submit"])
def test_program_spans_agree_with_the_wraps(probed, name):
    out, doc = probed
    assert out["correct"] is True
    wrap_s, wrap_calls, wrap_items = doc["wraps"][name]
    span = doc["layers"]["spans"][name]
    assert wrap_calls > 10 and span["calls"] == wrap_calls
    if name == "observe":
        assert span["items"] == wrap_items
    assert span["total_ns"] / 1e9 == pytest.approx(wrap_s, rel=0.10)


def test_probe_reports_the_window_alone(probed):
    out, doc = probed
    spans, counters = doc["layers"]["spans"], doc["layers"]["counters"]
    assert set(doc["quantities"]) == {"pkts_per_observe", "slow_path_pct",
                                      "observe_slow_time_pct", "dispatch_ns_per_pkt"}
    assert spans["observe"]["items"] == spans["ingest"]["items"] > 0
    # every shape was warmed before the window: nothing compiles in it
    assert counters.get("jax.compiles", 0) == 0
    # with no device operation the whole window is idle; the program's
    # spans cover what the harness's host spans hold
    assert doc["coverage"] > 0.9
    assert set(doc["idle_by_span"]) <= set(layer_probe.LAYER_ORDER) | {"generator"}


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_traced_run_reads_the_program_table(probed, name):
    out, doc = probed
    assert out["metrics"][name] == {"value": doc["quantities"][name],
                                    "unit": "%" if name.endswith("_pct") else "ns"}
    assert doc["quantities"][name] > 0


@pytest.mark.parametrize("name", PROGRAM_METRICS)
def test_untraced_run_attaches_no_tracer(untraced, name):
    out, r = untraced
    assert out["correct"] is True
    assert r.program is None and r.trace is None
    assert harness.metric_reader(name)(r) is None
    assert set(out["metrics"]) == {"setup_s", "pps"}


def test_quantities_by_hand():
    def row(calls, items, total, self_):
        return {"calls": calls, "items": items, "total_ns": total, "self_ns": self_}

    lay = {"spans": {
        "ingest": row(10, 4000, 9_000_000, 1_000_000),
        "observe": row(100, 4000, 6_000_000, 2_000_000),
        "observe.slow": row(80, 200, 1_500_000, 1_500_000),
        "ready": row(100, 90, 700_000, 300_000),
        "flush": row(5, 1280, 400_000, 100_000),
        "poll": row(3, 0, 60_000, 20_000),
    }, "counters": {}}
    q = program.quantities(lay)
    assert q["pkts_per_observe"] == 40.0
    assert q["slow_path_pct"] == 5.0
    assert q["observe_slow_time_pct"] == 25.0
    assert q["dispatch_ns_per_pkt"] == (1_000_000 + 300_000 + 100_000 + 20_000) / 4000
    assert program.quantities({"spans": {}, "counters": {}}) == {}


def test_idle_is_charged_to_the_innermost_program_span():
    spans = [["cato.ingest", 10, 50], ["cato.observe", 20, 10],
             ["cato.observe.slow", 22, 4], ["cato.flush", 40, 15],
             ["cato.submit", 45, 5]]
    ops = [["%fused_forest_infer.1", 0, 10], ["%copy.1", 52, 3]]
    got = layer_probe.idle_by_span(ops, spans, 0, 100)
    assert got == pytest.approx({"observe": 6e-9, "observe.slow": 4e-9,
                                 "ingest": 25e-9, "flush": 7e-9, "submit": 5e-9,
                                 "generator": 40e-9})
