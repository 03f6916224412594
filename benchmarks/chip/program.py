"""The program's layer table reduced to per-layer quantities.

A traced run keeps the table of the program's own tracer
(`repro.serve.obs.Tracer.layers()`: calls, items, total and self ns of each
``cato.*`` span, and its counters) on ``Result.program``; the metric readers
under ``metrics/`` and ``layer_probe.py`` take their quantities from it here.
"""
from __future__ import annotations


def quantities(layers: dict) -> dict:
    """Per-layer quantities of a layer table; a quantity whose span never
    ran is left out.

    - ``pkts_per_observe``: packets per `FlowTable.observe_batch` call;
    - ``slow_path_pct``: share of those packets that took the ordered
      scalar pass (``observe.slow``);
    - ``observe_slow_time_pct``: share of ``observe`` time spent there;
    - ``dispatch_ns_per_pkt``: self time of ``ingest``, ``ready``,
      ``flush`` and ``poll`` per ingested packet.
    """
    sp = layers["spans"]

    def get(name, key):
        return sp.get(name, {}).get(key, 0)

    out = {}
    if get("observe", "calls"):
        out["pkts_per_observe"] = get("observe", "items") / get("observe", "calls")
    if get("observe", "items"):
        out["slow_path_pct"] = 100.0 * get("observe.slow", "items") / get("observe", "items")
    if get("observe", "total_ns"):
        out["observe_slow_time_pct"] = (100.0 * get("observe.slow", "total_ns")
                                        / get("observe", "total_ns"))
    if get("ingest", "items"):
        out["dispatch_ns_per_pkt"] = sum(
            get(n, "self_ns") for n in ("ingest", "ready", "flush", "poll")
        ) / get("ingest", "items")
    return out


def read(r, name: str):
    """Quantity `name` of a run's layer table; None in an untraced run or
    where its span never ran."""
    return None if r.program is None else quantities(r.program).get(name)
