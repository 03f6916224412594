"""ingest_ns_per_pkt: wall time inside FlowTable.observe_batch per packet."""


def read(r):
    s, _, pkts = r.spans.get("observe", (0.0, 0, 0))
    return s / pkts * 1e9 if pkts else None
