"""submit_us_per_batch: wall time inside predict_async per batch (host to device)."""


def read(r):
    s, calls, _ = r.spans.get("submit", (0.0, 0, 0))
    return s / calls * 1e6 if calls else None
