"""queue_wait_p99_ms: 99th percentile of flush_ts - ready_ts (dispatch queue)."""
import numpy as np


def read(r):
    q = r.queue_wait_s
    return float(np.percentile(q, 99)) * 1e3 if q.size else None
