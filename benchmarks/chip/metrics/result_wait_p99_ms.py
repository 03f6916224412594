"""result_wait_p99_ms: 99th percentile of (result seen - flush_ts): the pending
window and the device, from a batch's flush to its class in the results."""
import numpy as np


def read(r):
    q = r.result_wait_s
    return float(np.percentile(q, 99)) * 1e3 if q.size else None
