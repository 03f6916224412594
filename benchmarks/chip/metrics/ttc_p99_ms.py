"""ttc_p99_ms: 99th percentile time-to-classification over every flow ready in
the window, from its ready packet's due time to the first return of an
ingest or poll call after which its class is in the results."""
import numpy as np


def read(r):
    return float(np.percentile(r.ttc_s, 99)) * 1e3 if r.ttc_s.size else None
