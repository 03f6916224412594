"""ttc_p50_ms: median time-to-classification over every flow ready in the window."""
import numpy as np


def read(r):
    return float(np.percentile(r.ttc_s, 50)) * 1e3 if r.ttc_s.size else None
