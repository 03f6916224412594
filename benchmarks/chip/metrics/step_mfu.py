"""step_mfu: the roofline time of the required work of every batch submitted in
the traced window (work.roofline_s) over the traced window: the whole
step's share of the chip's peak."""
import work


def read(r):
    if r.trace is None or r.peak is None or not r.submits:
        return None
    lo, hi = r.trace_window
    need = sum(work.roofline_s(r.shape, n, r.peak) for _, n in r.submits)
    return need / ((hi - lo) / 1e9) * 100.0 if need > 0 else None
