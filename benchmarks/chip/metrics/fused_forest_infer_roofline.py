"""fused_forest_infer_roofline: the fused kernel's share of its roofline, the
least time the required work of the batches submitted in the traced window
needs (work.roofline_s) over the kernel's device time in that window."""
import tracefile
import work


def read(r):
    if r.trace is None or r.peak is None or not r.trace["device"]:
        return None
    lo, hi = r.trace_window
    ev = tracefile.kernel_events(r.trace["device"][0], r.kernel_match, lo, hi)
    k_s = sum(d for _, _, d in ev) / 1e9
    need = sum(work.roofline_s(r.shape, n, r.peak) for _, n in r.submits)
    if k_s <= 0 or need <= 0:
        return None
    return need / k_s * 100.0
