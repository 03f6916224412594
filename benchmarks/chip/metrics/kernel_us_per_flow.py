"""kernel_us_per_flow: device time of the fused kernel's events in the traced
window over the real flows submitted in it."""
import tracefile


def read(r):
    if r.trace is None or not r.trace["device"]:
        return None
    lo, hi = r.trace_window
    ev = tracefile.kernel_events(r.trace["device"][0], r.kernel_match, lo, hi)
    flows = sum(n for _, n in r.submits)
    if not ev or not flows:
        return None
    return sum(d for _, _, d in ev) / 1e3 / flows
