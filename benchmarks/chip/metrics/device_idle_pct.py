"""device_idle_pct: 1 - union of device operation intervals over the traced window."""
import tracefile


def read(r):
    if r.trace is None or not r.trace["device"]:
        return None
    lo, hi = r.trace_window
    busy = tracefile.busy_ns(r.trace["device"][0], lo, hi)
    return (1.0 - busy / (hi - lo)) * 100.0
