"""pps: packets whose ingest call returned inside the window, over the window."""


def read(r):
    return r.pps_packets / r.window_s
