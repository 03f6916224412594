"""setup_s: chip ready to window start (program import, forest, traffic,
warm-up and compile, prefill)."""


def read(r):
    return r.setup_s
