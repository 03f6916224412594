"""observe_slow_time_pct: share of the program's ``cato.observe`` time (the
flow table's ingest) spent in ``cato.observe.slow``, the ordered scalar pass
that a new key's first packet and every FIN packet take."""
import program


def read(r):
    return program.read(r, "observe_slow_time_pct")
