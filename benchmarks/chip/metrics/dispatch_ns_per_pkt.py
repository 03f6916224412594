"""dispatch_ns_per_pkt: self time of the program's ``cato.ingest``,
``cato.ready``, ``cato.flush`` and ``cato.poll`` spans (the runtime's and the
dispatcher's own work, outside the flow table, submit and resolve) per
ingested packet."""
import program


def read(r):
    return program.read(r, "dispatch_ns_per_pkt")
