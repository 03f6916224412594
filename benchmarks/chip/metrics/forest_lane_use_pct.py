"""forest_lane_use_pct: the share of the forest traversal's one-hot lanes that
hold real nodes, 100 x the program's ``forest.nodes`` counter (real internal
nodes of the served forest, per batch) over its ``forest.lanes`` counter
(node slots the one-hots read for one flow, per batch). None where the
program counts neither."""


def read(r):
    if r.program is None:
        return None
    c = r.program["counters"]
    lanes = c.get("forest.lanes", 0)
    return 100.0 * c.get("forest.nodes", 0) / lanes if lanes else None
