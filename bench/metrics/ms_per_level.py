"""Synced traversal wall time per BFS level: the window's traversal
times summed, over the levels they ran (``BFSRunStats.levels``)."""


def read(rec):
    if rec["kind"] != "engine":
        return None
    levels = sum(t["levels"] for t in rec["traversals"])
    if not levels:
        return None
    return 1e3 * sum(t["wall_s"] for t in rec["traversals"]) / levels
