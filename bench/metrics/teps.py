"""Graph500 TEPS over the window: the input edges of every traversed
key's component, summed over the traversals completed, over the time
from the first dispatch to the last completion."""


def read(rec):
    if rec["kind"] != "engine":
        return None
    edges = sum(sum(t["edges"]) for t in rec["traversals"])
    return edges / rec["window_s"]
