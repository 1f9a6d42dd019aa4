"""Device time per BFS level under the ``bfs.expand`` phase scope: leaf-op
busy time of the window, averaged over the chips, over the levels of the
window's traversals."""

from harness.scopes import level_ms


def read(rec):
    return level_ms(rec, "phase", "expand")
