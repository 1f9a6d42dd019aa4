"""Device time per BFS level under no ``bfs.<phase>`` scope: the rest of
the window's leaf-op busy time (other programs, copies the compiler
added, loop plumbing), averaged over the chips, over the levels of the
window's traversals."""

from harness.scopes import level_ms


def read(rec):
    return level_ms(rec, "phase", "other")
