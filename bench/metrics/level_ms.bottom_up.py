"""Device time per bottom_up level: leaf-op busy time of the window under the
``bfs.bottom_up`` mode scope, averaged over the chips, over the levels that ran
in that mode (``BFSRunStats.mode_counts``); nothing where none did."""

from harness.scopes import level_ms


def read(rec):
    return level_ms(rec, "mode", "bottom_up")
