"""Device time per queue level: leaf-op busy time of the window under the
``bfs.queue`` mode scope, averaged over the chips, over the levels that ran
in that mode (``BFSRunStats.mode_counts``); nothing where none did."""

from harness.scopes import level_ms


def read(rec):
    return level_ms(rec, "mode", "queue")
