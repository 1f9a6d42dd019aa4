"""Share of the traced window in which no operation ran on the device
(averaged over the cell's chips)."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr.has_device_ops() or tr.window_s() <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
