"""Share of device busy time spent in collectives while no other
operation ran on that device (collectives found by HLO opcode)."""


def read(rec):
    tr = rec["trace"]
    if tr is None or tr.busy_s() <= 0 or not tr.has_collectives():
        return None
    return 100.0 * tr.collective_exposed_s() / tr.busy_s()
