"""Process start to window start, on the host clock."""


def read(rec):
    return rec["setup_s"]
