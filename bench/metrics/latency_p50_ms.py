"""Median client latency, from each request's scheduled send time to
its decoded body; a failed request counts as infinitely late."""

from harness.common import nearest_rank


def read(rec):
    if rec["kind"] != "http":
        return None
    return 1e3 * nearest_rank([r["latency_s"] for r in rec["requests"]], 0.5)
