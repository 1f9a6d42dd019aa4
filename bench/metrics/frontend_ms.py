"""Mean per request of what the service's own times leave of the
client latency: latency - queue_wait - device.  Holds HTTP, the
response's encoding and decoding, and the client's lateness."""


def read(rec):
    ok = [r for r in rec.get("requests", ()) if r["timing_ms"]]
    if not ok:
        return None
    return sum(1e3 * r["latency_s"] - r["timing_ms"]["queue_wait"]
               - r["timing_ms"]["device"] for r in ok) / len(ok)
