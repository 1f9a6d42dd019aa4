"""Mean per-response device time (``timing_ms.device``: dispatch to
sync, on the front-end's clock)."""


def read(rec):
    ok = [r["timing_ms"] for r in rec.get("requests", ()) if r["timing_ms"]]
    return sum(t["device"] for t in ok) / len(ok) if ok else None
