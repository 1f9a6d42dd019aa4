"""Mean per-response wait from admission to dispatch
(``timing_ms.queue_wait``, on the front-end's clock)."""


def read(rec):
    ok = [r["timing_ms"] for r in rec.get("requests", ()) if r["timing_ms"]]
    return sum(t["queue_wait"] for t in ok) / len(ok) if ok else None
