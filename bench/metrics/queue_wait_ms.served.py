"""``queue_wait_ms.served``: mean ``queue_wait_s`` of the service's
``request`` spans (admission to seating), in ms."""


def read(record):
    w = [s["attrs"]["queue_wait_s"] for s in record.counters.get("spans", ())
         if s["name"] == "request"]
    return 1e3 * sum(w) / len(w) if w else None
