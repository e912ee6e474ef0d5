"""``d2h_ms.served``: the service's ``transfer.d2h`` spans (the physical
result fetched to the host), in ms per ``dispatch`` span."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "dispatch" for s in spans)
    t = [s["dur_s"] for s in spans if s["name"] == "transfer.d2h"]
    return 1e3 * sum(t) / n if t and n else None
