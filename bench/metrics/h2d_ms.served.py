"""``h2d_ms.served``: the service's ``transfer.h2d`` spans (the packed batch
placed on the chip, until resident), in ms per ``dispatch`` span."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "dispatch" for s in spans)
    t = [s["dur_s"] for s in spans if s["name"] == "transfer.h2d"]
    return 1e3 * sum(t) / n if t and n else None
