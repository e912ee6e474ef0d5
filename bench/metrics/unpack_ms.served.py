"""``unpack_ms.served``: the service's ``codec.unpack`` spans (physical to
canonical on the host), in ms per ``dispatch`` span."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "dispatch" for s in spans)
    t = [s["dur_s"] for s in spans if s["name"] == "codec.unpack"]
    return 1e3 * sum(t) / n if t and n else None
