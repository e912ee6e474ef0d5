"""``pack_ms.served``: the service's ``codec.pack`` spans (canonical to
physical on the host, A and B), in ms per ``dispatch`` span."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "dispatch" for s in spans)
    t = [s["dur_s"] for s in spans if s["name"] == "codec.pack"]
    return 1e3 * sum(t) / n if t and n else None
