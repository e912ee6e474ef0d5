"""``stack_ms.served``: the service's ``serve.stack`` spans (the popped
batch's canonical A and B stacked on the host), in ms per ``dispatch``
span."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "dispatch" for s in spans)
    t = [s["dur_s"] for s in spans if s["name"] == "serve.stack"]
    return 1e3 * sum(t) / n if t and n else None
