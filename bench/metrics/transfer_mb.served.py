"""``transfer_mb.served``: bytes moved by the service's ``transfer.h2d`` and
``transfer.d2h`` spans, per completed request (``request`` span), in MB
(10^6 B)."""


def read(record):
    spans = record.counters.get("spans", ())
    n = sum(s["name"] == "request" for s in spans)
    b = [s["attrs"]["bytes"] for s in spans
         if s["name"] in ("transfer.h2d", "transfer.d2h")]
    return sum(b) / n / 1e6 if b and n else None
