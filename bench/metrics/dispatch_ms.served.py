"""``dispatch_ms.served``: mean of the service's ``dispatch`` spans (host
stack, pack, host-to-device copy, kernel, copy back, unpack), in ms."""


def read(record):
    d = [s["dur_s"] for s in record.counters.get("spans", ()) if s["name"] == "dispatch"]
    return 1e3 * sum(d) / len(d) if d else None
