"""``device_ms.served``: the chip's busy time over the traced window (the
union of its op intervals, from the device trace) per ``dispatch`` span of
the service in that window, in ms."""


def read(record):
    n = sum(s["name"] == "dispatch" for s in record.counters.get("spans", ()))
    if not n or record.trace is None or record.trace.busy_s <= 0:
        return None
    return 1e3 * record.trace.busy_s / n
