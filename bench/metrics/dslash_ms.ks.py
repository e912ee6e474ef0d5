"""``dslash_ms.ks``: the chip's busy time over the traced window (the union
of its op intervals, from the device trace) per half-lattice Dslash
application the solves dispatched (``CGResult.dslash_applies``), in ms: one
application with its gathers and its share of the CG vector work."""


def read(record):
    applies = sum(record.counters.get("dslash_applies", ()))
    if not applies or record.trace is None or record.trace.busy_s <= 0:
        return None
    return 1e3 * record.trace.busy_s / applies
