"""Share (%) of the traced window in which the chip ran no op: 1 - the
union of op intervals over the window."""


def read(record):
    return None if record.trace is None else record.trace.idle_share()
