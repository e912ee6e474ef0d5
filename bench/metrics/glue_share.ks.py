"""Share (%) of device op time spent outside Pallas kernels (custom calls):
the staggered solve's neighbour gathers, axpys and reductions."""


def read(record):
    return None if record.trace is None else record.trace.glue_share()
