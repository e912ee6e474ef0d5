"""Share (%) of device op time spent outside Pallas kernels (custom calls):
relayout copies, neighbour gathers, pads, axpys and reductions."""


def read(record):
    return None if record.trace is None else record.trace.glue_share()
