"""``ks_iterations``: even/odd staggered CG iterations per solve to the
configuration's tolerance (``CGResult.iterations``), the mean over the
traced solves."""


def read(record):
    its = record.counters.get("iterations")
    return sum(its) / len(its) if its else None
