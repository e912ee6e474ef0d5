"""``cg_iter_roofline`` (%): the floor of one CG iteration (132 words/site:
U read once and the two passes the two global reductions force; see
``bench/counts.py``) over the device busy time per iteration, counting every
op the solves ran (per-solve set-up included)."""
from bench import counts


def read(record):
    iterations = sum(record.counters.get("iterations", ()))
    if not iterations or record.trace is None or record.trace.busy_s <= 0:
        return None
    cfg = record.cell.config
    floor = counts.cg_iteration(cfg["L"], cfg["dtype"]).floor_s(record.peaks, cfg["dtype"])
    return 100.0 * floor / (record.trace.busy_s / iterations)
