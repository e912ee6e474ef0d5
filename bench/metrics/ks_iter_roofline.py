"""``ks_iter_roofline`` (%): the floor of one even/odd staggered CG iteration
(183 words per lattice site: every link read once per half-lattice
application, each vector once per pass; see ``bench/counts_staggered.py``)
over the device busy time per iteration, counting every op the solves ran
(per-solve set-up and the lagged check's extra iteration included)."""
from bench import counts_staggered


def read(record):
    iterations = sum(record.counters.get("iterations", ()))
    if not iterations or record.trace is None or record.trace.busy_s <= 0:
        return None
    cfg = record.cell.config
    floor = counts_staggered.iteration(cfg["L"], cfg["dtype"]).floor_s(
        record.peaks, cfg["dtype"])
    return 100.0 * floor / (record.trace.busy_s / iterations)
