"""``mult_roofline`` (%): the algorithmic floor of one SU3_Bench iteration
(A read and C written once, 576 B/site in float32, B once; 864 flops/site;
the larger bound at the chip's peaks) over the device busy time per
iteration, counting every op the iteration ran."""
from bench import counts


def read(record):
    steps = record.counters.get("steps")
    if not steps or record.trace is None or record.trace.busy_s <= 0:
        return None
    cfg = record.cell.config
    floor = counts.multiply(cfg["L"], cfg["dtype"]).floor_s(record.peaks, cfg["dtype"])
    return 100.0 * floor / (record.trace.busy_s / steps)
