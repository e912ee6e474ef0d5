"""``seat_ms.cg_served``: mean of the service's ``serve.seat`` spans (a
solve request's gauge field and right-hand side packed and placed on the
chip, its CG state initialised), in ms."""


def read(record):
    d = [s["dur_s"] for s in record.counters.get("spans", ()) if s["name"] == "serve.seat"]
    return 1e3 * sum(d) / len(d) if d else None
