"""The served cell's per-layer span metrics: each reader on hand-made spans
(and, for ``device_ms.served``, a hand-made trace), silent where what it
reads is absent (as on a program that records no such span), and all of
them in a traced run of the served cell at L=4 on the CPU."""
import time
from types import SimpleNamespace

import jax
import pytest

from bench import harness

BENCHMARK = harness.load_benchmark()
CELL = "su3bench_l32_f32.served"
READERS = ["stack_ms.served", "pack_ms.served", "unpack_ms.served", "h2d_ms.served",
           "d2h_ms.served", "transfer_mb.served", "device_ms.served"]


def _span(name, dur_s, **attrs):
    return {"name": name, "dur_s": dur_s, "attrs": attrs}


# two dispatches of two requests each
SPANS = (
    [_span("request", 7.0, queue_wait_s=0.001) for _ in range(4)]
    + [_span("dispatch", 6.0) for _ in range(2)]
    + [_span("serve.stack", s) for s in (0.6, 0.8)]
    + [_span("codec.pack", s) for s in (0.1, 0.3)]
    + [_span("transfer.h2d", s, bytes=1_000_000) for s in (3.0, 3.2)]
    + [_span("device.step", s, k=1) for s in (0.01, 0.03)]
    + [_span("transfer.d2h", s, bytes=500_000) for s in (1.0, 1.4)]
    + [_span("codec.unpack", s) for s in (0.5, 0.7)]
)
EXPECTED = {"stack_ms.served": 700.0, "pack_ms.served": 200.0,
            "unpack_ms.served": 600.0, "h2d_ms.served": 3100.0,
            "d2h_ms.served": 1200.0, "transfer_mb.served": 0.75,
            "device_ms.served": 20.0}


# the chip busy 40 ms over the traced window of the two dispatches
TRACE = SimpleNamespace(busy_s=0.04, window_s=12.0)


def _record(spans, trace):
    cell = harness.load_cell(CELL, 1)
    return harness.Record(cell=cell, trace=trace, counters={"spans": spans},
                          peaks=None)


def _read(name, spans, trace=TRACE):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(
        _record(spans, trace))


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_made_spans(name):
    assert _read(name, SPANS) == pytest.approx(EXPECTED[name])


SPAN_OF = {"stack_ms.served": "serve.stack", "pack_ms.served": "codec.pack",
           "unpack_ms.served": "codec.unpack", "h2d_ms.served": "transfer.h2d",
           "d2h_ms.served": "transfer.d2h", "device_ms.served": "dispatch"}


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_its_span(name):
    absent = ({"transfer.h2d", "transfer.d2h"} if name == "transfer_mb.served"
              else {SPAN_OF[name]})
    assert _read(name, [s for s in SPANS if s["name"] not in absent]) is None
    assert _read(name, []) is None


@pytest.mark.parametrize("trace", [None, SimpleNamespace(busy_s=0.0, window_s=12.0)])
def test_device_ms_is_silent_without_device_time(trace):
    assert _read("device_ms.served", SPANS, trace) is None


def test_every_reader_is_in_the_benchmark():
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == (
            "device_trace" if name == "device_ms.served" else "program_span")


def test_traced_served_run_reports_every_span_metric():
    cell = harness.load_cell(CELL, 19)
    cell.config = dict(cell.config, L=4, sites=4**4)
    cell.workload = dict(cell.workload, params=dict(cell.params, k=[1]))
    out = harness.run_cell(cell, 0.2, True, devices=jax.devices(), peaks=None,
                           benchmark=BENCHMARK, started_s=time.perf_counter())
    assert out["correct"], out["checks"]
    spans = [n for n in READERS if n != "device_ms.served"]
    for name in spans + ["dispatch_ms.served", "queue_wait_ms.served"]:
        assert name in out["metrics"], name
    # the CPU has no device plane: the chip's busy time reads where idle does
    assert ("device_ms.served" in out["metrics"]) == ("idle_share.served" in out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # per request: A in and C out, 72 words a site over 256 sites padded to
    # one tile, and B's 72 words in
    sites = -(-4**4 // cell.config["tile"]) * cell.config["tile"]
    assert m["transfer_mb.served"] == pytest.approx((2 * sites * 72 * 4 + 288) / 1e6)
    steps = sum(m[k] for k in ("pack_ms.served", "h2d_ms.served", "d2h_ms.served",
                               "unpack_ms.served"))
    assert steps <= m["dispatch_ms.served"]
