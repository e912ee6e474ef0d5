"""The trace reduction on a small synthetic XSpace: busy union, idle gaps
and per-op sums, read through ``jax.profiler.ProfileData`` as the harness
reads a recorded trace."""
import pytest

from bench import trace

US = 1_000_000  # picoseconds per microsecond


def _events(line_events):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {int(s * US)} duration_ps: {int(d * US)} }}"
        for m, s, d in line_events)


def _meta(names):
    return "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())


KERNEL = r'%su3_mult_planar.1 = f32[2,36,64]{2,1,0} custom-call(f32[2,36,64]{2,1,0} %a), custom_call_target=\"tpu_custom_call\"'
COPY = r'%copy.1 = f32[2,36,64]{2,1,0} copy(f32[2,36,64]{2,0,1} %a)'
FUSION = r'%fusion.5 = f32[64,2,3]{0,1,2} fusion(f32[2,3,64]{2,0,1} %r)'


def xspace():
    """Two chips; times in microseconds from 0.

    chip 0 ops: kernel [10, 30], fusion [25, 35] (overlaps), copy [40, 50];
    chip 1 ops: kernel [10, 20].  Host: bench.step [5, 95], bench.pop [60, 90],
    a runtime TransferToDevice [55, 92] and a short Alloc [70, 71]."""
    dev0 = _events([(1, 10, 20), (2, 25, 10), (3, 40, 10)])
    dev1 = _events([(1, 10, 10)])
    host = _events([(7, 5, 90), (8, 60, 30), (9, 55, 37), (10, 70, 1)])
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev0}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 4 offset_ps: 0 duration_ps: {100 * US} }}
  }}
{_meta({1: KERNEL, 2: FUSION, 3: COPY, 4: "jit_step"})}
}}
planes {{ id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev1}
  }}
{_meta({1: KERNEL})}
}}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 5 name: "main" timestamp_ns: 0
{host}
  }}
{_meta({7: "bench.step", 8: "bench.pop", 9: "TransferToDevice", 10: "Alloc"})}
}}
'''


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace.reduce_profile(ProfileData.from_text_proto(xspace()))


def test_window_spans_the_annotations(reduced):
    assert reduced.window_s == pytest.approx(90e-6)


def test_busy_is_the_union_averaged_over_chips(reduced):
    # chip 0: [10, 35] + [40, 50] = 35 us; chip 1: 10 us
    assert reduced.busy_s == pytest.approx((35e-6 + 10e-6) / 2)
    assert reduced.idle_share() == pytest.approx(100 * (1 - 22.5 / 90))


@pytest.mark.parametrize("op, seconds, kind", [
    ("su3_mult_planar.1", 30e-6, "kernel"),
    ("fusion.5", 10e-6, "glue"),
    ("copy.1", 10e-6, "glue"),
])
def test_per_op_sums_and_kinds(reduced, op, seconds, kind):
    assert reduced.op_s[op] == pytest.approx(seconds)
    assert reduced.op_kind[op] == kind


def test_glue_share(reduced):
    assert reduced.glue_share() == pytest.approx(100 * 20 / 50)


def test_idle_gaps_by_host_activity(reduced):
    # chip 0 idle: [5, 10], [35, 40], [50, 95]; the last is mostly covered by
    # the transfer [55, 92], with bench.pop innermost at its midpoint
    assert reduced.gaps == pytest.approx({
        "bench.step": 10e-6,
        "bench.pop / TransferToDevice": 45e-6,
    })
    top = reduced.breakdown()
    assert top["idle_gaps"][0][0] == "bench.pop / TransferToDevice"
    assert top["device_ops"][0] == ["su3_mult_planar.1 (kernel)", pytest.approx(30e-6)]


def test_nothing_traced_reads_nothing():
    empty = trace.reduce_events([], [], [])
    assert empty.busy_s == 0.0
    assert empty.glue_share() is None and empty.idle_share() is None


@pytest.mark.parametrize("text, name, kind", [
    (KERNEL.replace("\\", ""), "su3_mult_planar.1", "kernel"),
    (COPY, "copy.1", "glue"),
    ("%gather.3 = f32[8] gather(f32[64] %v, s32[8] %i)", "gather.3", "glue"),
    ("copy-start.2", "copy-start.2", "glue"),
])
def test_op_names_and_kinds_from_hlo_text(text, name, kind):
    assert trace.op_name(text) == name
    assert trace.op_kind(text) == kind


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
