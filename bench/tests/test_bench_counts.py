"""The yardstick's counts and peaks, pinned."""
import pathlib

import pytest

from bench import counts, peaks

BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("value, expected", [
    (counts.MULTIPLY_FLOPS_PER_SITE, 864),  # the paper's figure
    (counts.MULTIPLY_WORDS_PER_SITE * 4, 576),  # A read + C written, float32
    (counts.CG_WORDS_PER_SITE, 132),  # two passes per CG iteration
    (counts.CG_WORDS_PER_SITE * 4, 528),
    (counts.CG_FLOPS_PER_SITE, 648),
])
def test_per_site_counts(value, expected):
    assert value == expected


@pytest.mark.parametrize("work, flops, nbytes", [
    (counts.multiply(32), 864 * 32**4, 576 * 32**4 + 288),
    (counts.multiply(32, "bfloat16"), 864 * 32**4, 288 * 32**4 + 144),
    (counts.cg_iteration(32), 648 * 32**4, 528 * 32**4),
    (counts.multiply(4), 864 * 256, 576 * 256 + 288),
])
def test_work_from_shapes(work, flops, nbytes):
    assert work.flops == flops
    assert work.bytes == nbytes


@pytest.mark.parametrize("work, floor_s", [
    (counts.multiply(32), (576 * 32**4 + 288) / 819e9),  # HBM-bound
    (counts.cg_iteration(32), 528 * 32**4 / 819e9),
])
def test_floors_are_bandwidth_bound_on_v5e(work, floor_s):
    assert work.floor_s(peaks.TPU_V5E, "float32") == pytest.approx(floor_s)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    p = peaks.for_device_kind(kind)
    assert (p.hbm_bytes_per_s, p.bf16_flops, p.hbm_bytes) == (819e9, 197e12, 16e9)
    assert p.modelled == ("f32_flops",)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no peaks"):
        peaks.for_device_kind(kind)


@pytest.mark.parametrize("name", ["peaks.py", "counts.py", "data.py", "trace.py",
                                  "references/su3_multiply.py",
                                  "references/shifted_stencil_cg.py"])
def test_yardstick_imports_nothing_of_the_program(name):
    assert "repro" not in (BENCH / name).read_text()
