"""Roofline math + HLO cost model unit tests (synthetic HLO text)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_costs, roofline

SYNTH_HLO = """
HloModule synth

%body (p: (s32[], f32[128,128])) -> (s32[], f32[128,128]) {
  %p = (s32[], f32[128,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant({...})
  %d = f32[128,128]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[128,128]{1,0} all-reduce(%d), replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %t = (s32[], f32[128,128]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[128,128])) -> pred[] {
  %p = (s32[], f32[128,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[128,128]) -> f32[128,128] {
  %a = f32[128,128]{1,0} parameter(0)
  %i0 = s32[] constant(0)
  %t0 = (s32[], f32[128,128]) tuple(%i0, %a)
  %w = (s32[], f32[128,128]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  %ag = f32[256,128]{1,0} all-gather(%a), replica_groups=[1,2]<=[2], dimensions={0}
  ROOT %out = f32[128,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_synthetic_hlo_costs():
    cost = hlo_costs.analyze_hlo(SYNTH_HLO)
    # dot: 2*128*128*128 = 4.19e6 flops x 5 trips
    assert cost.flops == pytest.approx(5 * 2 * 128**3, rel=0.05)
    # all-reduce in loop: 2*(3/4)*65536 bytes x 5; all-gather: (1/2)*131072
    ar = 5 * 2 * (3 / 4) * 128 * 128 * 4
    ag = (1 / 2) * 256 * 128 * 4
    assert cost.collective_link_bytes == pytest.approx(ar + ag, rel=0.01)
    assert cost.collective_by_kind["all-reduce"] == pytest.approx(ar, rel=0.01)
    assert cost.collective_by_kind["all-gather"] == pytest.approx(ag, rel=0.01)


def test_tuple_shape_with_index_comments():
    txt = """
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %t = (f32[4]{0}, /*index=1*/f32[8,2]{1,0}) tuple(%a, %a)
  ROOT %o = f32[4]{0} add(%a, %a)
}
"""
    comps, entry, _ = hlo_costs.parse_computations(txt)
    assert "t" in comps[entry].instructions  # the /*index=1*/ comment parses


def test_real_scan_trip_count_accounting():
    """cost_analysis counts while bodies once; our model multiplies them."""
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, jnp.zeros((8, 64)), None, length=10)
        return out

    compiled = jax.jit(f).lower(w).compile()
    ours = hlo_costs.analyze_hlo(compiled.as_text())
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax returns [per-device dict]
        ca = ca[0] if ca else {}
    theirs = float(ca.get("flops", 0.0))
    expected_dots = 10 * 2 * 8 * 64 * 64
    assert ours.flops >= expected_dots * 0.95
    assert theirs < expected_dots * 0.5  # XLA undercounts -> why we parse


# -- roofline report math ----------------------------------------------------


def test_collective_ring_models():
    mk = lambda kind, b, n: roofline.CollectiveOp(kind, b, n)
    assert mk("all-reduce", 100, 4).link_bytes == pytest.approx(2 * 3 / 4 * 100)
    assert mk("all-gather", 100, 4).link_bytes == pytest.approx(3 / 4 * 100)
    assert mk("reduce-scatter", 25, 4).link_bytes == pytest.approx(3 * 25)
    assert mk("collective-permute", 100, 2).link_bytes == 100
    assert mk("all-reduce", 100, 1).link_bytes == 0.0


def test_report_dominance_and_fraction():
    r = roofline.RooflineReport(
        name="t", hw=roofline.TPU_V5E, n_chips=4,
        flops_per_device=197e12,  # exactly 1s compute
        bytes_per_device=819e9 * 2,  # 2s memory
        collective_link_bytes=50e9 * 0.5,  # 0.5s collective
        collective_by_kind={}, model_flops=4 * 197e12,
    )
    assert r.dominant == "memory"
    assert r.bound_s == pytest.approx(2.0)
    assert r.compute_s == pytest.approx(1.0)
    assert r.useful_flops_ratio == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(0.5)


def test_analytic_su3_report_is_bandwidth_bound():
    rep = roofline.analytic_su3_report(
        n_sites=32**4, word_bytes=4, bytes_per_site_rw=576, n_chips=1
    )
    assert rep.dominant == "memory"
    # AI=1.5 on SoA; VPU ridge = 1.9e12/819e9 = 2.3 flop/byte -> memory-bound
    assert rep.memory_s > rep.compute_s


def test_instruction_mix_counted_loop_aware():
    cost = hlo_costs.analyze_hlo(SYNTH_HLO)
    # body (x5 trips): dot + cond's compare -> 10 arith; all-reduce x5 plus
    # the entry all-gather -> 6 collective; the while op itself -> 1 control
    assert cost.instr_by_class["arith"] == pytest.approx(10)
    assert cost.instr_by_class["collective"] == pytest.approx(6)
    assert cost.instr_by_class["control"] == pytest.approx(1)
    assert cost.instructions == pytest.approx(
        sum(cost.instr_by_class.values())
    )


def test_issue_term_reproduces_piuma_pipeline_bound():
    """Paper §5.3: 12 loads + 2 stores + 12 FMAs per 24 flops — SU3 on PIUMA
    is bounded by the ISSUE rate (3.6 GF/s), below both the 8 GF/s FMA roof
    and the 4.32 GF/s bandwidth bound.  The three-term report must reproduce
    that: issue dominant, effective throughput ~3.6 GF/s."""
    n = 10_000  # sites
    rep = roofline.RooflineReport(
        name="piuma_su3", hw=roofline.PIUMA_CORE, n_chips=1,
        flops_per_device=24.0 * n,
        bytes_per_device=24.0 / 0.675 * n,  # AI = 0.675 (fp64)
        collective_link_bytes=0.0, collective_by_kind={},
        instructions_per_device=26.0 * n,
    )
    assert rep.issue_s > 0
    assert rep.dominant == "issue"
    assert rep.flops_per_device / rep.bound_s == pytest.approx(3.6e9, rel=0.02)


def test_issue_term_absent_without_instruction_counts():
    r = roofline.RooflineReport(
        name="t", hw=roofline.TPU_V5E, n_chips=1,
        flops_per_device=1e12, bytes_per_device=819e9,
        collective_link_bytes=0.0, collective_by_kind={},
    )
    assert r.issue_s == 0.0  # unmeasured -> two/three-term users unaffected
    assert r.dominant == "memory"


def test_xeon_piuma_models_match_paper():
    """Paper §4/§5.3 platform models. (The paper states 17.1 = 2420.1/105.0,
    which is arithmetically 23.05 — we keep the stated inputs, so our ridge
    is 23.05; the discrepancy is the paper's, noted in EXPERIMENTS.md.)"""
    assert roofline.XEON_8280_SOCKET.ridge_flops_per_byte == pytest.approx(
        2420.1 / 105.0, rel=0.01
    )
    assert roofline.PIUMA_CORE.ridge_flops_per_byte < 3.0
    # PIUMA compute-bound 8 GF/s FMA; bandwidth-bound 4.32 GF/s at AI=0.675
    assert roofline.PIUMA_CORE.hbm_bw * 0.675 == pytest.approx(4.32e9, rel=0.01)


def test_peaks_are_keyed_by_device_kind():
    assert roofline.hardware_for_device_kind("TPU v5 lite") is roofline.TPU_V5E
    assert roofline.TPU_V5E.hbm_bw == 819e9 and roofline.TPU_V5E.peak_flops == 197e12
    # CPU rehearsals rank against the v5e model; an unrecorded kind raises
    cpu_kind = jax.devices()[0].device_kind
    assert roofline.hardware_for_device_kind(cpu_kind) is roofline.TPU_V5E
    for unrecorded in ("TPU v9", "unknown"):
        with pytest.raises(ValueError, match="no peaks recorded"):
            roofline.hardware_for_device_kind(unrecorded)


def test_compile_cache_dir_rule(monkeypatch):
    import pathlib

    from repro import compile_cache

    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == saved[
            "jax_compilation_cache_dir"]  # JAX reads the env itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = pathlib.Path(compile_cache.enable_compile_cache())
        assert path.name == ".jax_cache"
        assert (path.parent / "src" / "repro" / "compile_cache.py").is_file()
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:  # nothing this test process compiles lands in the checkout
        for name, value in saved.items():
            jax.config.update(name, value)
