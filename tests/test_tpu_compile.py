"""Compile-only checks of the main-path kernels for a described TPU v5e.

Nothing here runs: each test lowers a plan's jitted step at the paper's
L=32 (1,048,576 sites, tile 512) with shapes only, and the installed TPU
compiler either accepts the program or raises what the chip would raise.
That witnesses what interpret mode cannot: Mosaic lowering (lane alignment,
VMEM limits, the dynamic ``fori_loop`` chain) and, on the
described 2x2 mesh, that every sharded kernel sits inside a shard_map.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.  Under pytest-xdist the file is
one unit of work, so one worker describes the chip: ``--dist loadfile``
sends a whole file to one worker, and ``--dist loadgroup`` honours the
``xdist_group`` mark below.  The persistent compilation cache is off around
these compiles (an entry written without a chip cannot be read back).
"""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.xdist_group("tpu_compile")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.su3.layouts import Layout
from repro.core.su3.plan import BatchedLatticeRunner, EngineConfig, build_plan
from repro.launch.mesh import MeshSpec

L = 32
TILE = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # plans pick interpret mode from the local (CPU) backend; these compiles
    # are for the chip, so its Mosaic path is the one lowered
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.kernels.ops._use_interpret", lambda: False)
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.Mesh(np.array(topo.devices[:1]), ("sites",))


@pytest.fixture(scope="module")
def four_chips(topo):
    return MeshSpec(hosts=4, devices_per_host=1).resolve(list(topo.devices))


def _shape(x_shape, dtype, sharding):
    return jax.ShapeDtypeStruct(x_shape, dtype, sharding=sharding)


def _gauge(plan):
    return _shape((2, plan.codec.planar_rows, plan.padded_sites),
                  plan.codec.word_dtype, plan.sharding)


def _b(plan):
    return _shape((2, 36), plan.codec.word_dtype, plan.replicated)


def _vec(plan):
    return _shape((2, 3, plan.padded_sites), plan.codec.word_dtype,
                  plan.vec_sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("dtype,accum,compression", [
    ("float32", "", "none"),
    ("bfloat16", "float32", "none"),
    ("float32", "", "two_row"),
])
def test_multiply_compiles(one_chip, dtype, accum, compression):
    plan = build_plan(EngineConfig(L=L, tile=TILE, dtype=dtype,
                                   accum_dtype=accum, compression=compression),
                      one_chip)
    compiled = plan.step.lower(_gauge(plan), _b(plan)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("k", [4, 16])  # unrolled chain / fori_loop chain
def test_fused_chain_compiles_aliased(one_chip, k):
    plan = build_plan(EngineConfig(L=L, tile=TILE), one_chip)
    compiled = plan.fused_step(k).lower(_gauge(plan), _b(plan)).compile()
    mem = compiled.memory_analysis()
    # the donated lattice is the output buffer: C is written in place
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes > 0


def _served_batch(runner, mesh):
    """Shapes of a two-request physical batch and its links."""
    a = _shape((2, 2, 36, runner.plan.padded_sites), jnp.float32,
               runner.plan.lattice_batch_sharding())
    return a, _shape((2, 2, 36), jnp.float32, NamedSharding(mesh, P()))


def test_served_batch_step_compiles(one_chip):
    runner = BatchedLatticeRunner(EngineConfig(L=L, tile=TILE), one_chip)
    a, b = _served_batch(runner, one_chip)
    assert _kernels(runner._batched_step(1).lower(a, b).compile()) == 1


def test_served_batch_chain_compiles(one_chip):
    """The served cell's deepest chain, k=8, is one kernel: the whole
    unrolled chain runs in a single dispatch."""
    runner = BatchedLatticeRunner(EngineConfig(L=L, tile=TILE), one_chip)
    a, b = _served_batch(runner, one_chip)
    assert _kernels(runner._batched_step(8).lower(a, b).compile()) == 1


def test_stencil_and_fused_cg_compile(one_chip):
    plan = build_plan(EngineConfig(L=L, tile=TILE), one_chip)
    u, v = _gauge(plan), _vec(plan)
    stencil = jax.jit(plan.stencil_step(overlap=False)).lower(u, v).compile()
    assert _kernels(stencil) == 1
    coefs = _shape((1, 2), jnp.float32, plan.replicated)
    cg = jax.jit(plan._cg_apply(fused=True, overlap=False)).lower(
        u, v, v, coefs).compile()
    assert _kernels(cg) == 1


def test_staggered_prepare_and_apply_compile(one_chip):
    """The even/odd staggered operator at L=32: the field preparation (rolls
    and a parity split along the site axis, no gather), one half-lattice
    Dslash (neighbours rolled, one kernel streaming forward and backward
    links) and the CG apply (two of them), within the chip's 16 GB.  Read
    with jax 0.9.0 and libtpu 0.0.34: temp 1,227,559,424 B for the
    preparation (604 MB out), 605,173,248 for a Dslash, 624,595,968 for the
    apply."""
    from repro.core.su3.plan import StaggeredField

    plan = build_plan(EngineConfig(L=L, tile=TILE), one_chip)
    parts, half = plan._ks_parts(), plan.half_sites
    assert half == L**4 // 2
    prepare = parts["prepare"].lower(
        _gauge(plan), _shape(parts["phases"].shape, jnp.float32, plan.replicated)
    ).compile()
    links = _shape((2, 36, half), jnp.float32, plan.vec_sharding)
    v = _shape((2, 3, half), jnp.float32, plan.vec_sharding)
    dslash = parts["dslash"].lower(links, links, v, parity=0).compile()
    assert _kernels(dslash) == 1
    field = StaggeredField(links, links, links, links, mass=0.635)
    coefs = _shape((1, 2), jnp.float32, plan.replicated)
    apply = jax.jit(plan._ks_apply()).lower(field, v, v, coefs).compile()
    assert _kernels(apply) == 2
    for compiled, most in ((prepare, 2.5e9), (dslash, 1.2e9), (apply, 1.2e9)):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes < most


def test_sharded_plan_compiles_on_four_chips(four_chips):
    """Mosaic refuses a pallas_call on a sharded operand outside a
    shard_map; the plan's multiply, overlapped stencil and fused CG must
    all compile over the 2x2 mesh with one kernel per shard."""
    plan = build_plan(EngineConfig(L=L, tile=TILE), four_chips)
    assert plan.n_devices == 4 and plan.is_multi_host
    u, v = _gauge(plan), _vec(plan)
    step = plan.step.lower(u, _b(plan)).compile()
    assert _kernels(step) == 1
    overlapped = jax.jit(plan.stencil_step(overlap=True)).lower(u, v).compile()
    assert _kernels(overlapped) == 2  # interior pass + boundary pass
    coefs = _shape((1, 2), jnp.float32, plan.replicated)
    cg = jax.jit(plan._cg_apply(fused=True, overlap=True)).lower(
        u, v, v, coefs).compile()
    assert _kernels(cg) == 2


def _device_codec(runner, bsz):
    """The served multiply's device pack and unpack at ``bsz`` fields,
    compiled for the runner's mesh."""
    words = (bsz, runner.plan.padded_sites * 72 // 128, 128)
    rep = runner.plan.replicated
    pack = runner._device_pack(words, (bsz, 72)).lower(
        _shape(words, jnp.float32, runner._whole_lattices(bsz, 3)),
        _shape((bsz, 72), jnp.float32, rep)).compile()
    phys = jax.eval_shape(runner._device_pack(words, (bsz, 72)),
                          jax.ShapeDtypeStruct(words, jnp.float32),
                          jax.ShapeDtypeStruct((bsz, 72), jnp.float32))[0]
    unpack = runner._device_unpack(phys.shape).lower(
        _shape(phys.shape, phys.dtype, runner.batch_sharding(bsz))).compile()
    return pack, unpack


def _canonical_shapes(compiled) -> list[str]:
    """Arrays whose minor dimension is the canonical 3 or 4, which a TPU
    pads to 128 lanes."""
    import re

    return re.findall(r"\b(?:f32|bf16|c64)\[[0-9,]*,[34]\]", compiled.as_text())


@pytest.mark.parametrize("dtype,accum,compression", [
    ("float32", "", "none"),
    ("bfloat16", "float32", "none"),
    ("float32", "", "two_row"),
])
def test_device_codec_compiles_lane_dense(one_chip, dtype, accum, compression):
    """At the served cell's L=32 and two fields: one relayout kernel each
    way and no canonical-shaped array on the chip."""
    runner = BatchedLatticeRunner(EngineConfig(
        L=L, tile=TILE, dtype=dtype, accum_dtype=accum,
        compression=compression), one_chip)
    for compiled in _device_codec(runner, 2):
        assert _kernels(compiled) == 1
        assert _canonical_shapes(compiled) == []


@pytest.mark.parametrize("bsz", [4, 2])  # whole lattices per chip / replicated
def test_device_codec_compiles_on_four_chips(four_chips, bsz):
    runner = BatchedLatticeRunner(EngineConfig(L=L, tile=TILE), four_chips)
    for compiled in _device_codec(runner, bsz):
        assert _kernels(compiled) == 1
        assert _canonical_shapes(compiled) == []
