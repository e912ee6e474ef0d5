"""The served multiply's codec on the device.

``BatchedLatticeRunner.multiply`` ships each request's canonical complex64
buffer as its float32 words (``layouts.words_view``, no copy) and relays it
into the plan's physical form on the device, and back.  These tests pin
that this is the host codec's data movement bit for bit: the device pack
equals ``LayoutCodec.pack`` and ``_pack_on_host`` for every layout, word
dtype and compression, the device unpack equals ``LayoutCodec.unpack`` and
``unpack_batch`` (TWO_ROW's rebuilt third row within rounding), and a whole
multiply returns exactly what the host-codec path returns.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.su3 import layouts
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import (
    FETCH_HEAD, BatchedLatticeRunner, EngineConfig, verify_tolerance)
from repro.kernels import su3_relayout
from repro.obs.tracer import Tracer

CPU = jax.devices("cpu")[0]


def _complex(rng, shape):
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _same_bits(x, y) -> bool:
    x, y = np.ascontiguousarray(x), np.ascontiguousarray(y)
    return (x.shape == y.shape and x.dtype == y.dtype
            and np.array_equal(x.view(np.uint8), y.view(np.uint8)))


def _runner(L, layout, dtype, compression, tile):
    # AoS streams through a canonical kernel; the planar layouts through
    # Pallas, which accumulates bf16 storage at f32
    pallas = layout != Layout.AOS
    return BatchedLatticeRunner(EngineConfig(
        L=L, layout=layout, dtype=dtype, compression=compression, tile=tile,
        variant="pallas" if pallas else "version0",
        accum_dtype="float32" if pallas and dtype == "bfloat16" else ""))


# (L, tile): 81 sites padded to 88, whose words are not whole rows of 128
# (the 1-D words); 16 sites padded to 128; 256 sites, at the tile
_SIZES = [(3, 8), (2, 128), (4, 128)]
_CODECS = [(layout, dtype, comp)
           for layout in Layout
           for dtype in ("float32", "bfloat16")
           for comp in ("none", "two_row")
           if not (layout == Layout.AOS and comp == "two_row")]


@pytest.mark.parametrize("L,tile", _SIZES)
@pytest.mark.parametrize("layout,dtype,compression", _CODECS)
def test_device_codec_is_the_host_codec(layout, dtype, compression, L, tile):
    runner = _runner(L, layout, dtype, compression, tile)
    codec, s_pad, n = runner.plan.codec, runner.plan.padded_sites, L**4
    rng = np.random.default_rng(L)
    a, b = _complex(rng, (2, n, 4, 3, 3)), _complex(rng, (2, 4, 3, 3))
    a[0, 1, 2, 0, 1] = -0.0  # a sign bit that arithmetic would lose

    padded = np.concatenate([a, np.zeros((2, s_pad - n, 4, 3, 3), a.dtype)], 1)
    words, links = layouts.words_view(padded), layouts.words_view(b)
    assert words.ndim == (3 if s_pad % 16 == 0 else 2)
    a_phys, b_p = runner._device_pack(words.shape, links.shape)(
        jnp.asarray(words), jnp.asarray(links))
    for i in range(2):
        assert _same_bits(a_phys[i], codec.pack(jnp.asarray(padded[i])))
        assert _same_bits(b_p[i], codec.pack_b(jnp.asarray(b[i])))
    assert _same_bits(a_phys, runner._pack_on_host(a))
    assert _same_bits(b_p, runner.plan.pack_links_on_host(b))

    out = np.asarray(runner._device_unpack(a_phys.shape)(a_phys))
    assert out.shape == (FETCH_HEAD + padded.size * 2,) and out.dtype == np.float32
    assert not out[:FETCH_HEAD].any()
    got = out[FETCH_HEAD:].view(np.complex64).reshape(padded.shape)[:, :n]
    want = np.asarray(runner.unpack_batch(a_phys, n))
    if codec.is_compressed:  # the rebuilt row: same formula, own rounding
        tol = verify_tolerance(dtype, reconstruct=True)
        assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0)
        assert _same_bits(got[:, :, :, :2], want[:, :, :, :2])
    else:
        assert _same_bits(got, want)
        for i in range(2):
            assert _same_bits(got[i], codec.unpack(a_phys[i], n))


@pytest.mark.parametrize("n_sites", [128, 2048, 2048 + 384])
def test_relayout_kernels_move_every_word_bit_for_bit(n_sites):
    rng = np.random.default_rng(n_sites)
    w = rng.standard_normal((2, n_sites, 72)).astype(np.float32)
    w[0, 5, 7], w[1, 9, 70] = -0.0, np.nan
    w.view(np.uint32)[1, 3, 3] = 0x7FC0BEEF  # a NaN with a payload
    flat = w.reshape(2, -1, 128)
    p = su3_relayout.planar_from_flat(jnp.asarray(flat), interpret=True)
    assert _same_bits(p, w.reshape(2, n_sites, 36, 2).transpose(0, 3, 2, 1))
    back = su3_relayout.flat_from_planar(p, interpret=True)
    assert _same_bits(back, flat)


def test_words_view_is_a_view_of_the_request_buffer():
    a = np.zeros((2, 16, 4, 3, 3), np.complex64)
    w = layouts.words_view(a)
    assert w.shape == (2, 9, 128) and np.shares_memory(w, a)
    odd = np.zeros((1, 81, 4, 3, 3), np.complex64)
    assert layouts.words_view(odd).shape == (1, 81 * 72)
    assert np.shares_memory(layouts.words_view(odd), odd)


def _host_codec_multiply(runner, a, b, k):
    """The multiply as the host codec runs it: pack on the host, the same
    batched step, unpack on the host; with the bytes it moves each way."""
    a_phys = jax.device_put(runner._pack_on_host(a), runner.batch_sharding(a.shape[0]))
    b_p = runner.plan.pack_links(b)
    c_phys = jax.device_put(runner.run(a_phys, b_p, k=k), CPU)
    return (runner.unpack_batch(c_phys, a.shape[1]),
            a_phys.nbytes + b_p.nbytes, c_phys.nbytes)


@pytest.fixture(scope="module")
def runners():
    """One runner per L, so its compiled steps serve every batch and k."""
    return {L: BatchedLatticeRunner(EngineConfig(L=L, tile=16)) for L in (2, 3)}


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("bsz", [1, 2, 3])
@pytest.mark.parametrize("L", [2, 3])
def test_multiply_returns_what_the_host_codec_returns(runners, L, bsz, k):
    runner = runners[L]
    rng = np.random.default_rng(10 * L + bsz)
    a, b = _complex(rng, (bsz, L**4, 4, 3, 3)), _complex(rng, (bsz, 4, 3, 3))
    want, h2d, d2h = _host_codec_multiply(runner, a, b, k)

    runner.tracer = tracer = Tracer()
    c = runner.multiply(a, b, k=k)
    assert isinstance(c, jax.Array) and c.devices() == {CPU}
    assert _same_bits(c, want)

    spans = tracer.spans()
    assert [s.name for s in spans] == [
        "transfer.h2d", "codec.pack", "device.step", "codec.unpack", "transfer.d2h"]
    attrs = {s.name: s.attrs for s in spans}
    assert attrs["codec.pack"] == attrs["codec.unpack"] == {"on": "device"}
    assert attrs["device.step"] == {"k": k}
    # the same bytes as the physical form, padding sites included (L=3:
    # 81 sites padded to 96)
    assert attrs["transfer.h2d"]["bytes"] == h2d
    assert attrs["transfer.d2h"]["bytes"] == d2h
