"""Pallas TPU flash-attention kernel (prefill/train hot spot).

Blockwise online-softmax attention with explicit BlockSpec VMEM tiling —
the LM-side analog of the SU3 kernel's HBM->VMEM blocking. Grid is
(batch*kv_heads, q_blocks); the kv loop runs inside the kernel body with
jax.lax.fori_loop over VMEM-resident K/V blocks of the same head.

Layout contract (one GQA group per grid row):
  q: (B*Hkv, G*Sq, D)   — G query-heads-per-kv-head folded into rows
  k: (B*Hkv, Skv, D)
  v: (B*Hkv, Skv, D)
  -> out (B*Hkv, G*Sq, D)

This kernel targets TPU (MXU matmuls over (block_q, D) x (D, block_k));
on CPU it runs under interpret=True for correctness tests. The model stack
uses the pure-JAX chunked path for AOT dry-runs (Pallas does not lower
through the CPU pipeline) and selects this kernel on TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sq: int, g: int,
                  causal: bool, scale: float):
    """One (batch-head, q-block) grid step."""
    q = q_ref[0].astype(jnp.float32) * scale  # (block_qg, d)
    block_qg, d = q.shape
    skv = k_ref.shape[1]
    nk = skv // block_k
    # absolute q positions: row r of this block maps to query index
    # (block_index * block_qg + r) // g   (G heads folded into rows)
    iq = pl.program_id(1)
    q_pos = (iq * block_qg + jax.lax.iota(jnp.int32, block_qg)) // g

    def body(ik, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(ik * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ik * block_k, block_k), :]
        s = q @ k_blk.astype(jnp.float32).T  # (block_qg, block_k) on the MXU
        if causal:
            k_pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v_blk.astype(jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_qg, d), jnp.float32)
    m0 = jnp.full((block_qg,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_qg,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l[:, None], 1e-37)).astype(o_ref.dtype)[None]


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_tpu(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = d**-0.5
    assert skv % block_k == 0, (skv, block_k)
    # fold: (B, Sq, Hkv, G, D) -> (B*Hkv, Sq*G rows, D) with q-major rows
    qf = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(b * hkv, sq * g, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    block_qg = min(block_q * g, sq * g)
    assert (sq * g) % block_qg == 0
    grid = (b * hkv, sq * g // block_qg)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, sq=sq, g=g, causal=causal, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_qg, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, skv, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_qg, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, sq * g, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return (
        out.reshape(b, hkv, sq, g, d).transpose(0, 2, 1, 3, 4).reshape(b, sq, hq, d)
    )


def vmem_bytes(block_q: int, block_k: int, skv: int, d: int, g: int = 1) -> int:
    """Working set per grid step: q/o blocks + the full K/V rows (streamed
    block_k at a time by the fori_loop, but resident per BlockSpec)."""
    return 4 * (block_q * g * d * 2 + 2 * skv * d * 2)
