"""Shared model machinery: param specs (single source of truth for shapes,
logical sharding axes, and init), norms, RoPE, embeddings, losses.

Every module defines a ``spec(cfg) -> {name: ParamSpec | nested dict}``;
``init_params`` materializes arrays (smoke tests / real training) while
``shape_tree`` yields ShapeDtypeStructs (dry-run — no allocation) and
``axes_tree`` yields the logical-axis tuples the sharding resolver consumes.
Keeping all three derived from one spec eliminates drift between init,
sharding, and dry-run paths.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

# Logical axis names (resolved to mesh axes in distributed/sharding.py):
#   embed   - d_model dim of params (FSDP target)
#   vocab   - vocabulary dim (TP)
#   heads   - query-head dim (TP)
#   kv_heads- kv-head dim (TP when divisible, else replicated)
#   mlp     - FFN hidden dim (TP)
#   experts - MoE expert dim (EP)
#   layers  - scan-stacked layer dim (never sharded)
#   qkv/head_dim/state/conv/latent/... - small dims, replicated


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float | None = None  # None -> 1/sqrt(fan_in)

    def __post_init__(self) -> None:
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


SpecTree = dict[str, Any]  # nested dicts of ParamSpec


def _is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init_params(spec: SpecTree, key: jax.Array, dtype: Any = jnp.float32) -> Any:
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        if s.init == "zeros":
            out.append(jnp.zeros(s.shape, dtype))
        elif s.init == "ones":
            out.append(jnp.ones(s.shape, dtype))
        else:
            fan_in = s.shape[0] if len(s.shape) > 1 else max(s.shape[0], 1)
            if s.init == "embed":
                scale = s.scale if s.scale is not None else 1.0
            else:
                scale = s.scale if s.scale is not None else 1.0 / math.sqrt(fan_in)
            out.append(scale * jax.random.normal(k, s.shape, dtype))
    return jax.tree.unflatten(treedef, out)


def shape_tree(spec: SpecTree, dtype: Any = jnp.bfloat16) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), spec, is_leaf=_is_spec
    )


def axes_tree(spec: SpecTree) -> Any:
    return jax.tree.map(lambda s: s.axes, spec, is_leaf=_is_spec)


def stack_specs(spec: SpecTree, n: int) -> SpecTree:
    """Prefix every param with a scan-stacked 'layers' dim."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        spec,
        is_leaf=_is_spec,
    )


def count_params(tree: Any) -> int:
    return sum(
        int(jnp.size(x)) if hasattr(x, "size") else int(jnp.prod(jnp.array(x.shape)))
        for x in jax.tree.leaves(tree)
    )


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 stats but NO materialized f32 copy of x.

    Custom VJP: with the standard autodiff rule, the backward pass promotes
    the (layer-stacked, remat-saved) bf16 residual `x` to f32 inside the
    backward layer scan, and XLA hoists that promotion out of the loop as a
    full fp32 copy of the residual stack (+22 GiB/device observed on a
    36-layer 4k cell). The custom bwd puts an optimization_barrier on the
    per-layer residual slice so the upcast cannot be hoisted stack-wide.
    """
    out, _ = _rmsnorm_fwd(x, w, eps)
    return out


def _rmsnorm_fwd(x: jax.Array, w: jax.Array, eps: float):
    var = (
        jnp.einsum("...d,...d->...", x, x, preferred_element_type=jnp.float32)
        / x.shape[-1]
    )
    inv32 = jax.lax.rsqrt(var + eps)  # (...,) f32 row stats
    out = (x * inv32[..., None].astype(x.dtype)) * w.astype(x.dtype)
    return out, (x, inv32, w)


def _rmsnorm_bwd(eps: float, res, g: jax.Array):
    x, inv32, w = res
    x = jax.lax.optimization_barrier(x)  # pin: no stack-wide f32 hoist
    d = x.shape[-1]
    gw = g.astype(jnp.float32) * w.astype(jnp.float32)  # (..., d)
    s = jnp.sum(gw * x.astype(jnp.float32), axis=-1)  # (...,)
    inv = inv32[..., None]
    dx = (gw * inv - x.astype(jnp.float32) * (inv**3) * (s / d)[..., None]).astype(x.dtype)
    dw_full = g.astype(jnp.float32) * x.astype(jnp.float32) * inv
    dw = jnp.sum(dw_full.reshape(-1, d), axis=0).astype(w.dtype)
    return dx, dw


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Mean token NLL; logits (..., vocab) computed in fp32.

    The gold logit is extracted with an iota-compare-reduce rather than
    ``take_along_axis``: a gather over the vocab axis forces SPMD to
    all-gather the (tokens, vocab) fp32 logits when vocab is TP-sharded
    (tens of GB/device for 150k-vocab models); the masked reduction stays
    local to each vocab shard and fuses into one pass.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    v_idx = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(v_idx == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def embed_lookup(embedding: jax.Array, tokens: jax.Array) -> jax.Array:
    """Token embedding via one-hot matmul when vocab is TP-sharded would
    be wasteful; gather is fine — XLA partitions it over the vocab dim."""
    return jnp.take(embedding, tokens, axis=0)
