"""Decoder-only LM assembly with scan-over-layers.

Covers the dense / moe / vlm families (GQA or MLA attention, dense or MoE
FFN, optional patch-embedding injection and multi-token-prediction heads).
Layers are parameter-stacked and driven by lax.scan so compile time is O(1)
in depth (88-layer granite-34b compiles the same HLO as a 4-layer smoke).

API (uniform across families via models.registry):
  spec(cfg) / init(key, cfg)            params
  loss_fn(params, batch, cfg)           train forward -> (loss, metrics)
  prefill(params, batch, cfg)           -> (logits, state)
  decode_step(params, batch, state, cfg)-> (logits, state)
  state_spec(cfg, batch, max_len)       decode-state ShapeDtypeStructs
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.act_sharding import shard
from repro.models import attention, common, ffn, mla, moe
from repro.models.common import ParamSpec

# §Perf A2 knob — see _scan_stack. Flip via transformer.CACHE_IN_CARRY.
CACHE_IN_CARRY = False


# ---------------------------------------------------------------------------
# Layer spec/apply
# ---------------------------------------------------------------------------


def _attn_spec(cfg: ModelConfig) -> common.SpecTree:
    return mla.spec(cfg) if cfg.use_mla else attention.spec(cfg)


def layer_spec(cfg: ModelConfig, *, moe_layer: bool) -> common.SpecTree:
    d = cfg.d_model
    s: common.SpecTree = {
        "attn_norm": ParamSpec((d,), ("embed",), init="ones"),
        "attn": _attn_spec(cfg),
        "ffn_norm": ParamSpec((d,), ("embed",), init="ones"),
    }
    if moe_layer:
        s["moe"] = moe.spec(cfg)
    else:
        s["ffn"] = ffn.spec(cfg)
    return s


def layer_apply(
    params: Any,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    moe_layer: bool,
    cache: Any = None,
    cur_len: jax.Array | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> tuple[jax.Array, Any, jax.Array]:
    """Pre-norm block. Returns (x, new_cache, aux_loss)."""
    x = shard(x, "btd")
    h = common.rmsnorm(x, params["attn_norm"], cfg.norm_eps)
    attn_mod = mla if cfg.use_mla else attention
    a, new_cache = attn_mod.apply(
        params["attn"], h, cfg, positions=positions, cache=cache, cur_len=cur_len,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )
    x = shard(x + a, "btd")
    h = common.rmsnorm(x, params["ffn_norm"], cfg.norm_eps)
    if moe_layer:
        f, aux = moe.apply(params["moe"], h, cfg)
    else:
        f = ffn.apply(params["ffn"], h)
        aux = jnp.zeros((), jnp.float32)
    return shard(x + f, "btd"), new_cache, aux


# ---------------------------------------------------------------------------
# Model spec
# ---------------------------------------------------------------------------


def _layer_counts(cfg: ModelConfig) -> tuple[int, int]:
    """(n_dense_scan, n_moe_scan). Non-MoE models: all layers in dense scan."""
    if cfg.is_moe:
        return cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    return cfg.n_layers, 0


def spec(cfg: ModelConfig) -> common.SpecTree:
    d, v = cfg.d_model, cfg.vocab_size
    n_dense, n_moe = _layer_counts(cfg)
    s: common.SpecTree = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), init="embed", scale=0.02),
        "final_norm": ParamSpec((d,), ("embed",), init="ones"),
    }
    if n_dense:
        s["layers"] = common.stack_specs(layer_spec(cfg, moe_layer=False), n_dense)
    if n_moe:
        s["moe_layers"] = common.stack_specs(layer_spec(cfg, moe_layer=True), n_moe)
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((d, v), ("embed", "vocab"), scale=0.02)
    if cfg.mtp_depth:
        s["mtp"] = {
            "proj": ParamSpec((2 * d, d), ("embed", None)),
            "norm_h": ParamSpec((d,), ("embed",), init="ones"),
            "norm_e": ParamSpec((d,), ("embed",), init="ones"),
            "layer": layer_spec(cfg, moe_layer=False),
        }
    return s


def init(key: jax.Array, cfg: ModelConfig, dtype: Any = jnp.float32) -> Any:
    return common.init_params(spec(cfg), key, dtype)


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def _scan_stack(
    stack_params: Any,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    moe_layer: bool,
    caches: Any = None,
    cur_len: jax.Array | None = None,
    remat: bool = False,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> tuple[jax.Array, Any, jax.Array]:
    """Scan x through a stacked-parameter layer stack."""

    if caches is not None and CACHE_IN_CARRY:
        # OPTIONAL serve-path variant (§Perf A2): thread the FULL cache
        # stack through the carry and dynamic-update each layer's slice.
        # Measured: -54% XLA allocation (8.19 -> 3.76 GiB/dev on qwen3
        # decode_32k) because the stacked-ys buffer + its copies vanish;
        # BUT the CPU pipeline then inserts per-ITERATION defensive copies
        # of the carried stack (aliasing analysis fails on read-then-write
        # at a dynamic index), so HLO-level traffic is worse on this host.
        # On TPU the carry+DUS pattern is the production one (MaxText);
        # default stays OFF until validated on hardware.
        def body_c(carry, lp):
            xc, aux_acc, cstack, idx = carry
            lcache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0, keepdims=False),
                cstack,
            )
            y, new_lcache, aux = layer_apply(
                lp, xc, cfg, positions=positions, moe_layer=moe_layer,
                cache=lcache, cur_len=cur_len, q_chunk=q_chunk, kv_chunk=kv_chunk,
            )
            cstack = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), idx, 0
                ),
                cstack, new_lcache,
            )
            return (y, aux_acc + aux, cstack, idx + 1), None

        (x, aux, new_caches, _), _ = jax.lax.scan(
            body_c,
            (x, jnp.zeros((), jnp.float32), caches, jnp.zeros((), jnp.int32)),
            stack_params,
        )
        return x, new_caches, aux

    def body(carry, layer_in):
        xc, aux_acc = carry
        lp, lcache = layer_in
        # Barrier: stops XLA hoisting the f32 upcast of the residual slice
        # out of the backward scan as a full-stack fp32 copy (observed:
        # +22 GiB/device on the qwen3 train cell without it).
        xc = jax.lax.optimization_barrier(xc)
        y, new_cache, aux = layer_apply(
            lp, xc, cfg, positions=positions, moe_layer=moe_layer,
            cache=lcache, cur_len=cur_len, q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        return (y, aux_acc + aux), new_cache

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stack_params, caches)
    )
    return x, new_caches, aux


def _embed_inputs(params: Any, batch: dict[str, jax.Array], cfg: ModelConfig) -> jax.Array:
    x = common.embed_lookup(params["embed"], batch["tokens"]).astype(jnp.dtype(cfg.dtype))
    if cfg.n_patches and "patches" in batch:
        # VLM stub frontend: precomputed patch embeddings replace the first
        # n_patches sequence positions (input_specs provides them).
        p = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([p, x[:, cfg.n_patches :]], axis=1)
    return x


def _logits(params: Any, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = common.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return shard(jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype)), "btv")


def forward(
    params: Any,
    batch: dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    state: Any = None,
    cur_len: jax.Array | None = None,
    remat: bool = False,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> tuple[jax.Array, Any, jax.Array]:
    """Returns (hidden (B,S,d), new_state, aux)."""
    b, s = batch["tokens"].shape
    if cur_len is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    else:
        positions = jnp.broadcast_to(cur_len + jnp.arange(s), (b, s))
    x = shard(_embed_inputs(params, batch, cfg), "btd")
    n_dense, n_moe = _layer_counts(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_state: dict[str, Any] = {}
    if n_dense:
        caches = state["dense"] if state is not None else None
        x, nc, aux = _scan_stack(
            params["layers"], x, cfg, positions=positions, moe_layer=False,
            caches=caches, cur_len=cur_len, remat=remat, q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        aux_total += aux
        new_state["dense"] = nc
    if n_moe:
        caches = state["moe"] if state is not None else None
        x, nc, aux = _scan_stack(
            params["moe_layers"], x, cfg, positions=positions, moe_layer=True,
            caches=caches, cur_len=cur_len, remat=remat, q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        aux_total += aux
        new_state["moe"] = nc
    return x, (new_state if state is not None else None), aux_total


# ---------------------------------------------------------------------------
# Train / serve entry points
# ---------------------------------------------------------------------------


def loss_fn(
    params: Any,
    batch: dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    remat: bool = True,
    aux_weight: float = 0.01,
    mtp_weight: float = 0.3,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    x, _, aux = forward(params, batch, cfg, remat=remat, q_chunk=q_chunk, kv_chunk=kv_chunk)
    logits = _logits(params, x, cfg)
    loss = common.softmax_cross_entropy(logits, batch["labels"])
    metrics = {"nll": loss, "aux": aux}
    total = loss + aux_weight * aux
    if cfg.mtp_depth and "labels2" in batch:
        # DeepSeek-V3 MTP: predict t+2 from h_t and embed(label_t (=token t+1)).
        m = params["mtp"]
        e_next = common.embed_lookup(params["embed"], batch["labels"]).astype(x.dtype)
        h_in = jnp.concatenate(
            [common.rmsnorm(x, m["norm_h"], cfg.norm_eps),
             common.rmsnorm(e_next, m["norm_e"], cfg.norm_eps)],
            axis=-1,
        )
        h_in = jnp.einsum("bse,ed->bsd", h_in, m["proj"].astype(x.dtype))
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h_mtp, _, _ = (
            layer_apply(m["layer"], h_in, cfg, positions=positions, moe_layer=False,
                        q_chunk=q_chunk, kv_chunk=kv_chunk)
        )
        mtp_logits = _logits(params, h_mtp, cfg)
        mtp_loss = common.softmax_cross_entropy(mtp_logits, batch["labels2"])
        metrics["mtp_nll"] = mtp_loss
        total = total + mtp_weight * mtp_loss
    metrics["loss"] = total
    return total, metrics


def state_spec(cfg: ModelConfig, batch: int, max_len: int, dtype: Any = jnp.bfloat16) -> Any:
    n_dense, n_moe = _layer_counts(cfg)
    mod = mla if cfg.use_mla else attention
    out: dict[str, Any] = {}

    def stacked(n: int) -> Any:
        per = mod.cache_spec(cfg, batch, max_len, dtype)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), per
        )

    if n_dense:
        out["dense"] = stacked(n_dense)
    if n_moe:
        out["moe"] = stacked(n_moe)
    return out


def init_state(cfg: ModelConfig, batch: int, max_len: int, dtype: Any = jnp.bfloat16) -> Any:
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), state_spec(cfg, batch, max_len, dtype)
    )


def prefill(
    params: Any, batch: dict[str, jax.Array], state: Any, cfg: ModelConfig,
    *, q_chunk: int = 512, kv_chunk: int = 1024,
) -> tuple[jax.Array, Any]:
    """Prefill writes the cache and returns last-position logits.

    MLA note: prefill uses the decompressed flash path; the latent cache is
    produced by projecting the prefix once (decode then uses absorbed path).
    """
    b, s = batch["tokens"].shape
    if cfg.use_mla:
        # run forward cache-less, then write latent caches per layer via scan
        x, _, _ = forward(params, batch, cfg, q_chunk=q_chunk, kv_chunk=kv_chunk)
        logits = _logits(params, x[:, -1:], cfg)
        new_state = _mla_prefill_cache(params, batch, state, cfg)
        return logits, new_state
    cur = jnp.zeros((), jnp.int32)
    x, new_state, _ = forward(
        params, batch, cfg, state=state, cur_len=cur, q_chunk=q_chunk, kv_chunk=kv_chunk
    )
    logits = _logits(params, x[:, -1:], cfg)
    return logits, new_state


def _mla_prefill_cache(params: Any, batch: dict[str, jax.Array], state: Any, cfg: ModelConfig) -> Any:
    """Recompute per-layer latents to fill the MLA cache (prefill path)."""
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = shard(_embed_inputs(params, batch, cfg), "btd")
    n_dense, n_moe = _layer_counts(cfg)
    new_state = {}
    for key, stack_key, is_moe in (("dense", "layers", False), ("moe", "moe_layers", True)):
        n = n_dense if key == "dense" else n_moe
        if not n:
            continue

        def body(carry, layer_in):
            xc = carry
            lp, lcache = layer_in
            h = common.rmsnorm(xc, lp["attn_norm"], cfg.norm_eps)
            c, k_rope = mla._kv_latent(lp["attn"], h, cfg, positions)
            lcache = {
                "ckv": jax.lax.dynamic_update_slice(
                    lcache["ckv"], c.astype(lcache["ckv"].dtype), (0, 0, 0)
                ),
                "k_rope": jax.lax.dynamic_update_slice(
                    lcache["k_rope"], k_rope.astype(lcache["k_rope"].dtype), (0, 0, 0)
                ),
            }
            y, _, _ = layer_apply(lp, xc, cfg, positions=positions, moe_layer=is_moe)
            return y, lcache

        x, nc = jax.lax.scan(body, x, (params[stack_key], state[key]))
        new_state[key] = nc
    return new_state


def decode_step(
    params: Any,
    batch: dict[str, jax.Array],
    state: Any,
    cur_len: jax.Array,
    cfg: ModelConfig,
) -> tuple[jax.Array, Any]:
    """One-token decode: batch['tokens'] is (B, 1)."""
    x, new_state, _ = forward(params, batch, cfg, state=state, cur_len=cur_len)
    return _logits(params, x, cfg), new_state
