"""Attention variants: GQA (with qk-norm, RoPE), MLA (DeepSeek latent
attention with weight absorption at decode), and sliding-window GQA with a
ring KV cache (the long-context variant for dense architectures,
DESIGN.md §4).

Each variant provides:
  init(factory, cfg)                          — parameters + specs
  forward(params, cfg, x, positions)          — full-sequence (train/prefill)
  decode(params, cfg, x, cache, pos, live)    — one token against a KV cache
  init_cache / cache_specs                    — cache pytree + shardings

Decode is *ragged*: ``pos`` is a per-request ``(B,)`` vector of positions
(continuous batching serves requests at different offsets in one batch) and
``live`` masks cache writes so idle/padding slots never touch the cache.
GQA additionally provides a *paged* decode (``gqa_decode_paged`` /
``gqa_init_paged_cache``) over a shared page pool — the serving engine's
production KV layout, consumed by ``repro.kernels.paged_attention``.

Caches carry no layer axis here; the transformer stacks them for scan.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import kernels_bridge
from repro.models.common import ParamFactory, apply_rope, causal_mask, rmsnorm
from repro.models.config import ModelConfig

Params = Dict[str, Any]


# =============================================================================
# GQA
# =============================================================================


def gqa_init(f: ParamFactory, cfg: ModelConfig) -> None:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    f.add("wq", (d, H * hd), (None, "model"))
    f.add("wk", (d, KV * hd), (None, "model"))
    f.add("wv", (d, KV * hd), (None, "model"))
    f.add("wo", (H * hd, d), ("model", None))
    if cfg.qk_norm:
        f.add("q_norm", (hd,), (None,), init="ones")
        f.add("k_norm", (hd,), (None,), init="ones")


def _gqa_qkv(
    p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    use_kernels: bool = False,
    kv_hint: Optional[P] = None,
) -> jax.Array:
    """Full-sequence causal attention (train / prefill).

    ``kv_hint`` (§Perf): a PartitionSpec applied to k/v once, above the
    blocked-attention tile loop — without it the SPMD partitioner may
    re-gather k/v on every query tile when kv_heads < model-axis size."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    if kv_hint is not None:
        k = jax.lax.with_sharding_constraint(k, kv_hint)
        v = jax.lax.with_sharding_constraint(v, kv_hint)
    window = cfg.sliding_window
    o = kernels_bridge.causal_attention(
        q, k, v, window=window, use_kernels=use_kernels
    )
    return o.reshape(B, S, H * hd) @ p["wo"]


def gqa_prefill(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    use_kernels: bool = False,
    kv_hint: Optional[P] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention that also emits the decode cache."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    if kv_hint is not None:
        k = jax.lax.with_sharding_constraint(k, kv_hint)
        v = jax.lax.with_sharding_constraint(v, kv_hint)
    o = kernels_bridge.causal_attention(
        q, k, v, window=cfg.sliding_window, use_kernels=use_kernels
    )
    out = o.reshape(B, S, H * hd) @ p["wo"]
    if cfg.sliding_window and cfg.sliding_window < S:
        W = cfg.sliding_window
        # contract-ok: no-bare-assert trace-time shape precondition inside jit
        assert S % W == 0, "prefill length must align with the ring window"
        cache = {
            "k": k[:, S - W :],
            "v": v[:, S - W :],
            "slot_pos": jnp.broadcast_to(
                jnp.arange(S - W, S, dtype=jnp.int32), (B, W)
            ),
        }
    else:
        cache = {"k": k, "v": v}
    return out, cache


def gqa_init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype: Any
) -> Dict[str, jax.Array]:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.sliding_window and cfg.sliding_window < max_len:
        W = cfg.sliding_window
        return {
            "k": jnp.zeros((batch, W, KV, hd), dtype),
            "v": jnp.zeros((batch, W, KV, hd), dtype),
            "slot_pos": jnp.full((batch, W), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, KV, hd), dtype),
        "v": jnp.zeros((batch, max_len, KV, hd), dtype),
    }


def gqa_cache_specs(cfg: ModelConfig, dp: Tuple[str, ...], seq_axis: Optional[str]):
    spec = P(dp, seq_axis, None, None)
    out = {"k": spec, "v": spec}
    if cfg.sliding_window:
        out["slot_pos"] = P(dp, None)
    return out


def normalize_pos(pos: jax.Array, batch: int) -> Tuple[jax.Array, jax.Array]:
    """Broadcast a scalar-or-(B,) position to ``(B,)`` and derive liveness.

    Negative positions mark idle/padding slots: their logits are still
    computed (the batch shape is static) but their cache writes are masked.
    Returns ``(clamped_pos (B,), live (B,) bool)``."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))
    return jnp.maximum(pos, 0), pos >= 0


def _masked_row_update(
    cache: jax.Array,  # (B, S, ...)
    new: jax.Array,  # (B, 1, ...)
    idx: jax.Array,  # (B,) int32 — row to write, per batch element
    live: jax.Array,  # (B,) bool — rows of dead slots stay untouched
) -> jax.Array:
    upd = jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
    )(cache, new, idx)
    mask = live.reshape((-1,) + (1,) * (cache.ndim - 1))
    return jnp.where(mask, upd, cache)


def gqa_decode(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, d)
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # (B,) int32 per-slot position of the new token (or scalar)
    live: Optional[jax.Array] = None,  # (B,) bool; None => all live
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cpos, derived_live = normalize_pos(pos, B)
    live = derived_live if live is None else live
    q, k_new, v_new = _gqa_qkv(p, cfg, x, cpos[:, None])
    if "slot_pos" in cache:  # ring buffer (sliding window), slot_pos (B, W)
        W = cache["k"].shape[1]
        slot = cpos % W
        k = _masked_row_update(cache["k"], k_new, slot, live)
        v = _masked_row_update(cache["v"], v_new, slot, live)
        onehot = jnp.arange(W)[None, :] == slot[:, None]
        slot_pos = jnp.where(
            onehot & live[:, None], cpos[:, None], cache["slot_pos"]
        )
        valid = (
            (slot_pos >= 0)
            & (slot_pos > cpos[:, None] - W)
            & (slot_pos <= cpos[:, None])
        )  # (B, W)
        new_cache = {"k": k, "v": v, "slot_pos": slot_pos}
    else:
        k = _masked_row_update(cache["k"], k_new, cpos, live)
        v = _masked_row_update(cache["v"], v_new, cpos, live)
        S = k.shape[1]
        valid = jnp.arange(S)[None, :] <= cpos[:, None]  # (B, S)
        new_cache = {"k": k, "v": v}
    o = kernels_bridge.decode_attention(q, k, v, valid)
    return o.reshape(B, 1, H * hd) @ p["wo"], new_cache


# -- paged KV (shared page pool; the serving engine's production layout) ------


def gqa_init_paged_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype: Any
) -> Dict[str, jax.Array]:
    """Per-layer page pools.  One logical page id addresses a slab across all
    layers (the transformer stacks these along the scan axis), so a single
    host-side :class:`~repro.serving.paged_cache.PagePool` table drives every
    layer's kernel."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "pool_k": jnp.zeros((num_pages, page_size, KV, hd), dtype),
        "pool_v": jnp.zeros((num_pages, page_size, KV, hd), dtype),
    }


def gqa_decode_paged(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, d)
    cache: Dict[str, jax.Array],  # {"pool_k","pool_v"} (P, ps, KV, hd)
    page_tables: jax.Array,  # (B, max_pages) int32
    pos: jax.Array,  # (B,) int32 per-slot position of the new token
    live: jax.Array,  # (B,) bool
    use_kernels: bool = False,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Ragged decode against the paged pool: the new token's k/v is scattered
    into its slot's current page (idle slots are routed to an out-of-bounds
    page id, so jax drops their write), then attention runs over the pages —
    the Pallas paged kernel when ``use_kernels``, a gather + flat-decode
    reference otherwise.

    With ``layer`` (an int32 scalar), the pools are the whole layer stack
    ``(L, P, ps, KV, hd)`` and only that layer's pages are written and read:
    a layer scan carries the stacked pools and updates them in place rather
    than slicing out and re-stacking a copy per layer."""
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cpos, _ = normalize_pos(pos, B)
    q, k_new, v_new = _gqa_qkv(p, cfg, x, cpos[:, None])
    pool_k, pool_v = cache["pool_k"], cache["pool_v"]
    stacked = layer is not None
    if not stacked:
        pool_k, pool_v, layer = pool_k[None], pool_v[None], jnp.int32(0)
    num_pages, ps = pool_k.shape[1], pool_k.shape[2]
    page = page_tables[jnp.arange(B), cpos // ps]
    page = jnp.where(live, page, num_pages)  # OOB => scatter dropped
    off = cpos % ps
    pool_k = pool_k.at[layer, page, off].set(k_new[:, 0], mode="drop")
    pool_v = pool_v.at[layer, page, off].set(v_new[:, 0], mode="drop")
    lengths = jnp.where(live, cpos + 1, 0)
    if use_kernels:
        from repro.kernels import ops  # lazy: kernels are optional at import

        o = ops.paged_decode_attention(
            q, pool_k, pool_v, page_tables, lengths, layer
        )
    else:
        S = page_tables.shape[1] * ps
        k = pool_k[layer, page_tables].reshape(B, S, KV, hd)
        v = pool_v[layer, page_tables].reshape(B, S, KV, hd)
        valid = jnp.arange(S)[None, :] < lengths[:, None]
        o = kernels_bridge.decode_attention(q, k, v, valid)
    if not stacked:
        pool_k, pool_v = pool_k[0], pool_v[0]
    new_cache = {"pool_k": pool_k, "pool_v": pool_v}
    return o.reshape(B, 1, H * hd) @ p["wo"], new_cache


# =============================================================================
# MLA — DeepSeek multi-head latent attention
# =============================================================================


def mla_init(f: ParamFactory, cfg: ModelConfig) -> None:
    d, H = cfg.d_model, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if qr:
        f.add("w_dq", (d, qr), (None, None))
        f.add("q_norm", (qr,), (None,), init="ones")
        f.add("w_uq", (qr, H * (nd + rd)), (None, "model"))
    else:
        f.add("w_uq", (d, H * (nd + rd)), (None, "model"))
    f.add("w_dkv", (d, r + rd), (None, None))
    f.add("kv_norm", (r,), (None,), init="ones")
    f.add("w_uk", (r, H * nd), (None, "model"))
    f.add("w_uv", (r, H * vd), (None, "model"))
    f.add("wo", (H * vd, d), ("model", None))


def _mla_q(p: Params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nd, rd = cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    else:
        cq = x
    q = (cq @ p["w_uq"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p: Params, cfg: ModelConfig, x, positions):
    """Compressed KV: c_kv (B,S,r) and the shared rotary key (B,S,rd)."""
    r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
    dkv = x @ p["w_dkv"]  # (B,S,r+rd)
    ckv = rmsnorm(dkv[..., :r], p["kv_norm"], cfg.norm_eps)
    krope = apply_rope(dkv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return ckv, krope


def mla_forward(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    use_kernels: bool = False,
    kv_hint: Optional[P] = None,
) -> jax.Array:
    """Prefill/train path: expand the latent into per-head K/V."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, krope = _mla_latent(p, cfg, x, positions)
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, nd)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, rd))], axis=-1)
    if kv_hint is not None:
        k = jax.lax.with_sharding_constraint(k, kv_hint)
        v = jax.lax.with_sharding_constraint(v, kv_hint)
    o = kernels_bridge.causal_attention(
        q, k, v, window=cfg.sliding_window, use_kernels=use_kernels,
        scale=1.0 / math.sqrt(nd + rd),
    )
    return o.reshape(B, S, H * vd) @ p["wo"]


def mla_prefill(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    use_kernels: bool = False,
    kv_hint: Optional[P] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill that also emits the latent decode cache (c_kv + rotary key)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, krope = _mla_latent(p, cfg, x, positions)
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, nd)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, rd))], axis=-1
    )
    if kv_hint is not None:
        k = jax.lax.with_sharding_constraint(k, kv_hint)
        v = jax.lax.with_sharding_constraint(v, kv_hint)
    o = kernels_bridge.causal_attention(
        q, k, v, window=cfg.sliding_window, use_kernels=use_kernels,
        scale=1.0 / math.sqrt(nd + rd),
    )
    out = o.reshape(B, S, H * vd) @ p["wo"]
    if cfg.sliding_window and cfg.sliding_window < S:
        W = cfg.sliding_window
        # contract-ok: no-bare-assert trace-time shape precondition inside jit
        assert S % W == 0
        cache = {
            "ckv": ckv[:, S - W :],
            "krope": krope[:, S - W :],
            "slot_pos": jnp.broadcast_to(
                jnp.arange(S - W, S, dtype=jnp.int32), (B, W)
            ),
        }
    else:
        cache = {"ckv": ckv, "krope": krope}
    return out, cache


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype: Any):
    r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
    if cfg.sliding_window and cfg.sliding_window < max_len:
        W = cfg.sliding_window
        return {
            "ckv": jnp.zeros((batch, W, r), dtype),
            "krope": jnp.zeros((batch, W, rd), dtype),
            "slot_pos": jnp.full((batch, W), -1, jnp.int32),
        }
    return {
        "ckv": jnp.zeros((batch, max_len, r), dtype),
        "krope": jnp.zeros((batch, max_len, rd), dtype),
    }


def mla_cache_specs(cfg: ModelConfig, dp: Tuple[str, ...], seq_axis: Optional[str]):
    out = {"ckv": P(dp, seq_axis, None), "krope": P(dp, seq_axis, None)}
    if cfg.sliding_window:
        out["slot_pos"] = P(dp, None)
    return out


def mla_decode(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # (B,) int32 per-slot position of the new token (or scalar)
    live: Optional[jax.Array] = None,  # (B,) bool; None => all live
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Weight-absorbed decode: score and read directly in the latent space —
    the cache stays (B, S, r + rd) instead of (B, S, H, nd + vd)."""
    B = x.shape[0]
    H = cfg.num_heads
    nd, rd, vd, r = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    cpos, derived_live = normalize_pos(pos, B)
    live = derived_live if live is None else live
    positions = cpos[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)  # (B,1,H,nd),(B,1,H,rd)
    ckv_new, krope_new = _mla_latent(p, cfg, x, positions)

    if "slot_pos" in cache:  # ring buffer, slot_pos (B, W)
        W = cache["ckv"].shape[1]
        slot = cpos % W
        ckv = _masked_row_update(cache["ckv"], ckv_new, slot, live)
        krope = _masked_row_update(cache["krope"], krope_new, slot, live)
        onehot = jnp.arange(W)[None, :] == slot[:, None]
        slot_pos = jnp.where(
            onehot & live[:, None], cpos[:, None], cache["slot_pos"]
        )
        valid = (
            (slot_pos >= 0)
            & (slot_pos > cpos[:, None] - W)
            & (slot_pos <= cpos[:, None])
        )  # (B, W)
        new_cache = {"ckv": ckv, "krope": krope, "slot_pos": slot_pos}
    else:
        ckv = _masked_row_update(cache["ckv"], ckv_new, cpos, live)
        krope = _masked_row_update(cache["krope"], krope_new, cpos, live)
        valid = jnp.arange(ckv.shape[1])[None, :] <= cpos[:, None]  # (B, S)
        new_cache = {"ckv": ckv, "krope": krope}

    # absorb W_uk into the query: q_abs (B,1,H,r)
    w_uk = p["w_uk"].reshape(r, H, nd)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scores = jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv) + jnp.einsum(
        "bqhd,bsd->bhqs", q_rope, krope
    )
    scores = scores.astype(jnp.float32) / math.sqrt(nd + rd)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    o_latent = jnp.einsum("bhqs,bsr->bqhr", probs, ckv)  # (B,1,H,r)
    w_uv = p["w_uv"].reshape(r, H, vd)
    o = jnp.einsum("bqhr,rhv->bqhv", o_latent, w_uv)
    return o.reshape(B, 1, H * vd) @ p["wo"], new_cache
