"""Bridge between model code and the attention compute layer.

Models call :func:`causal_attention` / :func:`decode_attention`; the bridge
routes to the Pallas TPU kernels (``repro.kernels.ops``) when
``use_kernels=True`` (real TPU, or interpret mode in kernel tests) and to a
pure-jnp implementation otherwise.  The jnp prefill path is *blocked* over
query tiles (lax.scan) so its HLO memory profile resembles the flash kernel
rather than materialising the full S×S score matrix.

GQA grouping (H = KV·G) is handled here so both backends see the same
contract.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# Sequence tile of the flash prefill kernel: a prefill whose length is a
# multiple of it runs in the kernel, any other length on the jnp path.
FLASH_TILE = 128


def _grouped(q: jax.Array, kv_heads: int):
    B, S, H, hd = q.shape
    G = H // kv_heads
    return q.reshape(B, S, kv_heads, G, hd)


def _naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    window: Optional[int],
    scale: float,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    Skv = k.shape[1]
    q5 = _grouped(q, KV)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q5, k).astype(jnp.float32) * scale
    qi = jnp.arange(Sq)[:, None] + q_offset
    kj = jnp.arange(Skv)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= kj > qi - window
    scores = jnp.where(ok[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return o.reshape(B, Sq, H, v.shape[-1])


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    window: Optional[int] = None,
    use_kernels: bool = False,
    scale: Optional[float] = None,
    q_block: int = 1024,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, (B,S,H,hd) layout."""
    B, S, H, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if use_kernels and q.shape[-1] == v.shape[-1] and S % FLASH_TILE == 0:
        # (MLA's q head dim != v head dim and non-tile-aligned S fall back
        # to the jnp path; the kernel covers the GQA serving hot path)
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.flash_attention(q, k, v, window=window, scale=scale)
    if S <= q_block:
        return _naive_attention(q, k, v, window, scale)
    # blocked over query tiles: score tile is (B,KV,G,q_block,S), never S×S
    n_blk = S // q_block
    # contract-ok: no-bare-assert trace-time shape precondition inside jit
    assert S % q_block == 0, f"seq {S} not divisible by q_block {q_block}"
    q_tiles = q.reshape(B, n_blk, q_block, H, hd).transpose(1, 0, 2, 3, 4)
    offsets = jnp.arange(n_blk) * q_block

    def body(_, inp):
        q_tile, off = inp
        o = _naive_attention_dyn(q_tile, k, v, window, scale, off)
        return None, o

    _, o_tiles = jax.lax.scan(body, None, (q_tiles, offsets))
    return o_tiles.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def _naive_attention_dyn(
    q: jax.Array, k: jax.Array, v: jax.Array,
    window: Optional[int], scale: float, q_offset: jax.Array,
) -> jax.Array:
    """Like _naive_attention but with a traced query offset (scan tile)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    Skv = k.shape[1]
    q5 = _grouped(q, KV)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q5, k).astype(jnp.float32) * scale
    qi = jnp.arange(Sq)[:, None] + q_offset
    kj = jnp.arange(Skv)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= kj > qi - window
    scores = jnp.where(ok[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return o.reshape(B, Sq, H, v.shape[-1])


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    valid: jax.Array,  # (S,) or (B, S) bool — per-request ragged validity
    use_kernels: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    B, _, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None, :], (B, S))
    if use_kernels:
        from repro.kernels import ops

        return ops.decode_attention(q, k, v, valid, scale=scale)
    q5 = _grouped(q, KV)  # (B,1,KV,G,hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q5, k).astype(jnp.float32) * scale
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return o.reshape(B, 1, H, v.shape[-1])
