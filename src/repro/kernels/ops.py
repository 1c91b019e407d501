"""Public jit'd wrappers around the Pallas kernels.

These adapt the model layout (B, S, H, D) to the kernel layouts, pick
interpret mode automatically on CPU (kernels are TPU-targeted; interpret mode
executes the kernel body in Python for validation), and expose the same
signatures :mod:`repro.models.kernels_bridge` expects.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssm_scan as _ssd


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


@functools.partial(jax.jit, static_argnames=("window", "scale", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,  # (B, S, H, D) — model layout
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    out = _fa.flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        scale=scale,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=_interpret(),
    )
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("scale", "block_k"))
def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    valid: jax.Array,  # (S,) or (B, S) bool — per-request ragged validity
    scale: Optional[float] = None,
    block_k: int = 512,
) -> jax.Array:
    B, _, H, D = q.shape
    S = k.shape[1]
    vmask = jnp.broadcast_to(valid.astype(jnp.int32), (B, S))
    out = _dec.decode_attention_bhd(
        q[:, 0], k, v, vmask, scale=scale, block_k=block_k, interpret=_interpret()
    )
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_attention(
    q: jax.Array,  # (B, 1, H, D) — model layout
    pool_k: jax.Array,  # (L, num_pages, page_size, KV, D)
    pool_v: jax.Array,
    page_tables: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,  # (B,) int32 — valid tokens per request
    layer: jax.Array,  # int32 scalar — the layer of the stacked pools to read
    scale: Optional[float] = None,
) -> jax.Array:
    from repro.kernels import paged_attention as _paged

    out = _paged.paged_decode_attention(
        q[:, 0], pool_k, pool_v, page_tables, lengths, layer,
        scale=scale, interpret=_interpret(),
    )
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssm_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    B_: jax.Array,  # (B, S, N)
    C_: jax.Array,  # (B, S, N)
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    return _ssd.ssm_scan_bshp(x, dt, A, B_, C_, chunk=chunk, interpret=_interpret())
