"""Pallas TPU paged decode attention (production serving memory layout).

Real serving engines store KV in fixed-size *pages* from a shared pool so
requests of different lengths share HBM without per-request max-length
buffers (vLLM-style).  TPU adaptation: the page table is *scalar-prefetched*
(``pltpu.PrefetchScalarGridSpec``) so each grid step's BlockSpec index_map
can pick the right page out of the pool — the TPU analogue of a GPU kernel
chasing the page table through shared memory.

Layouts:
  pool_k / pool_v : (L, num_pages, page_size, KV, D) — every layer's pool
  layer           : int32 scalar — the layer this call reads
  page_tables     : (B, max_pages) int32 — page ids per request, row-major
  lengths         : (B,) int32 — valid tokens per request
  q               : (B, H, D)

Grid: (B, max_pages) with the page loop innermost, carrying (m, l, acc)
scratch exactly like the flat decode kernel.  Pages past a request's length
contribute nothing (masked); page id 0 is a legal dummy for unused slots.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    tables_ref,  # (B, max_pages) int32: page ids (read by the index maps)
    lengths_ref,  # (B,) int32
    layer_ref,  # (1,) int32: layer of the stacked pool
    q_ref, k_ref, v_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, scale: float, groups: int, page_size: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a scalar ref takes no negative index on the TPU (-1 reads out of row)
    length = lengths_ref[b]
    page_start = j * page_size
    live = page_start < length

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (H, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (page_size, KV, D)
        v = v_ref[0, 0].astype(jnp.float32)
        H, D = q.shape
        P, KV, _ = k.shape
        qg = q.reshape(KV, groups, D)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))), preferred_element_type=jnp.float32
        ) * scale  # (KV, G, P)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, (P,), 0)
        ok = pos < length
        s = jnp.where(ok[None, None, :], s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_cur[:, :, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv
        m_ref[...] = m_cur

    @pl.when(j == nj - 1)
    def _finalize():
        H, D = q_ref.shape[1], q_ref.shape[2]
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, :, None]).reshape(H, D).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, H, D)
    pool_k: jax.Array,  # (L, num_pages, page_size, KV, D)
    pool_v: jax.Array,
    page_tables: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,  # (B,) int32
    layer: jax.Array,  # int32 scalar
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    page_size, KV = pool_k.shape[2], pool_k.shape[3]
    max_pages = page_tables.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(b, j, tables, lens, layer_ref):
        return (b, 0, 0)

    def kv_map(b, j, tables, lens, layer_ref):
        return (layer_ref[0], tables[b, j], 0, 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, groups=G, page_size=page_size
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), q_map),
            pl.BlockSpec((1, 1, page_size, KV, D), kv_map),
            pl.BlockSpec((1, 1, page_size, KV, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_tables.astype(jnp.int32), lengths.astype(jnp.int32), layer,
      q, pool_k, pool_v)
