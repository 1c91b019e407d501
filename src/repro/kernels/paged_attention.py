"""Pallas TPU paged decode attention (production serving memory layout).

Real serving engines store KV in fixed-size *pages* from a shared pool so
requests of different lengths share HBM without per-request max-length
buffers (vLLM-style).  The page table and lengths are *scalar-prefetched*
(``pltpu.PrefetchScalarGridSpec``) into SMEM, and the kernel chases the page
table itself: the pool stays in HBM (``memory_space=ANY``) and the kernel
copies the pages it needs into VMEM with ``pltpu.make_async_copy`` — the TPU
analogue of a GPU kernel chasing the page table through shared memory.

Layouts:
  pool_k / pool_v : (L, num_pages, page_size, KV, D) — every layer's pool
  layer           : int32 scalar — the layer this call reads
  page_tables     : (B, max_pages) int32 — page ids per request, row-major
  lengths         : (B,) int32 — valid tokens per request (0: idle row)
  q               : (B, H, D)

Walk.  The grid runs over rows, ``(B,)``, in order.  Inside a grid step a
loop runs over the row's live *blocks* of :func:`pages_per_block` pages —
``cdiv(length, pages_per_block * page_size)`` of them, none for an idle row
— so the work follows the live tokens, not ``B * max_pages``.  A block's
pages are copied into one of two VMEM buffers; the next block's copy (the
row's next block, or the first block of the next live row, across the grid
step) starts before the current block is computed.  Only the pages that
hold a row's tokens are copied: a page slot past the row's length is neither
fetched nor visited.

Each page is viewed as ``(page_size * KV, D)``, one row per (token, KV
head) — a bitcast of the pool's TPU layout, so the pool is read in place and
an MQA page is not padded to a sublane tile per token.  A block is then one
``(R, D)`` matrix and its scores one ``(H, R)`` matmul: query head ``h`` keeps
the rows of its own KV head (``h // (H // KV)``) at positions under the
row's length, and every other score is masked.  Scores, the online softmax
(m, l, acc) and P·V accumulate in float32 over K/V as stored.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# K bytes one block of pages moves: large enough that a block's copies and
# matmuls outweigh its fixed cost, small enough that a short row fetches
# little past its length and two K and two V buffers sit in VMEM at ease
BLOCK_BYTES = 256 * 1024


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages the kernel copies and computes as one block: as many as fill
    :data:`BLOCK_BYTES` of K, at least one and at most ``max_pages``."""
    page = page_size * kv_heads * head_dim * itemsize
    return max(1, min(max_pages, BLOCK_BYTES // page))


def _paged_kernel(
    tables_ref,  # (B, max_pages) int32: page ids
    lengths_ref,  # (B,) int32
    layer_ref,  # (1,) int32: layer of the stacked pool
    live_ref,  # (B + 1,) int32: first row at or after each row with length > 0; B if none
    q_ref,  # (1, H, D)
    k_hbm, v_hbm,  # (L, num_pages, page_size * KV, D), in HBM
    o_ref,  # (1, H, D)
    k_buf, v_buf,  # (2, pages_per_block * page_size * KV, D): two blocks
    sems,  # DMA semaphores, one per buffer
    slot_ref,  # (1,) int32 SMEM: the buffer the next block lands in
    *, scale: float, groups: int, kv_heads: int, page_size: int,
    block_pages: int,
):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    length = lengths_ref[b]
    page_rows = page_size * kv_heads
    block_tokens = block_pages * page_size

    def copies(row, blk, slot, j):
        """The K and V copies of page ``j`` of block ``blk`` of ``row``."""
        page = tables_ref[row, blk * block_pages + j]
        dst = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
        return (pltpu.make_async_copy(k_hbm.at[layer_ref[0], page],
                                      k_buf.at[slot, dst], sems.at[slot]),
                pltpu.make_async_copy(v_hbm.at[layer_ref[0], page],
                                      v_buf.at[slot, dst], sems.at[slot]))

    def pages_in(row, blk):
        """Pages of ``row``'s block ``blk`` that hold its tokens."""
        return jnp.minimum(block_pages,
                           pl.cdiv(lengths_ref[row], page_size) - blk * block_pages)

    def start(row, blk, slot):
        def one(j, carry):
            for c in copies(row, blk, slot, j):
                c.start()
            return carry
        jax.lax.fori_loop(0, pages_in(row, blk), one, 0)

    def wait(row, blk, slot):
        def one(j, carry):
            for c in copies(row, blk, slot, j):
                c.wait()
            return carry
        jax.lax.fori_loop(0, pages_in(row, blk), one, 0)

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _row():
        @pl.when(b == live_ref[0])  # the first live row: no row before started it
        def _first():
            slot_ref[0] = 0
            start(b, 0, 0)

        nblocks = pl.cdiv(length, block_tokens)
        next_row = live_ref[b + 1]
        q = q_ref[0]  # (H, D)
        H, D = q.shape
        R = k_buf.shape[1]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        token = col // kv_heads  # the block position of each K/V row
        head_ok = (col % kv_heads
                   == jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // groups)

        def block(i, carry):
            m, l, acc = carry
            slot = slot_ref[0]

            @pl.when(i + 1 < nblocks)
            def _next_block():
                start(b, i + 1, 1 - slot)

            @pl.when((i + 1 == nblocks) & (next_row < rows))
            def _next_row():
                start(next_row, 0, 1 - slot)

            wait(b, i, slot)
            k = k_buf[slot]
            v = v_buf[slot]
            dt = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (H, R)
            ok = head_ok & (i * block_tokens + token < length)
            s = jnp.where(ok, s, NEG_INF)
            m_cur = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m - m_cur)
            # pages past the row's length were not copied: their rows hold
            # whatever the buffer held before, so zero them under p's zeros
            row_ok = (i * block_tokens
                      + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // kv_heads
                      < length)
            v = jnp.where(row_ok, v.astype(jnp.float32), 0.0)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            slot_ref[0] = 1 - slot
            return (m_cur, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + pv)

        init = (jnp.full((H, 1), NEG_INF, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, D), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, nblocks, block, init)
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    L, num_pages, page_size, KV, _ = pool_k.shape
    max_pages = page_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_pages = pages_per_block(page_size, KV, D, pool_k.dtype.itemsize, max_pages)
    R = block_pages * page_size * KV

    lengths = lengths.astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    live = jax.lax.cummin(jnp.where(lengths > 0, rows, B), reverse=True)
    live = jnp.concatenate([live, jnp.full((1,), B, jnp.int32)])
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # (page_size, KV, D) -> (page_size * KV, D): a bitcast of the pool
    pool_k = pool_k.reshape(L, num_pages, page_size * KV, D)
    pool_v = pool_v.reshape(L, num_pages, page_size * KV, D)

    def row_map(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, groups=H // KV, kv_heads=KV,
        page_size=page_size, block_pages=block_pages,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, R, D), pool_k.dtype),
            pltpu.VMEM((2, R, D), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # a row's last block starts the copy of the next live row's first
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_tables.astype(jnp.int32), lengths, layer, live,
      q, pool_k, pool_v)
