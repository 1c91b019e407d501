"""Pallas TPU flash attention (prefill hot-spot).

Block-tiled causal attention with online softmax.  TPU adaptation notes
(DESIGN.md §2): the kv-block loop lives in the *grid* (TPU grid steps execute
sequentially, so the running (m, l, acc) state is carried in VMEM scratch),
block shapes are MXU-aligned (q/kv tiles of 128 × head_dim 128), and
causally-dead kv blocks are skipped with ``pl.when`` rather than thread-level
predication — there is no warp-level masking on a systolic array.

Layouts: q (B, H, S, D); k/v (B, KV, S, D); GQA via ``h // group`` in the kv
index maps.  Supports an optional sliding window.
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


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,
    m_ref, l_ref, acc_ref,  # scratch
    *, scale: float, block_q: int, block_k: int, window: Optional[int],
    kv_len: int,
):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    # causal pruning: kv block strictly after the last query of this tile
    live = k_start <= q_start + block_q - 1
    if window is not None:
        live &= k_start + block_k - 1 >= q_start - window + 1 - (block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = kj <= qi
        if window is not None:
            ok &= kj > qi - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, KV, S, D)
    v: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    # contract-ok: no-bare-assert trace-time shape precondition inside jit
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (B, H, S // block_q, S // block_k)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, block_q=block_q, block_k=block_k, window=window, kv_len=S,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
