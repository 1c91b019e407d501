"""Operations and bytes the served work requires, from shapes and live lengths.

These count what the algorithm needs, not what an implementation happens to
do: padding, idle batch rows, dead pages and masked-out score blocks are not
counted. So a kernel's roofline share and a step's MFU change only when the
time changes, never when the grid, the page table or the bucket does.

``m`` is a configuration's ``model`` block (the dict in
``bench/configs/<name>.json``).  Every attention layer here is a full causal
GQA layer with one K and one V head of ``head_dim`` per KV group.
"""

from __future__ import annotations

from typing import Iterable, Mapping, Tuple

BF16_BYTES = 2


def layer_matmul_params(m: Mapping) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (m["num_heads"] + 2 * m["num_kv_heads"]) + m["num_heads"] * hd * d
    mlp = (3 if m["mlp_gated"] else 2) * d * m["d_ff"]
    return attn + mlp


def head_params(m: Mapping) -> int:
    """Weights of the output projection over the real vocabulary."""
    return m["d_model"] * m["vocab_size"]


def _attn_pair_flops(m: Mapping) -> int:
    """FLOPs of one (query, key) pair over all heads of one layer: QK^T and
    PV, each ``2 * head_dim`` per head."""
    return 4 * m["num_heads"] * m["head_dim"]


def decode_step_flops(m: Mapping, ctx_lens: Iterable[int]) -> float:
    """One decode step over the live rows; ``ctx_lens`` holds, per live row,
    the tokens its new token attends to (itself included)."""
    per_row = 2 * (m["num_layers"] * layer_matmul_params(m) + head_params(m))
    pair = _attn_pair_flops(m) * m["num_layers"]
    return float(sum(per_row + pair * n for n in ctx_lens))


def prefill_flops(m: Mapping, length: int) -> float:
    """One prefill of ``length`` real tokens: every token through every
    layer, the causal half of attention, and the head for the last token."""
    layers = m["num_layers"]
    dense = 2 * layers * layer_matmul_params(m) * length
    attn = _attn_pair_flops(m) * layers * length * (length + 1) // 2
    return float(dense + attn + 2 * head_params(m))


def paged_attn_cost(m: Mapping, ctx_lens: Iterable[int]) -> Tuple[float, float]:
    """``(flops, bytes)`` of one paged decode-attention call (one layer):
    the K/V of the live tokens, and q in and out for the live rows."""
    ctx = list(ctx_lens)
    hd, kv, h = m["head_dim"], m["num_kv_heads"], m["num_heads"]
    tokens = sum(ctx)
    flops = _attn_pair_flops(m) * tokens
    kv_bytes = tokens * 2 * kv * hd * BF16_BYTES
    q_out_bytes = len(ctx) * 2 * h * hd * BF16_BYTES
    return float(flops), float(kv_bytes + q_out_bytes)


def flash_attn_cost(m: Mapping, length: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one causal flash-attention call (one layer) over
    ``length`` real tokens: the causal half of the scores, and q, k, v and
    out read or written once."""
    hd, kv, h = m["head_dim"], m["num_kv_heads"], m["num_heads"]
    flops = _attn_pair_flops(m) * length * (length + 1) // 2
    nbytes = length * (2 * h + 2 * kv) * hd * BF16_BYTES
    return float(flops), float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
