"""The dense decoder block: RMSNorm, rotary embedding over the whole head (the
two halves rotated against each other), grouped-query attention (one KV head
is MQA), a SwiGLU or a tanh-GELU MLP, and a head that is tied to the
embedding or not.  A configuration whose file names no ``"arch"`` runs this
block.

``m`` is the configuration's ``model`` block (``bench/configs/<name>.json``).
This module gives the benchmark everything that depends on the block (the
names ``bench/spec.py`` lists): the weights it draws, the plain reference's
decoder layer, and the work the served path requires.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flops import BF16_BYTES
from reference import Q_BLOCK, _mm, fp8, rmsnorm, rope
from weights import EMBED_STD, padded_vocab


# -- weights ---------------------------------------------------------------
def shapes(m: Mapping) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """``{name: (shape, init)}`` with ``init`` a std or ``"ones"``.  Leaves
    under ``layers.`` carry a leading layer axis."""
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    h, kv, L = m["num_heads"], m["num_kv_heads"], m["num_layers"]
    vp = padded_vocab(m)
    out: Dict[str, Tuple[Tuple[int, ...], Any]] = {
        "embed": ((vp, d), EMBED_STD),
        "final_norm": ((d,), "ones"),
    }
    if not m["tie_embeddings"]:
        out["head"] = ((d, vp), 1.0 / np.sqrt(d))
    layer = {
        "ln1": ((d,), "ones"),
        "wq": ((d, h * hd), 1.0 / np.sqrt(d)),
        "wk": ((d, kv * hd), 1.0 / np.sqrt(d)),
        "wv": ((d, kv * hd), 1.0 / np.sqrt(d)),
        "wo": ((h * hd, d), 1.0 / np.sqrt(h * hd)),
        "ln2": ((d,), "ones"),
        "w_up": ((d, ff), 1.0 / np.sqrt(d)),
        "w_down": ((ff, d), 1.0 / np.sqrt(ff)),
    }
    if m["mlp_gated"]:
        layer["w_gate"] = ((d, ff), 1.0 / np.sqrt(d))
    for name, (shape, init) in layer.items():
        out[f"layers.{name}"] = ((L,) + shape, init)
    return out


# -- the plain reference's decoder layer -----------------------------------
def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def layer_weights(m: Mapping, weights: Mapping[str, jax.Array],
                  i: int) -> Tuple[Dict[str, jax.Array], int]:
    """The weights decoder layer ``i`` reads, and its index along their
    leading axis: every layer reads the stacked ``layers.`` leaves."""
    return {k[len("layers."):]: v for k, v in weights.items()
            if k.startswith("layers.")}, i


def layer(m: Mapping, x, w: Dict[str, jax.Array], i, *, low: bool):
    """One decoder layer over one sequence ``x`` (S, d_model) in float32, or
    with ``low`` in fp8 (e4m3, absmax scales per output channel for weights
    and per token for activations and K/V)."""
    S = x.shape[0]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    g = h // kv

    def wt(name):
        a = jax.lax.dynamic_index_in_dim(w[name], i, 0, keepdims=False)
        return a.astype(jnp.float32)

    def lin(a, name):
        b = wt(name)
        if low:
            a, b = fp8(a, -1), fp8(b, 0)
        return _mm("sd,de->se", a, b)

    pos = jnp.arange(S)
    a = rmsnorm(x, wt("ln1"), m["norm_eps"])
    q = rope(lin(a, "wq").reshape(S, h, hd), pos, m["rope_theta"])
    k = rope(lin(a, "wk").reshape(S, kv, hd), pos, m["rope_theta"])
    v = lin(a, "wv").reshape(S, kv, hd)
    if low:
        q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, -1)
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, kv, g, hd)
    scale = 1.0 / np.sqrt(hd)

    def block(args):
        qi, b = args
        s = _mm("qkgh,skh->kgqs", qi, k) * scale
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqs,skh->qkgh", p, v)

    o = jax.lax.map(block, (qb, jnp.arange(nb))).reshape(S, h * hd)
    x = x + lin(o, "wo")
    a = rmsnorm(x, wt("ln2"), m["norm_eps"])
    if m["mlp_gated"]:
        u = jax.nn.silu(lin(a, "w_gate")) * lin(a, "w_up")
    else:
        u = gelu_tanh(lin(a, "w_up"))
    return x + lin(u, "w_down")


# -- work counts -------------------------------------------------------------
# What the algorithm needs, not what an implementation happens to do (see
# bench/flops.py).  Every attention layer is a full causal GQA layer with one
# K and one V head of ``head_dim`` per KV group.
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


# The cost of one call (one layer) of each kernel, by the ``name=`` of its
# ``pl.pallas_call``, as the kernels' roofline readers look it up.
KERNEL_COSTS = {
    "paged_decode_attention": paged_attn_cost,
    "flash_attention": flash_attn_cost,
}
