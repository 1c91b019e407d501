"""The plain reference: a float32 ``jax.numpy`` forward of the decoder.

It imports nothing of the program.  What is the same for every block is
here: the embedding, the layer loop, the final RMSNorm and the head (tied to
the embedding or not), and the check.  The decoder layer is the one the
configuration's architecture module states (``layer_weights`` and ``layer``
in ``bench/arch/<arch>.py``), which builds it from the helpers here.  Every
matrix product runs at ``Precision.HIGHEST``.  It runs layer by layer, each
layer over every sequence, each sequence padded to whole blocks of
:data:`Q_BLOCK` rows for a layer's attention to take a block of query rows
at a time, so it fits beside the weights at the benchmark's largest
contexts.

The check (:func:`token_gaps`) runs each request's prompt followed by its
served tokens, and reads, at the position of every served token, how far the
served token's logit lies below the reference's best.  With ``control``
it also runs the same forward in fp8 (e4m3, absmax scales per output channel
for weights and per token for activations and K/V) and reads the gap of the
token the fp8 forward puts first: the lower precision that a faster path
would be tempted by, which the check has to refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block, and the sequence bucket
HEAD_ROWS = 256  # positions per block of the output projection
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def fp8(x: jax.Array, reduce_axis: int) -> jax.Array:
    """Round ``x`` to float8_e4m3fn with one absmax scale per slice along
    ``reduce_axis``, and return it in float32."""
    s = jnp.max(jnp.abs(x), axis=reduce_axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotary embedding over the whole head: x (S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Reference:
    """The forward of configuration ``m``, of architecture module ``arch``,
    on the benchmark's weights (:func:`bench.weights.make_weights`), in
    float32 or, with ``low``, in fp8."""

    def __init__(self, arch: Any, m: Mapping):
        self.arch = arch
        self.m = dict(m)
        self._layer = {lo: jax.jit(functools.partial(arch.layer, self.m, low=lo))
                       for lo in (False, True)}
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._head = jax.jit(self._head_fn)

    # -- output projection over the real vocabulary -------------------------
    def _head_fn(self, h_ref, h_low, tokens, final_norm, head):
        """Per row: (best reference logit - reference logit of ``tokens``,
        best reference logit - reference logit of the fp8 forward's first
        token).  ``h_low`` may be None."""
        m = self.m
        V = m["vocab_size"]
        tied = m["tie_embeddings"]
        spec = "rd,vd->rv" if tied else "rd,dv->rv"
        hw = head.astype(jnp.float32)
        fn = final_norm.astype(jnp.float32)
        ref = _mm(spec, rmsnorm(h_ref, fn, m["norm_eps"]), hw)[:, :V]
        best = ref.max(axis=-1)
        served = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
        if h_low is None:
            return best - served, None
        hq = fp8(hw, 1 if tied else 0)
        low = _mm(spec, fp8(rmsnorm(h_low, fn, m["norm_eps"]), -1), hq)[:, :V]
        pick = jnp.argmax(low, axis=-1)
        ctl = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return best - served, best - ctl

    # -- forward ------------------------------------------------------------
    def hidden(self, weights: Mapping[str, jax.Array],
               seqs: Sequence[np.ndarray], control: bool = False) -> List[List[jax.Array]]:
        """The last layer's output for each token sequence, padded up to a
        whole :data:`Q_BLOCK`: ``[[float32], ...]``, or with ``control``
        ``[[float32, fp8], ...]``.  Layer by layer, over every sequence."""
        xs = []
        for toks in seqs:
            S = -(-len(toks) // Q_BLOCK) * Q_BLOCK
            padded = np.zeros(S, np.int32)
            padded[: len(toks)] = toks
            x = self._embed(weights["embed"], jnp.asarray(padded))
            xs.append([x, x] if control else [x])
        with jax.default_matmul_precision("highest"):
            for i in range(self.m["num_layers"]):
                w, j = self.arch.layer_weights(self.m, weights, i)
                j = jnp.int32(j)
                for pair in xs:
                    pair[0] = self._layer[False](pair[0], w, j)
                    if control:
                        pair[1] = self._layer[True](pair[1], w, j)
        return xs

    def logits(self, weights: Mapping[str, jax.Array], tokens: np.ndarray) -> np.ndarray:
        """float32 logits over the real vocabulary at every position."""
        m = self.m
        (h,), = self.hidden(weights, [np.asarray(tokens, np.int32)])
        head = weights["embed"] if m["tie_embeddings"] else weights["head"]
        spec = "rd,vd->rv" if m["tie_embeddings"] else "rd,dv->rv"
        with jax.default_matmul_precision("highest"):
            fn = weights["final_norm"].astype(jnp.float32)
            out = _mm(spec, rmsnorm(h[: len(tokens)], fn, m["norm_eps"]),
                      head.astype(jnp.float32))
        return np.asarray(out[:, : m["vocab_size"]])

    # -- the check ----------------------------------------------------------
    def token_gaps(
        self,
        weights: Mapping[str, jax.Array],
        items: Sequence[Tuple[np.ndarray, Sequence[int]]],
        control: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """For each ``(prompt, served tokens)``: ``served`` holds, per served
        token, the reference's best logit minus the served token's logit at
        that position; with ``control``, ``control`` holds the same for the
        token the fp8 forward puts first."""
        m = self.m
        head = weights["embed"] if m["tie_embeddings"] else weights["head"]
        seqs = [np.concatenate([np.asarray(p, np.int32), np.asarray(s[:-1], np.int32)])
                for p, s in items]
        xs = self.hidden(weights, seqs, control)
        out = []
        with jax.default_matmul_precision("highest"):
            for (prompt, served), pair in zip(items, xs):
                served = np.asarray(served, np.int32)
                rows = np.arange(len(prompt) - 1, len(prompt) - 1 + served.size)
                g_served, g_ctl = [], []
                for s in range(0, rows.size, HEAD_ROWS):
                    r = rows[s: s + HEAD_ROWS]
                    n = r.size
                    r = np.pad(r, (0, HEAD_ROWS - n), mode="edge")
                    tk = np.pad(served[s: s + HEAD_ROWS], (0, HEAD_ROWS - n))
                    hl = pair[1][r] if control else None
                    a, b = self._head(pair[0][r], hl, jnp.asarray(tk),
                                      weights["final_norm"], head)
                    g_served.append(np.asarray(a)[:n])
                    if control:
                        g_ctl.append(np.asarray(b)[:n])
                res = {"served": np.concatenate(g_served)}
                if control:
                    res["control"] = np.concatenate(g_ctl)
                out.append(res)
        return out


def widest(gaps: Sequence[Dict[str, np.ndarray]], key: str) -> Optional[float]:
    """The widest gap under ``key`` over all items."""
    vals = [float(np.max(g[key])) for g in gaps if g[key].size]
    return max(vals) if vals else None
