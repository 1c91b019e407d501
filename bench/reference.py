"""The plain reference: a float32 ``jax.numpy`` forward of the dense decoder.

It imports nothing of the program.  Its block is the one the configuration
file states (``bench/configs/<name>.json``): RMSNorm, rotary embedding over
the whole head (the two halves rotated against each other), grouped-query
attention (one KV head is MQA), a SwiGLU or a tanh-GELU MLP, and a head that
is tied to the embedding or not.  Every matrix product runs at
``Precision.HIGHEST``.  It runs layer by layer, each layer over every sequence,
with attention in blocks of :data:`Q_BLOCK` query rows, so it fits beside
the weights at the benchmark's largest contexts.

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
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

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


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


class Reference:
    """The forward of configuration ``m`` on the benchmark's weights
    (:func:`bench.weights.make_weights`), in float32 or, with ``low``, in
    fp8."""

    def __init__(self, m: Mapping):
        self.m = dict(m)
        self._layer = {lo: jax.jit(functools.partial(self._layer_fn, low=lo))
                       for lo in (False, True)}
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._head = jax.jit(self._head_fn)

    # -- one decoder layer --------------------------------------------------
    def _layer_fn(self, x, w: Dict[str, jax.Array], i, *, low: bool):
        m = self.m
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
        layers = {k[len("layers."):]: v for k, v in weights.items()
                  if k.startswith("layers.")}
        xs = []
        for toks in seqs:
            S = -(-len(toks) // Q_BLOCK) * Q_BLOCK
            padded = np.zeros(S, np.int32)
            padded[: len(toks)] = toks
            x = self._embed(weights["embed"], jnp.asarray(padded))
            xs.append([x, x] if control else [x])
        with jax.default_matmul_precision("highest"):
            for i in range(self.m["num_layers"]):
                li = jnp.int32(i)
                for pair in xs:
                    pair[0] = self._layer[False](pair[0], layers, li)
                    if control:
                        pair[1] = self._layer[True](pair[1], layers, li)
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
