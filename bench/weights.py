"""Seeded random weights, made on the device in one jitted call.

The benchmark owns its weights: it draws them from ``--seed`` in the type
they are served in (bfloat16), at the scales that keep logits near unit
scale (normal with std ``1/sqrt(fan_in)``, the embedding at std 0.02, norm
weights 1).  Each stacked leaf is drawn one layer slice at a time and each
large leaf one row slab at a time, so a float32 draw never needs more than
one slice beside the weights.  The plain reference makes the same weights
again with the same call; it takes nothing the program made.

The weights are held under the benchmark's own names, as the
configuration's architecture module gives them (``shapes`` in
``bench/arch/<arch>.py``); :func:`program_params` lays them into the tree
the program's ``Model`` expects, matching leaves by name, without copying.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
_SLABS = 16  # row slabs of a large unstacked leaf


def padded_vocab(m: Mapping) -> int:
    p = m["vocab_pad"]
    return -(-m["vocab_size"] // p) * p


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, also past 32 bits."""
    if seed < 0:
        raise ValueError("seed must be non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, std, dtype, stacked: bool):
    """Draw a leaf slab by slab: along the layer axis when stacked, else in
    :data:`_SLABS` row slabs when the rows divide evenly."""
    if std == "ones":
        return jnp.ones(shape, dtype)
    n = shape[0] if stacked else (_SLABS if shape[0] % _SLABS == 0 and len(shape) > 1 else 1)
    slab = (shape[0] // n,) + shape[1:] if not stacked else shape[1:]

    def one(i):
        x = jax.random.normal(jax.random.fold_in(key, i), slab, jnp.float32)
        return (x * std).astype(dtype)

    out = jax.lax.map(one, jnp.arange(n))
    return out.reshape(shape)


@functools.lru_cache(maxsize=None)
def _maker(spec: Tuple[Tuple[str, Tuple[int, ...], Any], ...], dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return {
            name: _draw(jax.random.fold_in(key, i), shape, init, dtype,
                        name.startswith("layers."))
            for i, (name, shape, init) in enumerate(spec)
        }

    return jax.jit(make)


def make_weights(arch: Any, m: Mapping, seed: int) -> Dict[str, jax.Array]:
    """All weights of configuration ``m``, of architecture module ``arch``,
    for ``seed``, on the default device, in one jitted call."""
    spec = tuple((name, shape, init) for name, (shape, init) in sorted(arch.shapes(m).items()))
    return _maker(spec, m["dtype"])(seed_key(seed))


def program_params(abstract_params: Any, weights: Mapping[str, jax.Array]) -> Any:
    """Lay ``weights`` into the program's parameter tree, given as its
    abstract (shape-only) form.  A leaf ``.../layers/.../wq`` takes
    ``layers.wq``, a top-level leaf ``embed`` takes ``embed``.  A leaf with
    no weight of that name or of another shape is an error."""

    def place(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        name = f"layers.{keys[-1]}" if keys[0] == "layers" else keys[-1]
        if name not in weights:
            raise KeyError(f"the benchmark makes no weight for {'/'.join(map(str, keys))}")
        w = weights[name]
        if tuple(w.shape) != tuple(leaf.shape) or w.dtype != leaf.dtype:
            raise ValueError(
                f"{'/'.join(map(str, keys))}: program wants {leaf.shape} {leaf.dtype}, "
                f"benchmark made {w.shape} {w.dtype}"
            )
        return w

    return jax.tree_util.tree_map_with_path(place, abstract_params)
