"""Mixture-of-Experts layer (DeepSeek-style: shared + routed experts, top-k).

Dispatch is capacity-based scatter/gather: tokens are placed into an
(E, C, d) expert buffer (position = arrival order within the expert, tokens
beyond capacity dropped), expert SwiGLU runs as a batched matmul sharded over
the ``model`` axis (expert parallelism), and outputs are gathered back and
combined with the router weights.  Under pjit this lowers to the
all-to-all-shaped collectives the roofline analysis wants to see
(DESIGN.md §5); §Perf iterates on this dispatch.

The router runs in fp32; an aux load-balance loss (Switch-style) is returned
alongside the output.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamFactory
from repro.models.config import ModelConfig
from repro.models.mlp import mlp_forward, mlp_init


def moe_init(f: ParamFactory, cfg: ModelConfig) -> None:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    f.add("router", (d, E), (None, None), scale=0.02)
    f.add("we_gate", (E, d, ff), ("model", None, None))
    f.add("we_up", (E, d, ff), ("model", None, None))
    f.add("we_down", (E, ff, d), ("model", None, None))
    if cfg.num_shared_experts:
        sf = f.subfactory("shared")
        mlp_init(sf, cfg, d_ff=ff * cfg.num_shared_experts)


def capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.experts_per_token / cfg.num_experts * cfg.capacity_factor)
    return max(8, int(math.ceil(c / 8) * 8))


def moe_forward(
    p: Dict[str, Any], cfg: ModelConfig, x: jax.Array, buf_spec=None
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    ``buf_spec`` (§Perf): PartitionSpec for the (E, C, d) expert buffer.
    Without it the SPMD partitioner shards E over "model" but *replicates*
    the capacity dim across the data axis — every data shard redundantly
    computes the full expert GEMM (16× wasted MXU time on a 16×16 mesh).
    ``P("model", "data", None)`` splits capacity rows across data shards."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)  # (T,k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)  # DeepSeek renormalises top-k

    C = capacity(T, cfg)
    idx_f = idx.reshape(T * k)
    w_f = w.reshape(T * k).astype(x.dtype)
    onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_f = jnp.sum(pos * onehot, axis=-1)  # (T*k,) slot within expert
    keep = (pos_f < C).astype(x.dtype)
    safe_pos = jnp.minimum(pos_f, C - 1)

    xk = jnp.broadcast_to(xf[:, None, :], (T, k, d)).reshape(T * k, d)
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[idx_f, safe_pos].add(xk * keep[:, None])
    if buf_spec is not None:
        buf = jax.lax.with_sharding_constraint(buf, buf_spec)

    # expert SwiGLU, batched over E (sharded over the model axis)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["we_up"]
    )
    hout = jnp.einsum("ecf,efd->ecd", h, p["we_down"])  # (E,C,d)
    if buf_spec is not None:
        hout = jax.lax.with_sharding_constraint(hout, buf_spec)

    gathered = hout[idx_f, safe_pos] * (keep * w_f)[:, None]  # (T*k, d)
    out = gathered.reshape(T, k, d).sum(axis=1)

    if cfg.num_shared_experts:
        out = out + mlp_forward(p["shared"], xf)

    # Switch-style load-balance aux
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return out.reshape(B, S, d), aux


# =============================================================================
# shard_map expert-parallel dispatch (beyond-paper, EXPERIMENTS.md §Perf H4)
# =============================================================================


def moe_forward_shard_map(
    p: Dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,
    mesh,
    dp_axes: Tuple[str, ...] = ("data",),
    ep_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE with explicit per-device dispatch.

    The pjit scatter dispatch either replicates the expert GEMM across the
    data axis (16× wasted compute) or, when capacity is sharded, emits
    pessimal collectives (§Perf H4).  Here each (data, model) device runs
    the router on its *local* tokens (activations are already replicated
    over the model axis), keeps only the tokens routed to its own expert
    range, runs its expert shard's GEMM at local capacity, and psums partial
    outputs over the expert axis — the same all-reduce a dense TP MLP pays.
    Dispatch itself moves **zero** bytes.
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    ep = mesh.shape[ep_axis]
    # contract-ok: no-bare-assert trace-time shape precondition inside jit
    assert E % ep == 0, (E, ep)
    e_loc = E // ep
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    t_loc = (B // dp_size if B % dp_size == 0 else B) * S
    c_loc = capacity(t_loc, cfg)

    def body(x_loc, router, we_gate, we_up, we_down, shared):
        # x_loc: (B_loc, S, d) ; we_*: (e_loc, d, f) local expert shard
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xf = x_loc.reshape(T, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(x_loc.dtype)

        my_lo = jax.lax.axis_index(ep_axis) * e_loc
        idx_f = idx.reshape(T * k)
        w_f = w.reshape(T * k)
        local_e = idx_f - my_lo  # in [0, e_loc) if mine
        mine = (local_e >= 0) & (local_e < e_loc)
        safe_e = jnp.clip(local_e, 0, e_loc - 1)
        onehot = jax.nn.one_hot(safe_e, e_loc, dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos_f = jnp.sum(pos * onehot, axis=-1)
        keep = (mine & (pos_f < c_loc)).astype(x_loc.dtype)
        safe_pos = jnp.minimum(pos_f, c_loc - 1)

        xk = jnp.broadcast_to(xf[:, None, :], (T, k, d)).reshape(T * k, d)
        buf = jnp.zeros((e_loc, c_loc, d), x_loc.dtype)
        buf = buf.at[safe_e, safe_pos].add(xk * keep[:, None])
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we_gate)) * jnp.einsum(
            "ecd,edf->ecf", buf, we_up
        )
        hout = jnp.einsum("ecf,efd->ecd", h, we_down)
        gathered = hout[safe_e, safe_pos] * (keep * w_f)[:, None]
        out = gathered.reshape(T, k, d).sum(axis=1)
        out = jax.lax.psum(out, ep_axis)  # partial expert outputs combine

        if shared is not None:
            # shared experts are model-sharded like a dense TP MLP
            hs = jax.nn.silu(xf @ shared["w_gate"]) * (xf @ shared["w_up"])
            out = out + jax.lax.psum(hs @ shared["w_down"], ep_axis)

        frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return out.reshape(Bl, Sl, d), aux

    dp = dp_axes if B % dp_size == 0 and B >= dp_size else ()
    shared = p.get("shared")
    shared_specs = (
        {"w_gate": P(None, ep_axis), "w_up": P(None, ep_axis), "w_down": P(ep_axis, None)}
        if shared is not None
        else None
    )
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),
            P(None, None),
            P(ep_axis, None, None),
            P(ep_axis, None, None),
            P(ep_axis, None, None),
            shared_specs,
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared)
    return out, aux
