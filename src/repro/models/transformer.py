"""Config-driven decoder models for all assigned architecture families.

Layer stacks are *scanned* (``jax.lax.scan`` over stacked parameters) so HLO
size — and therefore dry-run compile time — is O(1) in depth even for the
126-layer llama3-405b (DESIGN.md §5).  Heterogeneous stacks are handled as:

  * dense / vlm / audio : one scanned stack of (attn + SwiGLU) blocks
  * moe                 : ``first_dense_layers`` unrolled dense blocks, then a
                          scanned stack of (attn + MoE) blocks
  * ssm                 : one scanned stack of Mamba2 blocks
  * hybrid (Zamba2)     : scanned *superblocks* of ``shared_attn_every``
                          Mamba2 sublayers + one invocation of a single
                          weight-shared GQA block (closed over, not scanned)

Public surface: :class:`Model` with ``init`` / ``forward`` / ``init_cache`` /
``decode_step``.  ``forward`` accepts token ids or — for the stub-modality
architectures (vlm/audio) — precomputed frontend embeddings.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import DTYPES, ParamFactory, batch_spec, rmsnorm
from repro.models.config import ModelConfig
from repro.models.mlp import mlp_forward, mlp_init

Params = Dict[str, Any]


def _attn_init(f: ParamFactory, cfg: ModelConfig) -> None:
    if cfg.attention_kind == "mla":
        attn.mla_init(f, cfg)
    else:
        attn.gqa_init(f, cfg)


def _attn_forward(p, cfg, x, positions, use_kernels, kv_hint=None):
    if cfg.attention_kind == "mla":
        return attn.mla_forward(p, cfg, x, positions, use_kernels, kv_hint=kv_hint)
    return attn.gqa_forward(p, cfg, x, positions, use_kernels, kv_hint=kv_hint)


def _attn_decode(p, cfg, x, cache, pos, live=None):
    if cfg.attention_kind == "mla":
        return attn.mla_decode(p, cfg, x, cache, pos, live)
    return attn.gqa_decode(p, cfg, x, cache, pos, live)


def _attn_init_cache(cfg, batch, max_len, dtype):
    if cfg.attention_kind == "mla":
        return attn.mla_init_cache(cfg, batch, max_len, dtype)
    return attn.gqa_init_cache(cfg, batch, max_len, dtype)


def _attn_cache_specs(cfg, dp, seq_axis):
    if cfg.attention_kind == "mla":
        return attn.mla_cache_specs(cfg, dp, seq_axis)
    return attn.gqa_cache_specs(cfg, dp, seq_axis)


def _stacked(n: int, layer_cache: Params) -> Params:
    """``n`` copies of one layer's cache along a new leading scan axis, each
    leaf allocated once at its stacked size — stacking ``n`` per-layer
    copies would hold the whole cache twice while it is built."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), layer_cache)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    use_kernels: bool = False
    remat: bool = True
    mesh_axes: Tuple[str, ...] = ("data", "model")
    # §Perf knob: constrain the residual stream's feature dim to the model
    # axis between blocks — XLA SPMD then lowers TP all-reduces into
    # reduce-scatter + all-gather pairs (sequence-parallel-style savings).
    act_tp: bool = False
    # §Perf knob: PartitionSpec pinned onto full-sequence k/v above the
    # blocked-attention tile loop (prevents per-tile re-gathers).
    kv_hint: object = None
    # §Perf knob: PartitionSpec for the MoE (E, C, d) expert buffer —
    # shards capacity over "data" so expert GEMMs are not replicated.
    moe_buf_spec: object = None
    # §Perf knob (H4 resolution): explicit shard_map expert dispatch —
    # requires the mesh object; zero-byte dispatch, no replicated GEMMs.
    moe_shard_map_mesh: object = None

    def _constrain(self, x: jax.Array) -> jax.Array:
        if not self.act_tp:
            return x
        dp = batch_spec(self.mesh_axes)
        return jax.lax.with_sharding_constraint(x, P(dp, None, "model"))

    # ------------------------------------------------------------------ init --
    def init(
        self, key: Optional[jax.Array], abstract: bool = False
    ) -> Tuple[Params, Params]:
        """Returns (params, partition-spec tree).  ``abstract=True`` emits
        ShapeDtypeStructs instead of arrays — the dry-run's no-allocation
        path (DESIGN.md §5)."""
        cfg = self.cfg
        dtype = DTYPES[cfg.dtype]
        f = ParamFactory(key, dtype, abstract=abstract)
        f.add("embed", (cfg.padded_vocab, cfg.d_model), ("model", None), scale=0.02)
        if not cfg.tie_embeddings:
            f.add("head", (cfg.d_model, cfg.padded_vocab), (None, "model"))
        f.add("final_norm", (cfg.d_model,), (None,), init="ones")

        if cfg.arch_type in ("dense", "vlm", "audio"):
            lf = f.subfactory("layers", stack_depth=cfg.num_layers)
            self._dense_block_init(lf, cfg)
        elif cfg.arch_type == "moe":
            for i in range(cfg.first_dense_layers):
                df = f.subfactory(f"dense_{i}")
                self._dense_block_init(df, cfg)
            n_moe = cfg.num_layers - cfg.first_dense_layers
            lf = f.subfactory("layers", stack_depth=n_moe)
            self._moe_block_init(lf, cfg)
        elif cfg.arch_type == "ssm":
            lf = f.subfactory("layers", stack_depth=cfg.num_layers)
            lf.add("ln", (cfg.d_model,), (None,), init="ones")
            ssm_mod.ssm_init(lf, cfg)
        elif cfg.arch_type == "hybrid":
            k = cfg.shared_attn_every
            # contract-ok: no-bare-assert trace-time shape precondition inside jit
            assert cfg.num_layers % k == 0, "hybrid depth must divide superblock"
            sf = f.subfactory("shared_attn")
            sf.add("ln", (cfg.d_model,), (None,), init="ones")
            _attn_init(sf, cfg)
            lf = f.subfactory("layers", stack_depth=cfg.num_layers // k)
            for i in range(k):
                mf = lf.subfactory(f"mamba_{i}")
                mf.add("ln", (cfg.d_model,), (None,), init="ones")
                ssm_mod.ssm_init(mf, cfg)
        else:
            raise ValueError(cfg.arch_type)
        if cfg.mtp:
            mf = f.subfactory("mtp")
            mf.add("proj", (2 * cfg.d_model, cfg.d_model), (None, "model"))
            mf.add("norm", (cfg.d_model,), (None,), init="ones")
        return f.params, f.specs

    def _dense_block_init(self, f: ParamFactory, cfg: ModelConfig) -> None:
        f.add("ln1", (cfg.d_model,), (None,), init="ones")
        af = f.subfactory("attn")
        _attn_init(af, cfg)
        f.add("ln2", (cfg.d_model,), (None,), init="ones")
        mf = f.subfactory("mlp")
        mlp_init(mf, cfg)

    def _moe_block_init(self, f: ParamFactory, cfg: ModelConfig) -> None:
        f.add("ln1", (cfg.d_model,), (None,), init="ones")
        af = f.subfactory("attn")
        _attn_init(af, cfg)
        f.add("ln2", (cfg.d_model,), (None,), init="ones")
        mf = f.subfactory("moe")
        moe_mod.moe_init(mf, cfg)

    # --------------------------------------------------------------- forward --
    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        return jnp.take(params["embed"], tokens, axis=0)

    def logits(self, params: Params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        out = h @ head
        if cfg.padded_vocab != cfg.vocab_size:
            # mask the padding ids so sampling/softmax never sees them
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            out = jnp.where(pad_mask, jnp.asarray(-1e30, out.dtype), out)
        return out

    def _dense_block(self, p, cfg, x, positions):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = self._constrain(x + _attn_forward(p["attn"], cfg, h, positions, self.use_kernels, self.kv_hint))
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        return self._constrain(x + mlp_forward(p["mlp"], h))

    def _moe_fn(self, p, cfg, h):
        if self.moe_shard_map_mesh is not None:
            mesh = self.moe_shard_map_mesh
            dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
            return moe_mod.moe_forward_shard_map(p, cfg, h, mesh, dp_axes=dp)
        return moe_mod.moe_forward(p, cfg, h, self.moe_buf_spec)

    def _moe_block(self, p, cfg, x, positions):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = self._constrain(x + _attn_forward(p["attn"], cfg, h, positions, self.use_kernels, self.kv_hint))
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        out, aux = self._moe_fn(p["moe"], cfg, h)
        return self._constrain(x + out), aux

    def _hybrid_superblock(self, p, shared, cfg, x, positions):
        for i in range(cfg.shared_attn_every):
            mp = p[f"mamba_{i}"]
            h = rmsnorm(x, mp["ln"], cfg.norm_eps)
            x = x + ssm_mod.ssm_forward(mp, cfg, h)
        h = rmsnorm(x, shared["ln"], cfg.norm_eps)
        return x + _attn_forward(shared, cfg, h, positions, self.use_kernels)

    def forward(
        self,
        params: Params,
        tokens: Optional[jax.Array] = None,
        embeds: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward.  Returns (logits, aux_loss)."""
        h, aux = self.hidden(params, tokens=tokens, embeds=embeds)
        return self.logits(params, h), aux

    def hidden(
        self,
        params: Params,
        tokens: Optional[jax.Array] = None,
        embeds: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward up to (pre-final-norm) hidden states."""
        cfg = self.cfg
        if embeds is None:
            x = self.embed(params, tokens)
        else:
            x = embeds.astype(DTYPES[cfg.dtype])
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        aux_total = jnp.zeros((), jnp.float32)

        maybe_remat = jax.checkpoint if self.remat else (lambda fn: fn)

        if cfg.arch_type in ("dense", "vlm", "audio"):
            @maybe_remat
            def body(x, lp):
                return self._dense_block(lp, cfg, x, positions), None

            x, _ = jax.lax.scan(body, x, params["layers"])
        elif cfg.arch_type == "moe":
            for i in range(cfg.first_dense_layers):
                x = self._dense_block(params[f"dense_{i}"], cfg, x, positions)

            @maybe_remat
            def body(x, lp):
                x, aux = self._moe_block(lp, cfg, x, positions)
                return x, aux

            x, auxs = jax.lax.scan(body, x, params["layers"])
            aux_total = aux_total + jnp.sum(auxs)
        elif cfg.arch_type == "ssm":
            @maybe_remat
            def body(x, lp):
                h = rmsnorm(x, lp["ln"], cfg.norm_eps)
                return x + ssm_mod.ssm_forward(lp, cfg, h), None

            x, _ = jax.lax.scan(body, x, params["layers"])
        elif cfg.arch_type == "hybrid":
            shared = params["shared_attn"]

            @maybe_remat
            def body(x, lp):
                return self._hybrid_superblock(lp, shared, cfg, x, positions), None

            x, _ = jax.lax.scan(body, x, params["layers"])
        return x, aux_total

    # ----------------------------------------------------------------- cache --
    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        dtype = DTYPES[cfg.dtype]

        if cfg.arch_type in ("dense", "vlm", "audio"):
            return {
                "layers": _stacked(
                    cfg.num_layers, _attn_init_cache(cfg, batch, max_len, dtype)
                )
            }
        if cfg.arch_type == "moe":
            out: Params = {}
            for i in range(cfg.first_dense_layers):
                out[f"dense_{i}"] = _attn_init_cache(cfg, batch, max_len, dtype)
            out["layers"] = _stacked(
                cfg.num_layers - cfg.first_dense_layers,
                _attn_init_cache(cfg, batch, max_len, dtype),
            )
            return out
        if cfg.arch_type == "ssm":
            return {
                "layers": _stacked(
                    cfg.num_layers, ssm_mod.ssm_init_cache(cfg, batch, dtype)
                )
            }
        if cfg.arch_type == "hybrid":
            def superblock():
                c = {
                    f"mamba_{i}": ssm_mod.ssm_init_cache(cfg, batch, dtype)
                    for i in range(cfg.shared_attn_every)
                }
                c["attn"] = _attn_init_cache(cfg, batch, max_len, dtype)
                return c

            return {
                "layers": _stacked(cfg.num_layers // cfg.shared_attn_every, superblock())
            }
        raise ValueError(cfg.arch_type)

    def cache_specs(
        self, seq_axis: Optional[str] = None, dp: Optional[Tuple[str, ...]] = None
    ) -> Params:
        cfg = self.cfg
        dp = batch_spec(self.mesh_axes) if dp is None else dp

        def with_layer(spec_tree):
            return jax.tree.map(
                lambda s: P(*((None,) + tuple(s))), spec_tree,
                is_leaf=lambda x: isinstance(x, P),
            )

        a_specs = _attn_cache_specs(cfg, dp, seq_axis)
        if cfg.arch_type in ("dense", "vlm", "audio"):
            return {"layers": with_layer(a_specs)}
        if cfg.arch_type == "moe":
            out: Params = {}
            for i in range(cfg.first_dense_layers):
                out[f"dense_{i}"] = a_specs
            out["layers"] = with_layer(a_specs)
            return out
        if cfg.arch_type == "ssm":
            return {"layers": with_layer(ssm_mod.ssm_cache_specs(cfg, dp))}
        if cfg.arch_type == "hybrid":
            sb = {
                f"mamba_{i}": ssm_mod.ssm_cache_specs(cfg, dp)
                for i in range(cfg.shared_attn_every)
            }
            sb["attn"] = a_specs
            return {"layers": with_layer(sb)}
        raise ValueError(cfg.arch_type)

    # ---------------------------------------------------------------- prefill --
    def prefill(
        self,
        params: Params,
        tokens: Optional[jax.Array] = None,
        embeds: Optional[jax.Array] = None,
        lengths: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Params]:
        """Full-sequence serving prefill: last-token logits + the decode cache
        for every layer (stacked along the scan axis).

        ``lengths`` (B,) marks right-padded ragged rows (the serving engine
        pads prompts up to ``ssm_chunk`` alignment): logits come from each
        row's true last token, SSM states are exact via dt-masking (identity
        recurrence on padded steps), and attention cache rows past a row's
        length hold garbage the decode-side validity mask never reads."""
        cfg = self.cfg
        if embeds is None:
            x = self.embed(params, tokens)
        else:
            x = embeds.astype(DTYPES[cfg.dtype])
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        cache: Params = {}

        def attn_prefill(p, h):
            if cfg.attention_kind == "mla":
                return attn.mla_prefill(p, cfg, h, positions, self.use_kernels,
                                        kv_hint=self.kv_hint)
            return attn.gqa_prefill(p, cfg, h, positions, self.use_kernels,
                                    kv_hint=self.kv_hint)

        if cfg.arch_type in ("dense", "vlm", "audio"):
            def body(x, lp):
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, c = attn_prefill(lp["attn"], h)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                return x + mlp_forward(lp["mlp"], h), c

            x, cs = jax.lax.scan(body, x, params["layers"])
            cache["layers"] = cs
        elif cfg.arch_type == "moe":
            for i in range(cfg.first_dense_layers):
                lp = params[f"dense_{i}"]
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, c = attn_prefill(lp["attn"], h)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                x = x + mlp_forward(lp["mlp"], h)
                cache[f"dense_{i}"] = c

            def body(x, lp):
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, c = attn_prefill(lp["attn"], h)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                out, _ = self._moe_fn(lp["moe"], cfg, h)
                return x + out, c

            x, cs = jax.lax.scan(body, x, params["layers"])
            cache["layers"] = cs
        elif cfg.arch_type == "ssm":
            def body(x, lp):
                h = rmsnorm(x, lp["ln"], cfg.norm_eps)
                y, c = ssm_mod.ssm_prefill(lp, cfg, h, lengths)
                return x + y, c

            x, cs = jax.lax.scan(body, x, params["layers"])
            cache["layers"] = cs
        elif cfg.arch_type == "hybrid":
            shared = params["shared_attn"]

            def body(x, lp):
                c = {}
                for i in range(cfg.shared_attn_every):
                    mp = lp[f"mamba_{i}"]
                    h = rmsnorm(x, mp["ln"], cfg.norm_eps)
                    y, ci = ssm_mod.ssm_prefill(mp, cfg, h, lengths)
                    x = x + y
                    c[f"mamba_{i}"] = ci
                h = rmsnorm(x, shared["ln"], cfg.norm_eps)
                a, ca = attn_prefill(shared, h)
                c["attn"] = ca
                return x + a, c

            x, cs = jax.lax.scan(body, x, params["layers"])
            cache["layers"] = cs
        else:
            raise ValueError(cfg.arch_type)
        if lengths is None:
            last = x[:, -1:]
        else:
            last = x[jnp.arange(B), lengths - 1][:, None, :]
        return self.logits(params, last), cache

    # ----------------------------------------------------------------- decode --
    def decode_step(
        self, params: Params, cache: Params, token: jax.Array, pos: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """One ragged decode step.

        token: (B, 1) int32; pos: (B,) int32 per-slot positions — each slot's
        next cache index (== its current context length) — or a scalar, which
        broadcasts (the aligned-batch special case).  ``pos[b] < 0`` marks an
        idle/padding slot: its logits are still computed (batch shape is
        static) but every cache write for it is masked, so live slots can
        never corrupt an idle slot under continuous batching.
        Returns (logits, cache)."""
        cfg = self.cfg
        B = token.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        live = pos >= 0
        x = self.embed(params, token)
        new_cache: Params = {}

        if cfg.arch_type in ("dense", "vlm", "audio"):
            def body(x, xs):
                lp, lc = xs
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, nc = _attn_decode(lp["attn"], cfg, h, lc, pos, live)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                return x + mlp_forward(lp["mlp"], h), nc

            x, ncs = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.arch_type == "moe":
            for i in range(cfg.first_dense_layers):
                lp = params[f"dense_{i}"]
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, nc = _attn_decode(
                    lp["attn"], cfg, h, cache[f"dense_{i}"], pos, live
                )
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                x = x + mlp_forward(lp["mlp"], h)
                new_cache[f"dense_{i}"] = nc

            def body(x, xs):
                lp, lc = xs
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, nc = _attn_decode(lp["attn"], cfg, h, lc, pos, live)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                out, _ = self._moe_fn(lp["moe"], cfg, h)
                return x + out, nc

            x, ncs = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.arch_type == "ssm":
            def body(x, xs):
                lp, lc = xs
                h = rmsnorm(x, lp["ln"], cfg.norm_eps)
                y, nc = ssm_mod.ssm_decode(lp, cfg, h, lc, live)
                return x + y, nc

            x, ncs = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.arch_type == "hybrid":
            shared = params["shared_attn"]

            def body(x, xs):
                lp, lc = xs
                nc = {}
                for i in range(cfg.shared_attn_every):
                    mp = lp[f"mamba_{i}"]
                    h = rmsnorm(x, mp["ln"], cfg.norm_eps)
                    y, c = ssm_mod.ssm_decode(mp, cfg, h, lc[f"mamba_{i}"], live)
                    x = x + y
                    nc[f"mamba_{i}"] = c
                h = rmsnorm(x, shared["ln"], cfg.norm_eps)
                a, c = _attn_decode(shared, cfg, h, lc["attn"], pos, live)
                nc["attn"] = c
                return x + a, nc

            x, ncs = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        else:
            raise ValueError(cfg.arch_type)
        return self.logits(params, x), new_cache

    # ------------------------------------------------------------- paged KV --
    @property
    def supports_paged_kv(self) -> bool:
        """Paged decode covers the GQA serving hot path: architectures with
        full-attention GQA layers.  MLA's latent cache and the sliding-window
        ring keep the flat layout (reference fallback); pure-SSM models have
        no growing KV to page at all."""
        cfg = self.cfg
        return (
            cfg.arch_type != "ssm"
            and cfg.attention_kind == "gqa"
            and not cfg.sliding_window
        )

    def init_paged_cache(
        self, batch: int, num_pages: int, page_size: int, max_pages: int
    ) -> Params:
        """Cache pytree for :meth:`decode_step_paged`: per-layer page pools
        (one page id addresses a slab across all layers) plus the batch's
        page tables, which the engine refreshes host-side from its
        :class:`~repro.serving.paged_cache.PagePool` before each step."""
        cfg = self.cfg
        if not self.supports_paged_kv:
            raise ValueError(
                f"paged KV unsupported for arch_type={cfg.arch_type!r} / "
                f"attention_kind={cfg.attention_kind!r} / "
                f"sliding_window={cfg.sliding_window!r}"
            )
        dtype = DTYPES[cfg.dtype]

        def pools():
            return attn.gqa_init_paged_cache(cfg, num_pages, page_size, dtype)

        out: Params = {"page_tables": jnp.zeros((batch, max_pages), jnp.int32)}
        if cfg.arch_type in ("dense", "vlm", "audio"):
            out["layers"] = _stacked(cfg.num_layers, pools())
        elif cfg.arch_type == "moe":
            for i in range(cfg.first_dense_layers):
                out[f"dense_{i}"] = pools()
            out["layers"] = _stacked(cfg.num_layers - cfg.first_dense_layers, pools())
        elif cfg.arch_type == "hybrid":
            def superblock():
                c = {
                    f"mamba_{i}": ssm_mod.ssm_init_cache(cfg, batch, dtype)
                    for i in range(cfg.shared_attn_every)
                }
                c["attn"] = pools()
                return c

            out["layers"] = _stacked(cfg.num_layers // cfg.shared_attn_every, superblock())
        else:
            raise ValueError(cfg.arch_type)
        return out

    def decode_step_paged(
        self, params: Params, cache: Params, token: jax.Array, pos: jax.Array
    ) -> Tuple[jax.Array, Params]:
        """Like :meth:`decode_step` but with attention KV in page pools
        (``cache`` from :meth:`init_paged_cache`).  Same ragged contract:
        per-slot ``pos``, idle slots (``pos < 0``) never touch any cache.

        The layer scan carries the stacked pools and writes each layer's new
        k/v into them in place, so a donated cache is the step's only copy
        of the pool."""
        cfg = self.cfg
        B = token.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        live = pos >= 0
        pt = cache["page_tables"]
        uk = self.use_kernels
        x = self.embed(params, token)
        new_cache: Params = {"page_tables": pt}

        def attend(p, h, pools, layer=None):
            return attn.gqa_decode_paged(p, cfg, h, pools, pt, pos, live, uk, layer)

        if cfg.arch_type in ("dense", "vlm", "audio", "moe"):
            if cfg.arch_type == "moe":
                for i in range(cfg.first_dense_layers):
                    lp = params[f"dense_{i}"]
                    h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                    a, nc = attend(lp["attn"], h, cache[f"dense_{i}"])
                    x = x + a
                    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                    x = x + mlp_forward(lp["mlp"], h)
                    new_cache[f"dense_{i}"] = nc

            def body(carry, xs):
                x, pools = carry
                lp, i = xs
                h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
                a, pools = attend(lp["attn"], h, pools, i)
                x = x + a
                h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
                if cfg.arch_type == "moe":
                    out, _ = self._moe_fn(lp["moe"], cfg, h)
                else:
                    out = mlp_forward(lp["mlp"], h)
                return (x + out, pools), None

            pools = cache["layers"]
            n = jax.tree.leaves(pools)[0].shape[0]
            (x, pools), _ = jax.lax.scan(
                body, (x, pools), (params["layers"], jnp.arange(n))
            )
            new_cache["layers"] = pools
        elif cfg.arch_type == "hybrid":
            shared = params["shared_attn"]
            ssm_caches = {k: v for k, v in cache["layers"].items() if k != "attn"}

            def body(carry, xs):
                x, pools = carry
                lp, lc, i = xs
                nc = {}
                for j in range(cfg.shared_attn_every):
                    mp = lp[f"mamba_{j}"]
                    h = rmsnorm(x, mp["ln"], cfg.norm_eps)
                    y, c = ssm_mod.ssm_decode(mp, cfg, h, lc[f"mamba_{j}"], live)
                    x = x + y
                    nc[f"mamba_{j}"] = c
                h = rmsnorm(x, shared["ln"], cfg.norm_eps)
                a, pools = attend(shared, h, pools, i)
                return (x + a, pools), nc

            n = cfg.num_layers // cfg.shared_attn_every
            (x, pools), ncs = jax.lax.scan(
                body, (x, cache["layers"]["attn"]),
                (params["layers"], ssm_caches, jnp.arange(n)),
            )
            new_cache["layers"] = {**ncs, "attn": pools}
        else:
            raise ValueError(cfg.arch_type)
        return self.logits(params, x), new_cache

    # ------------------------------------------------------ prefill scatter --
    def scatter_prefill(
        self,
        cache: Params,
        prefill_cache: Params,
        slot: jax.Array,
        length: jax.Array,
        page_row: Optional[jax.Array] = None,
    ) -> Params:
        """Scatter a batch-1 :meth:`prefill` cache into slot ``slot`` of an
        engine batch cache (flat :meth:`init_cache` layout, or paged
        :meth:`init_paged_cache` layout when ``page_row`` — the slot's
        ``(max_pages,)`` page-table row, covering ≥ ``length`` tokens — is
        given).

        ``length`` is the true prompt length; prefill rows past it (bucket
        padding) are never copied.  ``slot`` and ``length`` may be traced, so
        one jit serves every slot and prompt length of a prefill bucket; the
        engine jits this with the cache donated, updating it in place."""
        return _scatter_node(
            cache, prefill_cache, slot, length, False, page_row
        )


# -- prefill-scatter helpers (the engine's jitted admit path) -----------------


def _scatter_leaf(eng, pre, slot, length, stacked):
    """Copy one batch-1 prefill leaf into an engine cache leaf at ``slot``.

    Leaves with a sequence axis (k/v/ckv/krope; engine seq length differs
    from the prefill's padded length) copy only the first ``length`` rows;
    fixed-shape state leaves (SSM conv/state) copy whole."""
    if stacked:
        return jax.vmap(lambda e, p: _scatter_leaf(e, p, slot, length, False))(
            eng, pre
        )
    pre = pre.astype(eng.dtype)
    if eng.ndim > 1 and eng.shape[1] != pre.shape[1]:
        n = min(eng.shape[1], pre.shape[1])
        row = jax.lax.dynamic_index_in_dim(eng, slot, 0, keepdims=False)
        keep = (jnp.arange(n) < length).reshape((n,) + (1,) * (row.ndim - 1))
        new = row.at[:n].set(jnp.where(keep, pre[0, :n], row[:n]))
        return jax.lax.dynamic_update_index_in_dim(eng, new, slot, 0)
    return jax.lax.dynamic_update_index_in_dim(eng, pre[0], slot, 0)


def _scatter_pages(pool, pre, page_row, length, stacked):
    """Scatter the first ``length`` prefill k/v rows into the slot's pages:
    token t lands in (page_row[t // page_size], t % page_size).  The index
    vector spans the whole padded prefill; rows past ``length`` aim at an
    out-of-range page and are dropped."""
    if stacked:
        return jax.vmap(
            lambda p, x: _scatter_pages(p, x, page_row, length, False)
        )(pool, pre)
    num_pages, ps = pool.shape[0], pool.shape[1]
    t = jnp.arange(pre.shape[1])
    pi = page_row[jnp.minimum(t // ps, page_row.shape[0] - 1)]
    pi = jnp.where(t < length, pi, num_pages)
    return pool.at[pi, t % ps].set(pre[0], mode="drop")


def _scatter_node(eng, pre, slot, length, stacked, page_row):
    if isinstance(eng, dict):
        out = {}
        for key, sub in eng.items():
            if key == "page_tables":
                out[key] = sub  # refreshed host-side by the engine
            elif key == "pool_k":
                out[key] = _scatter_pages(sub, pre["k"], page_row, length, stacked)
            elif key == "pool_v":
                out[key] = _scatter_pages(sub, pre["v"], page_row, length, stacked)
            else:
                out[key] = _scatter_node(
                    sub, pre[key], slot, length, stacked or key == "layers",
                    page_row,
                )
        return out
    return _scatter_leaf(eng, pre, slot, length, stacked)
