"""Serving engine: ragged continuous batching on one instance.

An :class:`Engine` is what MIG-Serving schedules onto a GPU instance / TPU
slice: it owns the model params, a fixed-capacity batch of request *slots*,
and jit'd ``prefill`` / ``decode`` steps.  Requests join free slots; admission
runs the jit'd batch-1 :meth:`~repro.models.Model.prefill` over the prompt
and scatters the resulting cache into the slot (other slots are never
touched); every decode step advances all live slots by one token at their
*own* positions (``Model.decode_step`` takes a per-slot ``(B,)`` position
vector, with masked cache writes for idle slots).

Two KV backends:

* ``paged`` (default where supported) — attention KV lives in fixed-size
  pages from a shared :class:`~repro.serving.paged_cache.PagePool`; the
  slot's HBM budget maps to ``num_pages`` (:func:`page_hbm_bytes`), and pool
  exhaustion is an explicit signal: admission is *refused* (``OutOfPages``
  propagates to the caller) and a request that cannot grow mid-decode is
  *preempted* — its pages are released and it restarts later with its
  generated tokens folded into the prompt.  Nothing is ever silently
  clamped or overwritten.
* ``flat`` — the dense per-slot ``(B, max_len, ...)`` cache, kept as the
  reference fallback (and the only layout for MLA latent caches and
  sliding-window rings; pure-SSM models have no growing KV, so both backend
  names select their fixed-size state cache).

Sampling is seeded and explicit: ``temperature == 0`` (default) is argmax —
the deterministic mode the ragged oracle tests pin — otherwise
temperature/top-k sampling draws from the ``rng`` passed to
:meth:`Engine.step` / :meth:`Engine.admit`.

The batch capacity is chosen by the scheduler per the paper's rule: "the
largest batch size possible, as far as the inference latency is smaller than
what required by SLOs" (§7).  :func:`run_closed_loop` closes the paper's
§8.3 loop: measured throughput feeds a
:class:`~repro.core.online_profiles.MeasuredProfile` so the optimizer
consumes production-corrected profiles.

Host phases are observable on the wall clock.  Each phase of
:meth:`Engine.step` and :meth:`Engine.admit` runs inside an ``engine.<phase>``
span (:func:`jax.profiler.TraceAnnotation`), which lands in the profiler's
host trace, on the device trace's clock, when a profiler is attached; its
``time.perf_counter`` duration goes into :attr:`Engine.last_phases`,
replaced by every call.  :attr:`Engine.counters` counts decode steps,
preemptions, admission refusals and the paged kernel's blocks where they
happen.  Request stamps use the same ``time.perf_counter`` clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import Model
from repro.models.common import DTYPES
from repro.models.config import ModelConfig
from repro.models.kernels_bridge import FLASH_TILE
from repro.serving.paged_cache import OutOfPages, PagePool, page_bytes


@dataclasses.dataclass(eq=False)
class Request:
    # eq=False: requests are identity-compared.  A generated __eq__ would
    # tuple-compare fields including the numpy ``prompt``, so two distinct
    # requests sharing a rid would make ``pending.remove(req)`` raise on the
    # ambiguous array truth value instead of removing the right object.
    rid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    # time.perf_counter stamps: ``submitted_s`` by the caller as the request
    # is queued (the engine never sets it; TTFT is counted from it), the
    # other two by the engine
    submitted_s: float = 0.0
    first_token_s: float = 0.0
    finished_s: float = 0.0

    @property
    def done(self) -> bool:
        return len(self.out_tokens) >= self.max_new_tokens


def attn_layer_count(cfg: ModelConfig) -> int:
    """Number of layers holding a growing attention KV cache."""
    if cfg.arch_type == "ssm":
        return 0
    if cfg.arch_type == "hybrid":
        return cfg.num_layers // cfg.shared_attn_every
    return cfg.num_layers


def decode_fn(model: Model, kv_backend: str):
    """The engine's jitted decode step for ``kv_backend`` (``"paged"`` or
    ``"flat"``).  The cache is donated: the step writes the new token's k/v
    into the pool in place instead of returning a second copy of it."""
    step = model.decode_step_paged if kv_backend == "paged" else model.decode_step
    return jax.jit(step, donate_argnums=(1,))


def page_hbm_bytes(cfg: ModelConfig, page_size: int, dtype_bytes: int = 2) -> int:
    """HBM cost of ONE logical page for this architecture — the unit a
    slice's HBM budget is divided by to get ``num_pages``."""
    return page_bytes(
        page_size, cfg.num_kv_heads, cfg.head_dim,
        attn_layer_count(cfg), dtype_bytes,
    )


@contextlib.contextmanager
def _phase(phases: Dict[str, float], name: str) -> Iterator[None]:
    """One host phase of an engine call: an ``engine.<name>`` span in the
    profiler's trace (a no-op with no profiler attached), and its wall time
    added to ``phases[name]``."""
    t0 = time.perf_counter()
    try:
        with TraceAnnotation("engine." + name):
            yield
    finally:
        phases[name] = phases.get(name, 0.0) + time.perf_counter() - t0


class Engine:
    def __init__(
        self,
        model: Model,
        params: Any,
        batch: int,
        max_len: int,
        *,
        kv_backend: str = "auto",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
    ):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.temperature = temperature
        self.top_k = top_k
        # events counted where they happen: decode steps run, requests
        # preempted, admissions refused with OutOfPages, and the blocks of
        # pages the paged kernel walks per layer (see decode_inputs)
        self.counters: Dict[str, int] = dict.fromkeys(
            ("steps", "preempted", "refused", "kv_blocks"), 0)
        # seconds per host phase of the last step() or admit() call
        self.last_phases: Dict[str, float] = {}
        self.slots: List[Optional[Request]] = [None] * batch
        # per-slot context length; -1 marks an idle slot (the decode-side
        # convention: negative position => masked cache writes)
        self.slot_pos = np.full(batch, -1, np.int32)
        self._finished: List[Request] = []
        self._preempted: List[Request] = []

        cfg = self.cfg
        if cfg.sliding_window and cfg.sliding_window < max_len:
            raise NotImplementedError(
                "Engine does not serve sliding-window ring caches; use the "
                "flat decode path directly (repro.launch.specs long_500k)"
            )
        if kv_backend == "auto":
            backend = "paged" if model.supports_paged_kv else "flat"
        elif kv_backend == "paged" and not model.supports_paged_kv:
            if cfg.arch_type == "ssm":
                backend = "flat"  # no growing KV to page: state cache as-is
            else:
                raise ValueError(
                    f"paged KV unsupported for {cfg.name}: "
                    f"attention_kind={cfg.attention_kind!r}"
                )
        elif kv_backend in ("paged", "flat"):
            backend = kv_backend
        else:
            raise ValueError(f"unknown kv_backend {kv_backend!r}")
        self.kv_backend = backend

        if backend == "paged":
            max_pages_per_req = -(-max_len // page_size)  # ceil
            if num_pages is None:
                if hbm_budget_bytes is not None:
                    num_pages = hbm_budget_bytes // max(1, page_hbm_bytes(cfg, page_size))
                else:
                    num_pages = batch * max_pages_per_req
            if num_pages < 1:
                raise ValueError(
                    f"HBM budget yields num_pages={num_pages}; need >= 1"
                )
            self.pool: Optional[PagePool] = PagePool(
                num_pages, page_size, max_pages_per_req
            )
            self.cache = model.init_paged_cache(
                batch, num_pages, page_size, max_pages_per_req
            )
            # lazy: kernels are optional at import
            from repro.kernels.paged_attention import pages_per_block

            self.block_tokens = page_size * pages_per_block(
                page_size, cfg.num_kv_heads, cfg.head_dim,
                jnp.dtype(DTYPES[cfg.dtype]).itemsize, max_pages_per_req)
        else:
            self.pool = None
            self.cache = model.init_cache(batch, max_len)
        self._decode = decode_fn(model, backend)

        def prefill(p, toks, lens):
            return model.prefill(p, tokens=toks, lengths=lens)

        self._prefill = jax.jit(prefill)
        # admission writes the prefill cache into the donated engine cache
        self._scatter = jax.jit(model.scatter_prefill, donate_argnums=(0,))
        # Prompts are right-padded (exact — dt-masked SSM states, masked-out
        # attention rows, true-last-token logits; see Model.prefill) so the
        # jit'd prefill compiles one trace per length *bucket*, not per
        # distinct prompt/resume length.  SSM needs chunk alignment anyway;
        # MoE must see exact lengths because padded tokens would compete for
        # expert capacity and perturb real-token outputs.  With kernels on,
        # buckets are whole flash tiles so prefill never leaves the kernel.
        if cfg.arch_type in ("ssm", "hybrid"):
            self._pad_to = cfg.ssm_chunk
        elif cfg.arch_type == "moe":
            self._pad_to = 1
        elif model.use_kernels:
            self._pad_to = FLASH_TILE
        else:
            self._pad_to = 16

    def padded_len(self, length: int) -> int:
        """The prefill bucket a context of ``length`` tokens is padded to."""
        return -(-length // self._pad_to) * self._pad_to

    def compile(self, prompt_lens: Sequence[int]) -> Dict[str, Tuple[float, Any]]:
        """Compile ahead of time every program that serving contexts of these
        lengths runs: the decode step, and prefill + cache scatter for each
        padded length.  Later calls with the same shapes reuse these
        executables.  Returns ``{program: (compile seconds, compiled)}``."""
        out: Dict[str, Tuple[float, Any]] = {}

        def aot(name, fn, *args):
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            out[name] = (time.perf_counter() - t0, compiled)

        toks = jnp.zeros((self.batch, 1), jnp.int32)
        pos = jnp.full((self.batch,), -1, jnp.int32)
        aot("decode", self._decode, self.params, self.cache, toks, pos)
        row = (
            jnp.zeros((self.pool.max_pages_per_req,), jnp.int32)
            if self.pool is not None
            else None
        )
        for pad in sorted({self.padded_len(n) for n in prompt_lens}):
            ptoks = jax.ShapeDtypeStruct((1, pad), jnp.int32)
            lens = jax.ShapeDtypeStruct((1,), jnp.int32)
            aot(f"prefill[{pad}]", self._prefill, self.params, ptoks, lens)
            _, pcache = jax.eval_shape(self._prefill, self.params, ptoks, lens)
            aot(f"scatter[{pad}]", self._scatter, self.cache, pcache,
                np.int32(0), np.int32(1), row)
        return out

    # -- introspection --------------------------------------------------------
    def has_free_slot(self) -> bool:
        return any(s is None for s in self.slots)

    @property
    def num_live(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def steps(self) -> int:
        """Decode steps run so far (``counters["steps"]``)."""
        return self.counters["steps"]

    def take_preempted(self) -> List[Request]:
        """Requests evicted on pool exhaustion since the last call; re-admit
        them (their generated tokens resume from the prompt) once capacity
        frees up."""
        out, self._preempted = self._preempted, []
        return out

    # -- admission ------------------------------------------------------------
    def admit(self, req: Request, rng: Optional[np.random.Generator] = None) -> int:
        """Admit one request: batch-1 jit'd prefill over its context, cache
        scattered into a free slot, first output token sampled from the
        prefill logits.

        Raises :class:`OutOfPages` (paged backend) when the pool cannot hold
        the context plus one decode token — the admission-control signal; the
        request is left untouched for the caller to retry later.

        The caller stamps ``req.submitted_s`` (``time.perf_counter``) when it
        queues the request; an unstamped request's TTFT has no start."""
        ctx = np.asarray(req.prompt, np.int32)
        if req.out_tokens:  # resuming after preemption
            ctx = np.concatenate([ctx, np.asarray(req.out_tokens, np.int32)])
        L = int(ctx.size)
        if L < 1:
            raise ValueError("empty prompt")
        if L + 1 > self.max_len:
            raise ValueError(
                f"context length {L} does not fit max_len={self.max_len}"
            )
        slot = self.slots.index(None)
        self.last_phases = phases = {}
        with TraceAnnotation("engine.admit", rid=req.rid, ctx=L):
            with _phase(phases, "reserve"):
                if self.pool is not None:
                    self.pool.admit(req.rid)
                    try:
                        # context + room for the first decode write (so an
                        # admitted request can always take at least one step)
                        self.pool.append_tokens(req.rid, L + 1)
                    except OutOfPages:
                        self.pool.release(req.rid)
                        self.counters["refused"] += 1
                        raise
            try:
                with _phase(phases, "dispatch"):
                    toks = np.zeros((1, self.padded_len(L)), np.int32)
                    toks[0, :L] = ctx
                    logits, pcache = self._prefill(
                        self.params, jnp.asarray(toks), jnp.asarray([L], jnp.int32)
                    )
                    page_row = (
                        jnp.asarray(self.pool.tables([req.rid])[0][0])
                        if self.pool is not None
                        else None
                    )
                    self.cache = self._scatter(
                        self.cache, pcache, np.int32(slot), np.int32(L), page_row
                    )
                    logits = logits.astype(jnp.float32)
                self.slots[slot] = req
                self.slot_pos[slot] = L
                with _phase(phases, "wait"):
                    logits.block_until_ready()
                with _phase(phases, "fetch"):
                    row = np.asarray(logits)[0, 0]
                with _phase(phases, "sample"):
                    req.out_tokens.append(self._sample(row, rng))
                    if req.first_token_s == 0.0:
                        req.first_token_s = time.perf_counter()
            except BaseException:
                # prefill/scatter/sampling failed after the pages were
                # reserved: undo the reservation (free list byte-identical,
                # stale rid entry dropped so a retry of the same rid
                # re-admits cleanly) and free the slot — the OutOfPages
                # contract says a failed admission leaves the engine
                # untouched.
                self.slots[slot] = None
                self.slot_pos[slot] = -1
                if self.pool is not None:
                    self.pool.abort(req.rid)
                raise
            if req.done:
                self._finish(slot)
        return slot

    # -- decode ---------------------------------------------------------------
    def step(self, rng: Optional[np.random.Generator] = None) -> List[Request]:
        """One ragged decode step for all live slots; returns finished
        requests (including any that completed at admission since the last
        step).  Paged backend: slots that cannot allocate their next token's
        page are preempted first (see :meth:`take_preempted`)."""
        finished, self._finished = self._finished, []
        self.last_phases = phases = {}
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return finished
        with TraceAnnotation("engine.step", rows=len(live)):
            with _phase(phases, "grow"):
                if self.pool is not None:
                    for i in list(live):
                        req = self.slots[i]
                        need = (int(self.slot_pos[i]) + 1
                                - self.pool.request(req.rid).length)
                        if need > 0:
                            try:
                                self.pool.append_tokens(req.rid, need)
                            except OutOfPages:
                                self._preempt(i)
                                live.remove(i)
            if not live:
                return finished
            with _phase(phases, "inputs"):
                toks, pos = self.decode_inputs()
            with _phase(phases, "dispatch"):
                logits, self.cache = self._decode(self.params, self.cache, toks, pos)
                logits = logits.astype(jnp.float32)
            with _phase(phases, "wait"):
                logits.block_until_ready()
            with _phase(phases, "fetch"):
                lg = np.asarray(logits)
            with _phase(phases, "sample"):
                for i in live:
                    req = self.slots[i]
                    self.slot_pos[i] += 1
                    req.out_tokens.append(self._sample(lg[i, 0], rng))
                    if req.done or self.slot_pos[i] >= self.max_len:
                        self._finish(i)
        self.counters["steps"] += 1
        finished.extend(self._finished)
        self._finished = []
        return finished

    def decode_inputs(self) -> Tuple[jax.Array, jax.Array]:
        """``(tokens (B, 1), positions (B,))`` of the next decode step: each
        live slot's last token at its own position, ``-1`` for idle slots.
        Paged backend: the cache's page tables are refreshed first (the
        caller has already grown every live slot's pages), and
        ``counters["kv_blocks"]`` grows by the blocks the paged kernel walks
        in each layer: ``cdiv(length, block_tokens)`` per row, where a row's
        length is its position + 1 (0 for an idle slot)."""
        if self.pool is not None:
            self._refresh_page_tables()
        toks = np.zeros((self.batch, 1), np.int32)
        pos = np.full(self.batch, -1, np.int32)
        for i, req in enumerate(self.slots):
            if req is not None:
                toks[i, 0] = req.out_tokens[-1]
                pos[i] = self.slot_pos[i]
        if self.pool is not None:
            self.counters["kv_blocks"] += int(np.sum(-(-(pos + 1) // self.block_tokens)))
        return jnp.asarray(toks), jnp.asarray(pos)

    # -- internals ------------------------------------------------------------
    def _sample(
        self, logits_row: np.ndarray, rng: Optional[np.random.Generator]
    ) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        if rng is None:
            raise ValueError("temperature > 0 requires an rng")
        z = logits_row.astype(np.float64) / self.temperature
        if self.top_k and self.top_k < z.size:
            # exactly k candidates: a >= kth-value cut would keep *every*
            # logit tied with the k-th and sample from more than k on ties.
            # Stable sort makes the tie order deterministic (lowest index
            # wins), so seeded runs stay reproducible.
            keep = np.argsort(-z, kind="stable")[: self.top_k]
            cut = np.full_like(z, -np.inf)
            cut[keep] = z[keep]
            z = cut
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(z.size, p=p))

    def _finish(self, slot: int) -> None:
        req = self.slots[slot]
        req.finished_s = time.perf_counter()
        self.slots[slot] = None
        self.slot_pos[slot] = -1
        if self.pool is not None:
            self.pool.release(req.rid)
        self._finished.append(req)

    def _preempt(self, slot: int) -> None:
        req = self.slots[slot]
        # Re-admission prefills prompt + out_tokens (slot_pos + 1 tokens) and
        # needs one more decode position; a request already at the context
        # cap cannot resume — finish it truncated, exactly as the
        # non-preempted max_len path would.
        if int(self.slot_pos[slot]) + 2 > self.max_len:
            self._finish(slot)
            return
        self.slots[slot] = None
        self.slot_pos[slot] = -1
        self.pool.release(req.rid)
        self._preempted.append(req)
        self.counters["preempted"] += 1

    def _refresh_page_tables(self) -> None:
        rids = [s.rid if s is not None else None for s in self.slots]
        pt, _ = self.pool.tables(rids)
        self.cache["page_tables"] = jnp.asarray(pt)


@dataclasses.dataclass
class ServeStats:
    served: int = 0
    tokens: int = 0
    preempted: int = 0
    refused: int = 0  # OutOfPages admission refusals (request stays pending)
    wall_s: float = 0.0
    # per-request latency observations (wall clock): time-to-first-token
    # from submission and mean time-per-output-token — the measured twins of
    # the token-level serving model's TTFT/TPOT metrics (repro.sim.servemodel)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    tpot_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def throughput(self) -> float:
        return self.served / self.wall_s if self.wall_s else 0.0

    def summary(self, service: str = "engine") -> Dict[str, Any]:
        """The engine-side stats in the simulator's ``obs`` metrics schema
        (``launch/serve.py --stats-json`` writes exactly this), so real-run
        and simulated TTFT/TPOT read side by side: counters under the
        ``serving.*`` names the :class:`repro.obs.MetricsRegistry` uses,
        latency percentiles via the shared ``percentile_summary`` keys."""
        from repro.obs.metrics import percentile_summary

        return {
            "service": service,
            "counters": {
                "serving.completed": float(self.served),
                "serving.preemptions": float(self.preempted),
                "serving.refusals": float(self.refused),
                "serving.tokens": float(self.tokens),
            },
            "latency": {
                **percentile_summary(self.ttft_s, "ttft"),
                **percentile_summary(self.tpot_s, "tpot"),
            },
            "throughput_rps": self.throughput,
            "wall_s": self.wall_s,
        }


def run_closed_loop(
    engine: Engine,
    requests: List[Request],
    seed: int = 0,
    measured: Optional[Any] = None,  # repro.core.online_profiles.MeasuredProfile
    service: Optional[str] = None,
    size: Optional[int] = None,
) -> ServeStats:
    """Admit-and-decode until all requests finish (the Engine's test driver).

    Preempted requests are re-queued at the front (their generated tokens
    resume from the prompt); admission refusals (``OutOfPages``) leave the
    request pending until capacity frees up.  When ``measured`` (a
    :class:`~repro.core.online_profiles.MeasuredProfile`) plus ``service``
    and ``size`` are given, the measured throughput is fed back into the
    profile — the paper's §8.3 production-measurement loop.

    Every request not yet stamped gets ``submitted_s`` as it enters the
    pending queue, so TTFT counts the queue wait.  ``preempted`` and
    ``refused`` are the engine's own counters over the run."""
    rng = np.random.default_rng(seed)
    stats = ServeStats()
    before = dict(engine.counters)
    t0 = time.perf_counter()
    for req in requests:
        if not req.submitted_s:
            req.submitted_s = t0
    pending = list(requests)
    while stats.served < len(requests):
        admitted = False
        # first-fit admission: a request the pool cannot hold right now must
        # not block admittable requests queued behind it
        for req in list(pending):
            if not engine.has_free_slot():
                break
            try:
                engine.admit(req, rng)
            except OutOfPages:
                continue
            pending.remove(req)
            admitted = True
        finished = engine.step(rng)
        for req in finished:
            stats.served += 1
            stats.tokens += len(req.out_tokens)
            if req.first_token_s > 0.0:
                stats.ttft_s.append(req.first_token_s - req.submitted_s)
                if len(req.out_tokens) > 1:
                    stats.tpot_s.append(
                        (req.finished_s - req.first_token_s)
                        / (len(req.out_tokens) - 1)
                    )
        preempted = engine.take_preempted()
        pending = preempted + pending
        # Stuck only if this iteration made no progress of any kind —
        # a preemption frees pages the next admission pass can use.
        if (not finished and not admitted and not preempted
                and engine.num_live == 0 and pending):
            raise RuntimeError(
                f"requests {[r.rid for r in pending]} cannot be admitted: "
                f"page pool too small for their contexts"
            )
    stats.wall_s = time.perf_counter() - t0
    stats.preempted = engine.counters["preempted"] - before["preempted"]
    stats.refused = engine.counters["refused"] - before["refused"]
    if measured is not None and service is not None and size is not None:
        if stats.wall_s > 0:
            measured.observe(service, size, engine.batch, stats.throughput)
    return stats
