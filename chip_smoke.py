"""One-chip smoke run of the serving path at full model width.

Builds ``phi4-mini-3.8b`` at its published width, with seeded random weights
and the Pallas kernels on, and serves 16 seeded requests through the paged
:class:`~repro.serving.Engine` with ``run_closed_loop`` — the steps
``python -m repro.launch.serve`` takes (``build_engine``, ``make_requests``).
It prints the device, compile seconds per program, one-chip smoke latencies,
KV pool and HBM use, whether the compiled decode and prefill contain the
Pallas kernel, and how many requests match a solo batch-1 decode.  It fails
with a non-zero exit when:

* JAX finds no TPU;
* the kernel is absent from the compiled decode step or prefill;
* a request ends with the wrong number of tokens, or a token out of range;
* the KV pool is not all free at the end;
* kernel-path logits differ from the jnp reference path by more than
  :data:`LOGIT_TOL`, for one decode step or for a prefill.

Run it from the repository root on a machine with one TPU::

    python chip_smoke.py

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Its timings come from one short run: smoke output, not benchmark results.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    enable_compile_cache,
    make_requests,
)
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving import Engine, Request, run_closed_loop  # noqa: E402
from repro.serving.engine import decode_fn  # noqa: E402

ARCH = "phi4-mini-3.8b"

# Largest |kernel - jnp reference| logit difference allowed.  It is the bound
# of tests/test_models.py::test_use_kernels_matches_jnp_path: the two paths
# differ only in where attention rounds to bf16 (the kernels keep scores and
# softmax in float32).  It holds at full width because the logits are
# unit-scale at any width (final rmsnorm, head init std 1/sqrt(d_model)) and
# the difference grows slowly with depth: 0.023 at 2 layers and 0.051 at 32 in
# an interpret-mode CPU comparison at d_model 256.
LOGIT_TOL = 0.15


class SmokeFailure(RuntimeError):
    pass


def _check_served(reqs: Sequence[Request], new_tokens: int, vocab: int) -> None:
    for r in reqs:
        if len(r.out_tokens) != new_tokens:
            raise SmokeFailure(
                f"request {r.rid}: {len(r.out_tokens)} tokens, wanted {new_tokens}"
            )
        bad = [t for t in r.out_tokens if not 0 <= t < vocab]
        if bad:
            raise SmokeFailure(f"request {r.rid}: tokens out of range {bad}")


def _check_pool_free(engine: Engine) -> None:
    if engine.pool.free_pages != engine.pool.num_pages:
        raise SmokeFailure(
            f"KV pool: {engine.pool.free_pages} of {engine.pool.num_pages} "
            f"pages free after every request finished"
        )


def _abs_err(a: jax.Array, b: jax.Array, vocab: int) -> np.ndarray:
    """|a - b| per logit over the real vocabulary (padding ids hold -1e30)."""
    a = np.asarray(a, np.float32)[..., :vocab]
    return np.abs(a - np.asarray(b, np.float32)[..., :vocab])


def _logits_check(
    engine: Engine, progs: Dict[str, Any], prompts: List[np.ndarray],
    check_len: int, seed: int, log: Callable[[str], None],
) -> Dict[str, float]:
    """Kernel path against the jnp reference path, on the same state."""
    model = engine.model
    ref = dataclasses.replace(model, use_kernels=False)
    vocab = model.cfg.vocab_size

    # Decode: every slot holds a prompt; one step through each path.  Both
    # steps are donating (a copy of the pool does not fit beside it) and
    # write the same tokens' k/v at the same positions, so each attends over
    # the same prefix; the engine's next step rewrites those rows again.
    check = [
        Request(rid=len(prompts) + i, prompt=prompts[i % len(prompts)],
                max_new_tokens=2)
        for i in range(engine.batch)
    ]
    for r in check:
        engine.admit(r)
    toks, pos = engine.decode_inputs()
    ref_logits, engine.cache = decode_fn(ref, "paged")(
        engine.params, engine.cache, toks, pos
    )
    kern_logits, engine.cache = progs["decode"][1](
        engine.params, engine.cache, toks, pos
    )
    row_err = _abs_err(kern_logits, ref_logits, vocab).max(axis=(1, 2))
    decode_err = float(row_err.max())
    while engine.num_live:
        engine.step()
    _check_served(check, 2, vocab)

    # Prefill of one tile-aligned prompt: flash kernel against the jnp path
    # (_naive_attention up to its 1024-token query block).
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(1, vocab, size=(1, check_len)), jnp.int32)
    lens = jnp.asarray([check_len], jnp.int32)
    kern, _ = progs[f"prefill[{engine.padded_len(check_len)}]"][1](
        engine.params, toks, lens
    )
    ref_out = jax.jit(lambda p, t, n: ref.prefill(p, tokens=t, lengths=n)[0])(
        engine.params, toks, lens
    )
    prefill_err = float(_abs_err(kern, ref_out, vocab).max())
    ref_real = np.asarray(ref_out, np.float32)[..., :vocab]
    rms = float(np.sqrt(np.mean(np.square(ref_real))))
    log(
        f"logits, kernel vs jnp reference: decode max|err| {decode_err} "
        f"(per slot {row_err.tolist()}), prefill[{check_len}] max|err| "
        f"{prefill_err}, reference rms {rms}, tolerance {LOGIT_TOL}"
    )
    for name, err in (("decode", decode_err), ("prefill", prefill_err)):
        if not err <= LOGIT_TOL:
            raise SmokeFailure(f"{name} logits: max|err| {err} > {LOGIT_TOL}")
    return {"decode_logit_err": decode_err, "prefill_logit_err": prefill_err}


def run(
    cfg: ModelConfig,
    *,
    batch: int = 8,
    max_len: int = 4096,
    page_size: int = 16,
    prompt_lens: Sequence[int] = (128, 512, 1024, 2048),
    n_requests: int = 16,
    new_tokens: int = 32,
    check_len: int = 1024,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """Serve ``n_requests`` seeded requests through the paged engine with
    kernels on, check them, and return what was measured.  Raises
    :class:`SmokeFailure` on a wrong result.  ``check_len`` is the prefill
    length of the logits check; it must be one of ``prompt_lens``' buckets."""
    t0 = time.perf_counter()
    engine = build_engine(
        cfg, use_kernels=True, batch=batch, max_len=max_len,
        kv_backend="paged", page_size=page_size, seed=seed,
    )
    jax.block_until_ready((engine.params, engine.cache))
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    p_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(engine.params))
    log(
        f"model {cfg.name}: layers {cfg.num_layers}, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; {n_params} params "
        f"({p_bytes / 1e9} GB {cfg.dtype}); seeded random weights"
    )
    log(
        f"engine: paged KV, batch {batch}, max_len {max_len}, page {page_size}, "
        f"pool {engine.pool.num_pages} pages; built in "
        f"{time.perf_counter() - t0} s"
    )

    progs = engine.compile(prompt_lens)
    for name, (secs, _) in progs.items():
        log(f"compile {name}: {secs} s")
    kernel = {
        name: "tpu_custom_call" in compiled.as_text()
        for name, (_, compiled) in progs.items()
        if not name.startswith("scatter")
    }
    log(f"tpu_custom_call in compiled program: {kernel}")

    reqs = make_requests(cfg, n_requests, prompt_lens, new_tokens, seed)
    stats = run_closed_loop(engine, reqs, seed=seed)
    _check_served(reqs, new_tokens, cfg.vocab_size)
    _check_pool_free(engine)
    log(
        f"one-chip smoke numbers, not benchmark results: {stats.served} "
        f"requests, {stats.tokens} tokens in {stats.wall_s} s = "
        f"{stats.tokens / stats.wall_s} tokens/s; TTFT median "
        f"{np.median(stats.ttft_s)} s, TPOT median "
        f"{np.median(stats.tpot_s)} s; preempted {stats.preempted}, "
        f"refused {stats.refused}"
    )

    errs = _logits_check(
        engine, progs, [r.prompt for r in reqs], check_len, seed, log
    )

    # The ragged oracle, as a report: each request decoded alone in a
    # batch-1 engine on the same weights.
    solo = Engine(
        engine.model, engine.params, batch=1, max_len=max_len,
        kv_backend="paged", page_size=page_size,
    )
    alone = [
        Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        for r in reqs
    ]
    run_closed_loop(solo, alone, seed=seed)
    # index of the first token that differs, None where all match
    first_diff = [
        next((i for i, (x, y) in enumerate(zip(a.out_tokens, b.out_tokens))
              if x != y), None)
        for a, b in zip(reqs, alone)
    ]
    matches = first_diff.count(None)
    log(f"ragged oracle: {matches} of {len(reqs)} requests match a solo "
        f"batch-1 decode token for token; first differing token per "
        f"request: {first_diff}")
    del solo

    _check_pool_free(engine)
    log(f"KV pool: {engine.pool.num_pages} pages, {engine.pool.free_pages} "
        f"free at the end")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"HBM: peak_bytes_in_use {mem.get('peak_bytes_in_use')}, "
        f"bytes_limit {mem.get('bytes_limit')}")
    return {
        "kernel": kernel,
        "served": stats.served,
        "pool_pages": engine.pool.num_pages,
        "free_pages": engine.pool.free_pages,
        "solo_matches": matches,
        **errs,
    }


def main() -> int:
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is a "
              f"{dev.platform!r} device)", file=sys.stderr)
        return 1
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}", flush=True)
    res = run(get_config(ARCH), log=lambda s: print(s, flush=True))
    missing = [name for name, has in res["kernel"].items() if not has]
    if missing:
        raise SmokeFailure(f"no tpu_custom_call in compiled {missing}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
