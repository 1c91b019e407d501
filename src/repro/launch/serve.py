"""Serving driver — the end-to-end example of the paper's kind.

Builds a model, wraps it in a serving :class:`Engine` (ragged continuous
batching over a paged KV cache where the architecture supports it), fires a
stream of batched requests, and reports throughput and latency.  It then
closes the paper's §8.3 loop: the measured throughput is fed into a
:class:`~repro.core.online_profiles.MeasuredProfile` wrapped around the
roofline profile the optimizer consumes, and the resulting correction
factor is printed.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 16 --batch 4 --new-tokens 8

Without ``--smoke`` the model runs at its full published width.  On a TPU
the attention runs in the Pallas kernels; elsewhere on the jnp path.
``chip_smoke.py`` at the repository root drives the same
:func:`build_engine` / :func:`make_requests` steps on one chip.
"""

from __future__ import annotations

import argparse
import json
import os
from pathlib import Path
from typing import List, Sequence

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.arch_bridge import tpu_arch_profiles
from repro.core.online_profiles import MeasuredProfile
from repro.models import Model
from repro.models.config import ModelConfig
from repro.serving import Engine, Request, run_closed_loop

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset:
# one fixed directory inside the checkout, so every run finds what earlier
# runs compiled (the path is part of the cache key; a moving one never hits).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    Call it before anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when
    set, is used as it is (JAX reads it itself, so nothing is set here);
    otherwise the cache lives at :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def build_engine(
    cfg: ModelConfig,
    *,
    use_kernels: bool,
    batch: int,
    max_len: int,
    kv_backend: str = "auto",
    page_size: int = 16,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
) -> Engine:
    """The model with seeded random weights, wrapped in a serving engine."""
    model = Model(cfg, remat=False, use_kernels=use_kernels)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return Engine(
        model, params, batch=batch, max_len=max_len,
        kv_backend=kv_backend, page_size=page_size,
        temperature=temperature, top_k=top_k,
    )


def make_requests(
    cfg: ModelConfig,
    n: int,
    prompt_lens: Sequence[int],
    new_tokens: int,
    seed: int = 0,
) -> List[Request]:
    """``n`` seeded requests of random prompt tokens; request ``i`` has a
    prompt of ``prompt_lens[i % len(prompt_lens)]`` tokens."""
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=rng.integers(
                1, cfg.vocab_size, size=prompt_lens[i % len(prompt_lens)]
            ).astype(np.int32),
            max_new_tokens=new_tokens,
        )
        for i in range(n)
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="the architecture's reduced smoke config instead of "
                         "its full published width")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--backend", choices=["auto", "flat", "paged"], default="auto")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--size", type=int, default=16,
                    help="slice size credited in the §8.3 profile feedback")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats-json", type=str, default=None, metavar="PATH",
                    help="write engine TTFT/TPOT stats as JSON in the same "
                         "metrics schema as the simulator's obs block "
                         "(docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    engine = build_engine(
        cfg, use_kernels=jax.devices()[0].platform == "tpu",
        batch=args.batch, max_len=args.max_len, kv_backend=args.backend,
        page_size=args.page_size, temperature=args.temperature,
        top_k=args.top_k, seed=args.seed,
    )
    reqs = make_requests(
        cfg, args.requests, [args.prompt_len], args.new_tokens, args.seed
    )
    measured = MeasuredProfile(tpu_arch_profiles([args.arch]))
    stats = run_closed_loop(
        engine, reqs, seed=args.seed,
        measured=measured, service=args.arch, size=args.size,
    )
    lat = [r.finished_s - r.submitted_s for r in reqs]
    print(
        f"arch={cfg.name} backend={engine.kv_backend} served={stats.served} "
        f"tokens={stats.tokens} preempted={stats.preempted} "
        f"wall={stats.wall_s:.2f}s tput={stats.throughput:.2f} req/s "
        f"p50_lat={np.percentile(lat, 50)*1e3:.0f}ms p90_lat={np.percentile(lat, 90)*1e3:.0f}ms"
    )
    if engine.pool is not None:
        print(
            f"pages={engine.pool.num_pages} free={engine.pool.free_pages} "
            f"page_size={engine.pool.page_size}"
        )
    print(
        f"§8.3 feedback: measured correction for ({args.arch}, size={args.size}) "
        f"= {measured.correction(args.arch, args.size):.4f}"
    )
    if args.stats_json:
        with open(args.stats_json, "w") as f:
            json.dump(stats.summary(args.arch), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"stats written to {args.stats_json}")


if __name__ == "__main__":
    main()
