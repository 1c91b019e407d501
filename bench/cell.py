"""One run of one cell: build, warm up, measure, trace, check.

:func:`run_cell` is what ``bench/run.py`` calls once it has found the chip;
the tests call it on the CPU at a small size, and ``bench/calibrate.py``
and ``bench/sweep.py`` call its parts on the chip.

Order of a run:

1. the weights, drawn on the device from ``--seed``, laid into the program's
   ``Model`` and a paged ``Engine`` with the Pallas kernels on;
2. the decode step, and prefill plus scatter for every bucket that a
   context of this cell can reach, compiled (or loaded from JAX's
   persistent cache);
3. the cell's own traffic until the batch is at steady occupancy;
4. the window of ``--seconds``: the last ``TRACE_SECONDS`` of it traced
   when ``--trace 1``;
5. ``memory_peak_bytes`` read, the program's state freed, and the
   reference run over a sample of the finished requests.

Steps 1 to 3 are ``setup_s``.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import numpy as np

import trace_reduce
import weights as wmod
from driver import Driver, Iteration, Tracked
from reference import Reference, widest
from traffic import arrivals

from repro.models import Model
from repro.models.config import ModelConfig
from repro.serving import Engine

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PAGE_SIZE = 16  # tokens per KV page, in every cell
TRACE_SECONDS = 4.0  # the traced tail of a ``--trace 1`` window


class CompileCounter:
    """Counts JAX compilations (a compile, or a load from the persistent
    cache) while ``active``."""

    def __init__(self) -> None:
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs: Any) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class TraceView:
    device: List[trace_reduce.Event]
    spans: List[trace_reduce.Event]
    window_s: float  # host clock, first traced call to last
    busy_s: float
    iters: List[Iteration]  # the calls made inside the trace


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    m: Mapping[str, Any]  # the configuration's model block
    arch: Any  # its architecture module, bench/arch/<arch>.py
    peak: Any  # bench.peaks.Peak of the device
    setup_s: float
    w0: float
    w1: float
    iters: List[Iteration]  # window calls timed by the host, none traced
    tracked: List[Tracked]
    compiles: int
    num_pages: int  # of the KV pool
    trace: Optional[TraceView]


def build_engine(arch: Any, m: Mapping, sizes: Mapping, seed: int) -> Engine:
    """The cell's engine, on the weights that architecture module ``arch``
    draws for ``m``.  ``sizes`` gives ``batch`` and ``max_len``, and
    ``kv_pool_bytes`` where the pool is sized from the chip's memory rather
    than as ``batch`` rows of ``max_len``."""
    model = Model(ModelConfig(**m), remat=False, use_kernels=True)
    abstract, _ = model.init(None, abstract=True)
    params = wmod.program_params(abstract, wmod.make_weights(arch, m, seed))
    return Engine(model, params, batch=sizes["batch"], max_len=sizes["max_len"],
                  kv_backend="paged", page_size=PAGE_SIZE,
                  hbm_budget_bytes=sizes.get("kv_pool_bytes"))


def prefill_buckets(engine: Engine, mix: Mapping) -> List[int]:
    """Every prefill bucket a context of this cell can reach: from the
    shortest prompt to the longest, or to ``max_len`` where the pool is too
    small for every slot at ``max_len`` and a preempted request can resume
    with a longer context."""
    pool = engine.pool
    lo = int(mix["prompt_len"]["min"])
    hi = int(mix["prompt_len"]["max"])
    if pool.num_pages < engine.batch * pool.max_pages_per_req:
        hi = engine.max_len - 1
    return sorted({engine.padded_len(n) for n in range(lo, hi + 1)})


def warm_traffic(drv: Driver, mix: Mapping) -> None:
    """Run the cell's own traffic before the window: for a backlog, which
    fills the batch before its first step, ``steps`` decode steps; for
    timed arrivals, ``seconds`` of them."""
    w = mix["warmup"]
    drv.start()
    if "seconds" in w:
        end = drv.origin + float(w["seconds"])
        drv.run_until(lambda: drv.clock() >= end)
    else:
        drv.run_until(lambda: drv.steps >= int(w["steps"]))


def measure(drv: Driver, seconds: float, counter: CompileCounter,
            trace_seconds: float = TRACE_SECONDS, trace_dir: Optional[str] = None):
    """The window: iterate until ``seconds`` have passed, ending at the end
    of the call that crosses them.  With ``trace_dir``, the window's last
    ``trace_seconds`` run under the profiler, starting at a call boundary.
    Returns ``(w0, w1, first traced call index, traced window seconds)``."""
    tracing, tr_i, tr0 = False, None, None
    counter.count, counter.active = 0, True
    w0 = drv.clock()
    start_at = w0 + seconds - trace_seconds
    first = len(drv.iters)
    while drv.clock() < w0 + seconds:
        if trace_dir is not None and not tracing and drv.clock() >= start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, tr_i, tr0 = True, len(drv.iters), drv.clock()
        drv.iterate()
    w1 = drv.clock()
    counter.active = False
    if tracing:
        jax.profiler.stop_trace()
    return w0, w1, first, tr_i, (w1 - tr0) if tracing else None


def sample(tracked: Sequence[Tracked], w0: float, w1: float, seed: int,
           tokens: int, max_requests: int) -> List[Tracked]:
    """Requests finished inside the window to check against the reference:
    the one with the most served tokens, then others in an order drawn
    from ``seed`` until ``tokens`` served tokens or ``max_requests``."""
    done = sorted((t for t in tracked
                   if t.finished_at is not None and w0 <= t.finished_at <= w1),
                  key=lambda t: t.req.rid)
    if not done:
        return []
    longest = max(done, key=lambda t: len(t.req.out_tokens))
    rest = [t for t in done if t is not longest]
    picked, n = [longest], len(longest.req.out_tokens)
    for i in np.random.default_rng([seed, 1]).permutation(len(rest)):
        if n >= tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i].req.out_tokens)
    return picked


def check(arch: Any, m: Mapping, seed: int, picked: Sequence[Tracked], failed: int,
          limits: Mapping[str, float], control: bool = False) -> Dict[str, Dict]:
    """Compare the served tokens with the plain reference of architecture
    module ``arch``.  Returns each number compared with its limit.
    ``control`` puts the fp8 control in the program's place over the same
    prompts and tokens: its widest gap is held to the served tokens' limit,
    so a control run is not correct."""
    V = m["vocab_size"]
    bad = sum(
        int(len(t.req.out_tokens) != t.req.max_new_tokens)
        + int(sum(1 for x in t.req.out_tokens if not 0 <= x < V))
        for t in picked
    )
    out: Dict[str, Dict] = {}
    gap = None
    if picked and not bad:
        w = wmod.make_weights(arch, m, seed)
        gaps = Reference(arch, m).token_gaps(
            w, [(t.req.prompt, t.req.out_tokens) for t in picked], control)
        del w
        gap = widest(gaps, "served")
        if control:
            out["control_gap_max"] = {"value": widest(gaps, "control"),
                                      "limit": limits["served_gap_max"]}
    out["served_gap_max"] = {"value": gap, "limit": limits["served_gap_max"]}
    out["checked_tokens"] = {"value": sum(len(t.req.out_tokens) for t in picked),
                             "limit": None}
    out["bad_tokens"] = {"value": bad, "limit": 0}
    out["failed_requests"] = {"value": failed, "limit": 0}
    return out


def passed(checks: Mapping[str, Mapping]) -> bool:
    """Every compared number at most its limit (a number with no limit is a
    reading shown beside them, not compared)."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values() if c["limit"] is not None)


def peak_memory() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def reduce_trace(trace_dir: str, iters: List[Iteration], window_s: float) -> TraceView:
    device, spans = trace_reduce.read_xplane(trace_dir)
    return TraceView(device, spans, window_s,
                     trace_reduce.busy_seconds(device), iters)


def run_cell(
    arch: Any, m: Mapping, mix: Mapping, cellp: Mapping, seed: int, seconds: float,
    trace: bool, t_start: float, readers: Sequence, peak: Any,
    control: bool = False, trace_seconds: float = TRACE_SECONDS,
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr),
) -> Dict[str, Any]:
    """One run of a cell of configuration ``m``, of architecture module
    ``arch`` (:func:`bench.spec.arch`); returns the result's fields other
    than ``device``.  ``readers`` is ``[(name, unit, read)]`` of the metrics
    to report."""
    clock = time.perf_counter
    counter = CompileCounter()
    engine = build_engine(arch, m, cellp["engine"], seed)
    buckets = prefill_buckets(engine, mix)
    engine.compile(buckets)
    log(f"built and compiled {len(buckets)} prefill buckets at {clock() - t_start:.1f} s")
    backlog = mix["arrival"]["kind"] == "backlog"
    drv = Driver(engine, arrivals(mix, m["vocab_size"], seed), clock,
                 max_queue=engine.batch if backlog else None)
    warm_traffic(drv, mix)
    setup_s = clock() - t_start
    log(f"setup done at {setup_s:.1f} s; live {engine.num_live}, queued {len(drv.queue)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        w0, w1, first, tr_i, tr_s = measure(
            drv, seconds, counter, trace_seconds, trace_dir)
        view = None
        if trace_dir is not None:
            view = reduce_trace(trace_dir, drv.iters[tr_i:], tr_s)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    mem = peak_memory()
    host_iters = drv.iters[first:tr_i] if tr_i is not None else drv.iters[first:]
    run = Run(m, arch, peak, setup_s, w0, w1, host_iters, drv.tracked, counter.count,
              engine.pool.num_pages, view)
    metrics = {}
    for name, unit, read in readers:
        v = read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit}

    in_window = [t for t in drv.tracked
                 if t.due <= w1 and (t.finished_at is None or t.finished_at >= w0)]
    failed = sum(t.failed for t in in_window)
    chk = cellp["check"]
    picked = sample(drv.tracked, w0, w1, seed, int(chk["sample_tokens"]),
                    int(chk["max_requests"]))
    # free the program's state before the reference needs the memory
    drv.engine = engine = None
    gc.collect()
    t_ref = clock()
    checks = check(arch, m, seed, picked, failed, chk["limits"], control)
    log(f"reference over {len(picked)} requests took {clock() - t_ref:.1f} s")
    out = {
        "correct": passed(checks),
        "attempted": len(in_window),
        "failed": failed,
        "metrics": metrics,
        "memory_peak_bytes": mem,
    }
    if view is not None:
        out["busy_s"], out["trace_window_s"] = view.busy_s, view.window_s
        gaps = trace_reduce.idle_gaps(
            view.device, view.spans,
            min(s.start for s in view.spans), max(s.end for s in view.spans))
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(view.device),
            "idle_gaps": trace_reduce.gap_summary(gaps),
        }
    out["checks"] = checks
    return out
