"""Sweep of offered rates for a cell with timed arrivals, to find its knee.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1.5 2 2.5 3

One process, one engine: for each rate, in order, the cell's mix at that
rate is warmed up and measured for ``--seconds`` as a run would, then the
engine is drained.  One JSON line per rate gives the queue of due requests
not yet admitted at the window's start and end, the requests due and
admitted inside it, and the cell's end-to-end metrics.  The knee is the
highest rate at which the queue does not grow across the window; the cell's
mix file then fixes its rate at four fifths of it, as a number.
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    import spec
    from cell import CompileCounter, Run, build_engine, measure, prefill_buckets, warm_traffic
    from driver import Driver
    from peaks import peak_for
    from repro.launch.serve import enable_compile_cache
    from traffic import arrivals

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bm = spec.benchmark()
    w = spec.workload(bm, args.workload)
    m = spec.config(bm, w["config"])["model"]
    arch = spec.arch(bm, w["config"])
    base = spec.mix(w["traffic"])
    cellp = spec.cell(w["name"])
    readers = [(md["name"], spec.reader(md["name"], False))
               for md in spec.metrics(bm, w["name"], False)]
    peak = peak_for(jax.devices()[0].device_kind)
    engine = build_engine(arch, m, cellp["engine"], args.seed)
    engine.compile(prefill_buckets(engine, base))
    counter = CompileCounter()
    for rate in args.rates:
        mix = dict(base, arrival=dict(base["arrival"], rate=rate))
        drv = Driver(engine, arrivals(mix, m["vocab_size"], args.seed))
        warm_traffic(drv, mix)
        q0 = len(drv.queue)
        w0, w1, first, _, _ = measure(drv, args.seconds, counter)
        q1 = len(drv.queue)
        due = sum(1 for t in drv.tracked if w0 <= t.due <= w1)
        admitted = sum(1 for t in drv.tracked
                       if t.admit_start is not None and w0 <= t.admit_start <= w1)
        run = Run(m, arch, peak, 0.0, w0, w1, drv.iters[first:], drv.tracked,
                  counter.count, engine.pool.num_pages, None)
        line = {"rate": rate, "queue_start": q0, "queue_end": q1, "due": due,
                "admitted": admitted, "window_s": w1 - w0,
                "metrics": {name: read(run) for name, read in readers if name != "setup_s"}}
        print(json.dumps(line), flush=True)
        # drain: no more arrivals, finish what is admitted
        drv.stop_arrivals()
        drv.run_until(lambda: engine.num_live == 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
