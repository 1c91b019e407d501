"""Readings from which a cell's check limit is set, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 11 12 13 [--control]

Runs the cell once per seed in one process, each run as ``bench/run.py``
makes it (weights from the seed, warm-up, a window of ``--seconds`` at the
cell's own load, the reference over a sample of the finished requests), and
prints one JSON line per seed with the widest gap of the served tokens and
whether the run is correct.  With ``--control`` it also puts the fp8 control
in the program's place over the same prompts and tokens and holds its widest
gap to the cell's limit, so such a run has to print ``"correct": false``.
The benchmark's own runs never run the control.  A limit lies above every
sound run's reading and below every control's (``PERF.md`` gives the
readings and the limit).
"""

import time

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    import jax

    import spec
    from cell import run_cell
    from peaks import peak_for
    from repro.launch.serve import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bm = spec.benchmark()
    w = spec.workload(bm, args.workload)
    readers = [(md["name"], md["unit"], spec.reader(md["name"], False))
               for md in spec.metrics(bm, w["name"], False)]
    peak = peak_for(jax.devices()[0].device_kind)
    arch = spec.arch(bm, w["config"])
    for seed in args.seeds:
        out = run_cell(arch, spec.config(bm, w["config"])["model"],
                       spec.mix(w["traffic"]), spec.cell(w["name"]), seed,
                       args.seconds, False, time.perf_counter(), readers, peak,
                       control=args.control)
        line = {"workload": w["name"], "seed": seed, "correct": out["correct"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
