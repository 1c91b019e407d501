"""One measured run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell asks
for.  The cell, its configuration and that configuration's architecture,
its traffic mix and its metrics are found by name from ``BENCHMARK.json``
(see ``bench/spec.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit, which are also the last lines of
standard error.

It exits with code 2 and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a chip with no entry in ``bench/peaks.py``.
JAX's persistent compilation cache is on, at the program's fixed directory
inside the checkout (``JAX_COMPILATION_CACHE_DIR`` where that is set).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import spec

    bm = spec.benchmark()
    w = spec.workload(bm, args.workload)
    model = spec.config(bm, w["config"])["model"]
    arch = spec.arch(bm, w["config"])
    mix = spec.mix(w["traffic"])
    cellp = spec.cell(w["name"])
    per_layer = bool(args.trace)
    readers = [(md["name"], md["unit"], spec.reader(md["name"], per_layer))
               for md in spec.metrics(bm, w["name"], per_layer)]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        print(f"error: the cell needs {w['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from peaks import peak_for

    try:
        peak = peak_for(devices[0].device_kind)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from cell import run_cell

    out = run_cell(arch, model, mix, cellp, args.seed, args.seconds, per_layer,
                   T_START, readers, peak)
    used = devices[: w["chips"]]
    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": out["memory_peak_bytes"],
    }
    result = {
        "correct": out["correct"],
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": out["metrics"],
        "device": device,
    }
    if per_layer:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["trace_window_s"]
        result["breakdown"] = out["breakdown"]
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
