"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py --workload <cell> [--prefill 512]

Builds the cell's model at its real size from shapes only, and compiles the
engine's donated paged decode step at the cell's batch and pool, and one
prefill of the given length, for one chip of a described ``v5e:2x2``.  It
prints each program's ``memory_analysis()`` and whether the Pallas kernel is
in it.  A compile is not a run: it gives memory and tiling facts, never a
time.
"""

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prefill", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spec
    from cell import PAGE_SIZE
    from repro.kernels import ops
    from repro.models import Model
    from repro.models.config import ModelConfig
    from repro.serving.engine import decode_fn, page_hbm_bytes

    bm = spec.benchmark()
    w = spec.workload(bm, args.workload)
    m = spec.config(bm, w["config"])["model"]
    sizes = spec.cell(w["name"])["engine"]
    # the kernels pick interpret mode from the platform JAX runs on; the
    # described chip is not that platform, so lower them for the TPU here
    ops._interpret = lambda: False

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    cfg = ModelConfig(**m)
    model = Model(cfg, remat=False, use_kernels=True)
    params = jax.tree.map(on_chip, model.init(None, abstract=True)[0])
    B, ps = sizes["batch"], PAGE_SIZE
    max_pages = -(-sizes["max_len"] // ps)
    pages = B * max_pages
    if "kv_pool_bytes" in sizes:  # as bench/cell.py build_engine sizes it
        pages = sizes["kv_pool_bytes"] // page_hbm_bytes(cfg, ps)
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_cache(B, pages, ps, max_pages)))
    i32 = jnp.int32
    report = {}

    def show(name, compiled):
        ma = compiled.memory_analysis()
        report[name] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "kernel": "tpu_custom_call" in compiled.as_text(),
        }

    show("decode", decode_fn(model, "paged").lower(
        params, cache, on_chip(jax.ShapeDtypeStruct((B, 1), i32)),
        on_chip(jax.ShapeDtypeStruct((B,), i32))).compile())
    prefill = jax.jit(lambda p, t, n: model.prefill(p, tokens=t, lengths=n))
    show(f"prefill[{args.prefill}]", prefill.lower(
        params, on_chip(jax.ShapeDtypeStruct((1, args.prefill), i32)),
        on_chip(jax.ShapeDtypeStruct((1,), i32))).compile())
    print(json.dumps({"workload": w["name"], "num_pages": pages, "programs": report},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
