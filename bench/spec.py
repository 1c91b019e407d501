"""Finds everything of a cell by name: ``BENCHMARK.json`` at the root, and
beside it the files under ``bench/``:

* ``bench/configs/<config>.json``: the configuration as it is run (the file
  that ``BENCHMARK.json`` names for it);
* ``bench/arch/<arch>.py``: everything that depends on the block of the
  architecture a configuration file names under ``"arch"`` (``dense`` where
  it names none).  ``m`` is the file's ``model`` block:

  - ``shapes(m)``: ``{name: (shape, init)}`` of the weights it draws
    (``bench/weights.py``), with ``embed``, ``final_norm`` and, where the
    head is not tied, ``head`` among them;
  - ``layer_weights(m, weights, i)`` and ``layer(m, x, w, i, *, low)``: which
    weights decoder layer ``i`` of the plain reference reads, with its index
    along their leading axis, and its forward over one sequence in float32,
    or in fp8 with ``low`` (``bench/reference.py``);
  - ``decode_step_flops(m, ctx_lens)``, ``prefill_flops(m, length)`` and
    ``KERNEL_COSTS``, ``{kernel name: cost(m, ...) -> (flops, bytes)}`` of
    one call (one layer's): the work the MFU and roofline readers count;
* ``bench/traffic/<traffic>.json``: the traffic mix;
* ``bench/cells/<workload>.json``: the cell's engine sizes and its check;
* ``bench/e2e/<metric>.py`` and ``bench/metrics/<metric>.py``: one reader
  per end-to-end and per-layer metric, each with ``read(run) -> float|None``.

A new cell, mix, configuration or metric is a new file and a new entry in
``BENCHMARK.json``, and a new architecture one more file in ``bench/arch/``;
no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def workload(bm: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: Dict[str, Any], name: str, root: Path = ROOT) -> Dict[str, Any]:
    for c in bm["configs"]:
        if c["name"] == name:
            return _json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str) -> Dict[str, Any]:
    return _json(BENCH / "traffic" / f"{name}.json")


def cell(name: str) -> Dict[str, Any]:
    return _json(BENCH / "cells" / f"{name}.json")


def _module(path: Path, name: str) -> Any:
    """The Python file at ``path``, loaded as a module of its own."""
    found = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod


def arch(bm: Dict[str, Any], config_name: str, root: Path = ROOT) -> Any:
    """The architecture module of a configuration: ``bench/arch/<arch>.py``
    under ``root``, for the ``"arch"`` its file names, ``dense`` where it
    names none."""
    name = config(bm, config_name, root).get("arch", "dense")
    rel = f"{BENCH.name}/arch/{name}.py"
    if not (root / rel).is_file():
        raise FileNotFoundError(
            f"configuration {config_name!r} names the architecture {name!r}, "
            f"and there is no {rel}")
    return _module(root / rel, f"bench_arch_{name}")


def metrics(bm: Dict[str, Any], workload_name: str, per_layer: bool) -> List[Dict[str, Any]]:
    """The metrics a run of this cell reports: its end-to-end metrics, or
    with ``per_layer`` its per-layer ones."""
    key = "per_layer" if per_layer else "end_to_end"
    return [m for m in bm[key]
            if "workloads" not in m or workload_name in m["workloads"]]


def reader(name: str, per_layer: bool) -> Callable[[Any], Any]:
    path = BENCH / ("metrics" if per_layer else "e2e") / f"{name}.py"
    return _module(path, f"bench_metric_{name}").read
