"""Architecture modules, found by the name a configuration file gives.

The dense block's weights, work counts and weight bits are pinned
(``dense_pins.json``): the yardstick of both cells rests on them, so they
change only with a change of the benchmark.  A second architecture is new
files only: a small cell runs end to end under a module that the test
supplies through the lookup, and every block-specific step goes through it.
"""

import hashlib
import json
import time
from pathlib import Path

import numpy as np
import pytest

import cell
import spec
import weights
from peaks import PEAKS
from small import BACKLOG, CELL, DENSE, GQA, TRACE_SECONDS

BM = spec.benchmark()
PINS = json.loads((Path(__file__).parent / "dense_pins.json").read_text())
CONFIGS = sorted(PINS["configs"])


def _model(name):
    return spec.config(BM, name)["model"]


@pytest.mark.parametrize("name", [c["name"] for c in BM["configs"]])
def test_a_configuration_that_names_no_arch_is_dense(name):
    assert "arch" not in spec.config(BM, name)
    assert Path(spec.arch(BM, name).__file__) == spec.BENCH / "arch" / "dense.py"


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_shapes_are_pinned(name):
    got = {k: [list(s), i] for k, (s, i) in DENSE.shapes(_model(name)).items()}
    assert got == PINS["configs"][name]["shapes"]


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_work_counts_are_pinned(name):
    m, pin = _model(name), PINS["configs"][name]
    ctx, lengths = PINS["ctx_lens"], PINS["prefill_lens"]
    paged = DENSE.KERNEL_COSTS["paged_decode_attention"]
    flash = DENSE.KERNEL_COSTS["flash_attention"]
    assert DENSE.layer_matmul_params(m) == pin["layer_matmul_params"]
    assert DENSE.head_params(m) == pin["head_params"]
    assert ([DENSE.decode_step_flops(m, [n]) for n in ctx] + [DENSE.decode_step_flops(m, ctx)]
            == pin["decode_step_flops"])
    assert [DENSE.prefill_flops(m, n) for n in lengths] == pin["prefill_flops"]
    assert ([list(paged(m, [n])) for n in ctx] + [list(paged(m, ctx))]
            == pin["paged_decode_attention"])
    assert [list(flash(m, n)) for n in lengths] == pin["flash_attention"]


def test_dense_weights_are_pinned_to_the_bit():
    w = weights.make_weights(DENSE, GQA, PINS["weights_seed"])
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(f"{k} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINS["weights_sha256"]


# -- a second architecture is new files ------------------------------------
RECORDED = '''
"""The dense block, with the name of every function of it that is called."""
from small import DENSE

CALLS = []


def _recorded(name, fn):
    def call(*args, **kwargs):
        CALLS.append(name)
        return fn(*args, **kwargs)
    return call


def __getattr__(name):
    attr = getattr(DENSE, name)
    if isinstance(attr, dict):
        return {k: _recorded(f"{name}[{k}]", f) for k, f in attr.items()}
    return _recorded(name, attr) if callable(attr) else attr
'''


def _root(tmp_path, arch_name, module=None):
    """A checkout under ``tmp_path`` with one configuration, the small GQA
    one naming ``arch_name``, and ``module`` as ``bench/arch/<arch_name>.py``."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "arch").mkdir()
    (tmp_path / "bench" / "configs" / "small.json").write_text(
        json.dumps({"name": "small", "arch": arch_name, "model": GQA}))
    if module is not None:
        (tmp_path / "bench" / "arch" / f"{arch_name}.py").write_text(module)
    return {"configs": [{"name": "small", "file": "bench/configs/small.json"}]}


def test_a_cell_of_another_architecture_runs_through_its_module(tmp_path):
    bm = _root(tmp_path, "recorded", RECORDED)
    arch = spec.arch(bm, "small", root=tmp_path)
    m = spec.config(bm, "small", root=tmp_path)["model"]
    names = [md["name"] for md in spec.metrics(BM, "phi4-mini-3.8b.decode-offline", True)]
    readers = [(n, "", spec.reader(n, True)) for n in names]
    out = cell.run_cell(arch, m, BACKLOG, CELL, 3, 2.0, True, time.perf_counter(),
                        readers, PEAKS["TPU v5 lite"], trace_seconds=TRACE_SECONDS,
                        log=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["checks"]["checked_tokens"]["value"] > 0
    assert "decode_mfu" in out["metrics"]
    # the weights (for the engine and again for the reference), the
    # reference's layers, the decode MFU and the paged kernel's roofline
    assert arch.CALLS.count("shapes") == 2
    assert {"layer_weights", "layer", "decode_step_flops",
            "KERNEL_COSTS[paged_decode_attention]"} <= set(arch.CALLS)


def test_an_unknown_architecture_names_its_missing_file(tmp_path):
    bm = _root(tmp_path, "no-such-block")
    with pytest.raises(FileNotFoundError, match="bench/arch/no-such-block.py"):
        spec.arch(bm, "small", root=tmp_path)
