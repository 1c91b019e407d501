"""Compile the serving path's TPU programs for a described (not attached)
v5e chip, at ``phi4-mini-3.8b`` widths.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the tiling rules, kernels over their fast-memory budget, and programs
that do not fit the chip's HBM.  These cases compile the Pallas kernels with
``interpret=False``, and the engine's donated paged decode step for batch
8 × 4096 tokens and at the benchmark cells' sizes, and pin that the step fits
one chip with the KV pool updated in place.  Nothing runs, so they say
nothing about results or time.
"""

import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import Model
from repro.models.config import ModelConfig
from repro.serving.engine import decode_fn

HBM_BYTES = 16 * 10**9  # one v5e chip
BATCH, MAX_LEN, PAGE = 8, 4096, 16
MAX_PAGES = MAX_LEN // PAGE
NUM_PAGES = BATCH * MAX_PAGES  # the engine's default pool: a full context per slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the benchmark cells (bench/cells, bench/configs): rows, pool pages and
# max_len 2048 in 16-token pages, and the decode step's temporaries before the
# kernel walked live pages only (granite's: two copies of its MQA pool)
CELLS = {
    "phi4-mini-3.8b": (160, 3480, 387_072),
    "granite-20b-pp4": (256, 16384, 3_493_633_536),
}
CELL_MAX_PAGES = 2048 // PAGE
BENCH_CONFIGS = Path(__file__).resolve().parent.parent / "bench" / "configs"


def _cell_config(name):
    return ModelConfig(**json.loads((BENCH_CONFIGS / f"{name}.json").read_text())["model"])


def test_paged_decode_kernel_compiles(one_chip):
    cfg = get_config("phi4-mini-3.8b")
    H, KV, D, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    bf16 = jnp.bfloat16
    pool = _sds((L, NUM_PAGES, PAGE, KV, D), bf16, one_chip)
    args = (
        _sds((BATCH, H, D), bf16, one_chip), pool, pool,
        _sds((BATCH, MAX_PAGES), jnp.int32, one_chip),
        _sds((BATCH,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
    )
    fn = jax.jit(lambda *a: paged_decode_attention(*a, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_decode_kernel_compiles_at_the_cells_sizes(one_chip, cell):
    """The kernel alone at a cell's rows, pool and layer stack (phi4-mini:
    160 rows, 3480 pages, 32 layers, GQA 24/8; granite-20b's stage: 256 rows,
    16384 pages, 13 layers, MQA 48/1), under the name the benchmark's
    ``paged_attn_roofline`` finds its events by."""
    cfg = _cell_config(cell)
    rows, pages, _ = CELLS[cell]
    H, KV, D, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    bf16 = jnp.bfloat16
    pool = _sds((L, pages, PAGE, KV, D), bf16, one_chip)
    compiled = jax.jit(lambda *a: paged_decode_attention(*a, interpret=False)).lower(
        _sds((rows, H, D), bf16, one_chip), pool, pool,
        _sds((rows, CELL_MAX_PAGES), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
    ).compile()
    assert _kernel_names(compiled.as_text()) == {"paged_decode_attention"}


def test_flash_attention_kernel_compiles(one_chip):
    cfg = get_config("phi4-mini-3.8b")
    S, D = 1024, cfg.head_dim
    q = _sds((1, cfg.num_heads, S, D), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.num_kv_heads, S, D), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False))
    assert "tpu_custom_call" in fn.lower(q, kv, kv).compile().as_text()


def _kernel_names(hlo_text):
    """The instruction names, without their ``.N`` suffix, of the Pallas
    calls in a compiled program: what a device trace names their events."""
    return {re.match(r"\s*(?:ROOT\s+)?%?([^\s=.]+)", line).group(1)
            for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def test_kernels_carry_the_names_the_trace_is_matched_by(one_chip):
    """Each kernel names its own call, whatever jit wraps it: the benchmark's
    rooflines find their events in a trace by these names."""
    cfg = get_config("phi4-mini-3.8b")
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    pool = _sds((2, 64, PAGE, KV, D), bf16, one_chip)
    q = _sds((1, H, 256, D), bf16, one_chip)
    kv = _sds((1, KV, 256, D), bf16, one_chip)

    def both(qd, pk, pv, tables, lens, layer, q, k, v):
        return (paged_decode_attention(qd, pk, pv, tables, lens, layer, interpret=False),
                flash_attention_bhsd(q, k, v, interpret=False))

    compiled = jax.jit(both).lower(
        _sds((BATCH, H, D), bf16, one_chip), pool, pool,
        _sds((BATCH, 8), jnp.int32, one_chip), _sds((BATCH,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), q, kv, kv,
    ).compile()
    assert _kernel_names(compiled.as_text()) == {"paged_decode_attention", "flash_attention"}


@pytest.mark.parametrize("use_kernels", [False, True], ids=["jnp", "kernel"])
def test_engine_decode_step_fits_one_chip(one_chip, monkeypatch, use_kernels):
    """The engine's donated paged decode step at batch 8 × 4096: the pool is
    aliased from input to output (updated in place, never copied), and
    arguments + outputs − aliased + temporaries fit the chip's HBM."""
    if use_kernels:
        # a CPU process runs kernels in interpret mode; compile the real one
        monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = Model(get_config("phi4-mini-3.8b"), remat=False, use_kernels=use_kernels)

    def on_chip(x):
        return _sds(x.shape, x.dtype, one_chip)

    params = jax.tree.map(on_chip, model.init(None, abstract=True)[0])
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_cache(BATCH, NUM_PAGES, PAGE, MAX_PAGES)
    ))
    compiled = decode_fn(model, "paged").lower(
        params, cache,
        _sds((BATCH, 1), jnp.int32, one_chip), _sds((BATCH,), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache["layers"]))
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernels


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_decode_step_reads_the_pool_in_place(one_chip, monkeypatch, cell):
    """The engine's donated paged decode step at a cell's size, with the
    kernel: the pool is aliased from input to output, and the kernel reads
    it through a bitcast, so no temporary holds a copy of either half of the
    pool and the temporaries are no larger than they were."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = Model(_cell_config(cell), remat=False, use_kernels=True)
    rows, pages, temp_before = CELLS[cell]

    def on_chip(x):
        return _sds(x.shape, x.dtype, one_chip)

    params = jax.tree.map(on_chip, model.init(None, abstract=True)[0])
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_cache(rows, pages, PAGE, CELL_MAX_PAGES)
    ))
    compiled = decode_fn(model, "paged").lower(
        params, cache,
        _sds((rows, 1), jnp.int32, one_chip), _sds((rows,), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    half = cache["layers"]["pool_k"]
    assert mem.alias_size_in_bytes >= 2 * half.size * half.dtype.itemsize
    assert mem.temp_size_in_bytes < half.size * half.dtype.itemsize
    assert mem.temp_size_in_bytes <= temp_before
