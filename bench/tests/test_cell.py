"""A whole run of a cell, on the CPU at small width with the Pallas kernels in
interpret mode, through the functions ``bench/run.py`` calls after it has
found the chip; and the checks that a run on a wrong path comes out not
correct: the fp8 control in the program's place, and faults planted in the
timed path."""

import time

import jax
import jax.numpy as jnp
import pytest

import cell
import spec
from peaks import PEAKS
from small import BACKLOG, CELL, DENSE, GQA, MQA, POISSON, TRACE_SECONDS

BM = spec.benchmark()
PEAK = PEAKS["TPU v5 lite"]


# What a cell with timed arrivals reads: the doc-qa cell, whose readers stay
# in bench/ while its tails are too noisy for a bound (PERF.md, Open
# questions), and whose mix is the only one with arrivals in time.
TIMED = {
    False: ["output_tok_per_s", "itl_p95_s", "ttft_p90_s", "setup_s"],
    True: ["queue_wait_p90_s", "decode_batch_mean", "kv_pages_used_frac",
           "compiles_in_window", "decode_mfu", "prefill_mfu",
           "paged_attn_roofline", "flash_attn_roofline", "device_idle_frac"],
}
BACKLOG_CELL = "phi4-mini-3.8b.decode-offline"


def _names(workload, per_layer):
    if workload is None:
        return TIMED[per_layer]
    return [md["name"] for md in spec.metrics(BM, workload, per_layer)]


def _readers(workload, per_layer):
    return [(n, "", spec.reader(n, per_layer)) for n in _names(workload, per_layer)]


def _run(m=GQA, mix=BACKLOG, seed=3, trace=False, workload=BACKLOG_CELL,
         control=False, seconds=2.0):
    return cell.run_cell(DENSE, m, mix, CELL, seed, seconds, trace,
                         time.perf_counter(), _readers(workload, trace), PEAK,
                         control=control, trace_seconds=TRACE_SECONDS,
                         log=lambda s: None)


@pytest.mark.parametrize("m,mix,workload", [
    (GQA, BACKLOG, BACKLOG_CELL),
    (MQA, POISSON, None),
], ids=["gqa-backlog", "mqa-poisson"])
def test_a_run_serves_checks_and_reports_its_end_to_end_metrics(m, mix, workload):
    out = _run(m, mix, workload=workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = set(_names(workload, False))
    assert set(out["metrics"]) == names
    assert ("ttft_p90_s" in names) == (mix is POISSON)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out["checks"])[-2:] == ["bad_tokens", "failed_requests"]
    assert out["checks"]["checked_tokens"]["value"] > 0


@pytest.mark.parametrize("mix,workload", [
    (POISSON, None),
    (BACKLOG, "granite-20b-pp4.decode-offline"),
], ids=["poisson", "backlog"])
def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(mix, workload):
    out = _run(mix=mix, trace=True, workload=workload)
    assert out["correct"]
    got = out["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    listed = set(_names(workload, True))
    # the CPU has no TPU plane: nothing runs "on the device" to read
    device = {n for n in listed if n.endswith("_roofline")}
    assert device and set(got) == listed - device
    assert out["trace_window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


# -- the control and the planted faults ------------------------------------
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_the_fp8_control_fails_the_check_that_the_program_passes(seed):
    out = _run(seed=seed, control=True)
    checks = out["checks"]
    assert not out["correct"], checks
    assert checks["control_gap_max"]["limit"] == CELL["check"]["limits"]["served_gap_max"]
    # the same run's served tokens alone pass: the control is what fails
    assert cell.passed({k: v for k, v in checks.items() if k != "control_gap_max"}), checks


def _planted(monkeypatch, plant):
    real = cell.build_engine

    def build(*args, **kwargs):
        eng = real(*args, **kwargs)
        plant(eng)
        return eng

    monkeypatch.setattr(cell, "build_engine", build)


def test_a_step_that_leaves_the_cache_unchanged_is_not_correct(monkeypatch):
    def plant(eng):
        step = eng.model.decode_step_paged
        eng._decode = jax.jit(lambda p, c, t, pos: (step(p, c, t, pos)[0], c))

    _planted(monkeypatch, plant)
    assert not _run()["correct"]


def test_half_the_batch_left_out_of_the_step_is_not_correct(monkeypatch):
    def plant(eng):
        step = eng.model.decode_step_paged
        half = jnp.arange(eng.batch) >= eng.batch // 2

        def broken(p, c, t, pos):
            return step(p, c, t, jnp.where(half, -1, pos))

        eng._decode = jax.jit(broken, donate_argnums=(1,))

    _planted(monkeypatch, plant)
    assert not _run()["correct"]


def test_a_token_altered_where_it_is_sampled_is_not_correct(monkeypatch):
    def plant(eng):
        sample, calls = eng._sample, [0]

        def altered(row, rng):
            calls[0] += 1
            tok = sample(row, rng)
            return (tok + 1) % GQA["vocab_size"] if calls[0] % 7 == 0 else tok

        eng._sample = altered

    _planted(monkeypatch, plant)
    assert not _run()["correct"]
