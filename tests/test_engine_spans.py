"""The engine's host phases, counters and request stamps.

``Engine.step`` and ``Engine.admit`` time each host phase into
``last_phases`` and wrap it in an ``engine.<phase>`` profiler span;
``Engine.counters`` counts events where they happen, and the blocks the
paged kernel walks; ``run_closed_loop`` reads its preemptions and refusals
from those counters and times TTFT from submission.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

import test_engine_ragged as ragged
from repro.kernels import paged_attention
from repro.serving import Engine, OutOfPages, Request, run_closed_loop

STEP_PHASES = {"grow", "inputs", "dispatch", "wait", "fetch", "sample"}
ADMIT_PHASES = {"reserve", "dispatch", "wait", "fetch", "sample"}

# pool-exhaustion setups, as (engine keywords, prompt length, new tokens,
# requests): two 2-page contexts leave one page of five, so the third
# admission is refused; the second setup is test_engine_ragged.py's
# mid-decode preemption case
REFUSING = (dict(batch=3, page_size=4, num_pages=5), 7, 2, 4)
PREEMPTING = (dict(batch=3, page_size=4, num_pages=5), 5, 8, 4)


def _engine(backend="paged", **kw):
    m, params = ragged.model_and_params("qwen3-8b")
    return Engine(m, params, max_len=ragged.MAX_LEN, kv_backend=backend, **kw)


def _requests(n, prompt_len, new_tokens, rid0=0):
    return [Request(rid=rid0 + i, prompt=np.arange(1, prompt_len + 1, dtype=np.int32),
                    max_new_tokens=new_tokens) for i in range(n)]


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


@pytest.mark.parametrize("backend", ["flat", "paged"])
def test_last_phases_hold_each_calls_named_children(backend):
    eng = _engine(backend, batch=2)
    prompts = ragged.make_prompts(eng.cfg, (4, 6))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4) for i, p in enumerate(prompts)]
    for call, want in [(lambda: eng.admit(reqs[0]), ADMIT_PHASES),
                       (eng.step, STEP_PHASES),
                       (lambda: eng.admit(reqs[1]), ADMIT_PHASES),
                       (eng.step, STEP_PHASES)]:
        wall = _timed(call)
        # replaced by every call, never grown: exactly this call's phases
        assert set(eng.last_phases) == want
        assert all(v >= 0.0 for v in eng.last_phases.values())
        assert sum(eng.last_phases.values()) <= wall


def test_a_step_with_nothing_live_records_no_phases():
    eng = _engine(batch=1)
    eng.admit(_requests(1, 4, 1)[0])  # done at admission: nothing stays live
    assert eng.num_live == 0
    eng.step()
    assert eng.last_phases == {}


@pytest.mark.parametrize("setup", [REFUSING, PREEMPTING], ids=["refusing", "preempting"])
def test_counters_count_what_the_callers_see(setup):
    kw, prompt_len, new_tokens, n = setup
    eng = _engine(**kw)
    pending = _requests(n, prompt_len, new_tokens)
    reqs = list(pending)
    refused = preempted = finished = steps = 0
    while pending or eng.num_live:
        for req in list(pending):
            if not eng.has_free_slot():
                break
            try:
                eng.admit(req)
            except OutOfPages:
                refused += 1
                continue
            pending.remove(req)
        live = eng.num_live
        finished += len(eng.step())
        back = eng.take_preempted()
        preempted += len(back)
        # a step decodes unless nothing was live or every live row was preempted
        steps += live > len(back)
        pending = back + pending
    c = eng.counters
    assert set(c) == {"steps", "preempted", "refused", "kv_blocks"}
    assert all(isinstance(v, int) for v in c.values())
    assert (c["steps"], c["preempted"], c["refused"]) == (steps, preempted, refused)
    assert eng.steps == c["steps"]
    assert finished == n and (refused > 0 if setup is REFUSING else preempted > 0)
    assert all(r.done for r in reqs)


def test_kv_blocks_counts_the_blocks_the_paged_kernel_walks(monkeypatch):
    """Each step adds cdiv(length, block_tokens) for every live row, a row's
    length being its position + 1; a step with no live row adds nothing."""
    # 1 KiB of K a block: two of this model's 512 B pages, so rows span blocks
    monkeypatch.setattr(paged_attention, "BLOCK_BYTES", 1024)
    eng = _engine(batch=3, page_size=4)
    assert eng.block_tokens == 8
    for i, p in enumerate(ragged.make_prompts(eng.cfg, (3, 8, 13))):
        eng.admit(Request(rid=i, prompt=p, max_new_tokens=6))
    want = 0
    while eng.num_live:
        lengths = [int(eng.slot_pos[i]) + 1 for i, s in enumerate(eng.slots) if s]
        want += sum(-(-n // eng.block_tokens) for n in lengths)
        eng.step()
        assert eng.counters["kv_blocks"] == want
    assert want > 3 * eng.steps  # some rows took more than one block
    eng.step()
    assert eng.counters["kv_blocks"] == want


@pytest.mark.parametrize("setup", [REFUSING, PREEMPTING], ids=["refusing", "preempting"])
def test_closed_loop_reports_the_counters_deltas(setup):
    kw, prompt_len, new_tokens, n = setup
    eng = _engine(**kw)
    first = run_closed_loop(eng, _requests(n, prompt_len, new_tokens))
    before = dict(eng.counters)
    stats = run_closed_loop(eng, _requests(n, prompt_len, new_tokens, rid0=n))
    assert stats.preempted == eng.counters["preempted"] - before["preempted"]
    assert stats.refused == eng.counters["refused"] - before["refused"]
    assert (first.preempted, first.refused) == (before["preempted"], before["refused"])
    assert stats.preempted + stats.refused > 0


def test_ttft_counts_the_wait_in_the_queue():
    """One slot, two requests: the second waits for the first to finish, and
    its TTFT holds that wait."""
    eng = _engine("flat", batch=1)
    a, b = _requests(2, 4, 6)
    stats = run_closed_loop(eng, [a, b])
    assert a.submitted_s == b.submitted_s > 0.0
    assert b.first_token_s > a.finished_s
    assert sorted(stats.ttft_s) == pytest.approx(
        sorted([a.first_token_s - a.submitted_s, b.first_token_s - b.submitted_s]))
    assert max(stats.ttft_s) >= a.finished_s - a.submitted_s


def test_closed_loop_keeps_a_callers_submission_stamp():
    """A request the caller stamped before the loop keeps its stamp, so its
    TTFT holds the time it spent queued outside the loop."""
    eng = _engine("flat", batch=1)
    a, b = _requests(2, 4, 2)
    a.submitted_s = stamp = time.perf_counter() - 5.0
    stats = run_closed_loop(eng, [a, b])
    assert a.submitted_s == stamp and b.submitted_s > stamp + 5.0
    assert a.first_token_s - stamp >= 5.0
    assert sorted(stats.ttft_s) == pytest.approx(
        sorted([a.first_token_s - a.submitted_s, b.first_token_s - b.submitted_s]))


def _host_spans(trace_dir, prefixes):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(prefixes)]


def test_engine_spans_nest_inside_the_callers_span_in_the_profilers_trace(tmp_path):
    eng = _engine(batch=2)
    prompts = ragged.make_prompts(eng.cfg, (4, 6))
    for i, p in enumerate(prompts):
        eng.admit(Request(rid=i, prompt=p, max_new_tokens=4))
    eng.step()  # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.step"):  # as bench/driver.py wraps it
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path), ("bench.", "engine."))
    (outer,) = [s for s in spans if s[0] == "bench.step"]
    names = {s[0] for s in spans if s[0].startswith("engine.")}
    assert names == {"engine.step"} | {"engine." + p for p in STEP_PHASES}
    for name, start, end in spans:
        assert outer[1] <= start <= end <= outer[2], name
    (step,) = [s for s in spans if s[0] == "engine.step"]
    order = sorted((s for s in spans if s[0] in {"engine." + p for p in STEP_PHASES}),
                   key=lambda s: s[1])
    assert [s[0] for s in order] == ["engine.grow", "engine.inputs", "engine.dispatch",
                                     "engine.wait", "engine.fetch", "engine.sample"]
    assert all(step[1] <= s[1] and s[2] <= step[2] for s in order)
