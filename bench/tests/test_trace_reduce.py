"""The trace reduction, on synthetic traces, and the dense block's FLOP and
byte counts."""

import pytest

import trace_reduce as tr
from small import DENSE as dense
from trace_reduce import Event

from repro.serving.paged_cache import PagePool

M = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab_size": 500, "mlp_gated": True}


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_seconds_counts_overlap_once():
    ev = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.5), Event("c", 2.0, 2.25)]
    assert tr.busy_seconds(ev) == pytest.approx(1.75)


def test_kernel_events_match_by_name_without_suffix():
    ev = [Event("paged_decode_attention.6", 0, 1), Event("paged_decode_attention", 1, 2),
          Event("fusion.3", 2, 3), Event("flash_attention.2", 3, 4),
          Event("paged_decode_attention_x.1", 4, 5)]
    got = tr.kernel_events(ev, ("paged_decode_attention",))
    assert [e.name for e in got] == ["paged_decode_attention.6", "paged_decode_attention"]


def test_roofline_share_sums_the_least_time_per_call_and_names_the_bound():
    # call 1: 2 s of compute vs 1 s of memory; call 2: 1 s compute vs 3 s memory
    share, bound = tr.roofline_share([(2.0, 1.0), (1.0, 3.0)], kernel_s=10.0,
                                     peak_flops=1.0, peak_bw=1.0)
    assert share == pytest.approx(100.0 * (2.0 + 3.0) / 10.0)
    assert bound == "memory"
    assert tr.roofline_share([], 1.0, 1.0, 1.0) is None
    assert tr.roofline_share([(1.0, 1.0)], 0.0, 1.0, 1.0) is None


def test_idle_gaps_are_labelled_by_the_innermost_covering_span():
    device = [Event("op", 0.0, 1.0), Event("op", 2.0, 3.0), Event("op", 3.5, 4.0)]
    spans = [Event("bench.step", -1.0, 2.2), Event("bench.feed", 1.2, 1.8),
             Event("bench.admit", 3.1, 3.6)]
    gaps = tr.idle_gaps(device, spans, 0.0, 5.0)
    assert [(g[0], g[1], g[2]) for g in gaps] == [
        ("bench.feed", 1.0, 1.0), ("bench.admit", 3.0, 0.5), ("no span", 4.0, 1.0)]
    summary = tr.gap_summary(gaps)
    assert summary[0][1] == pytest.approx(1.0)
    assert summary[0][0].startswith(("bench.feed", "no span"))
    assert sum(row[1] for row in summary) == pytest.approx(2.5)


def test_idle_gaps_clip_to_the_window():
    device = [Event("op", -1.0, 0.5), Event("op", 0.9, 2.0)]
    gaps = tr.idle_gaps(device, [], 0.0, 1.0)
    assert gaps == [("no span", 0.5, pytest.approx(0.4))]


def test_top_ops_orders_by_total_time():
    ev = [Event("a.1", 0, 1), Event("b.2", 0, 3), Event("a.1", 5, 7)]
    assert tr.top_ops(ev) == [["a.1", 3], ["b.2", 3]] or tr.top_ops(ev)[0][1] == 3


def _lengths_from(pool_pages, max_pages, lengths, order):
    """Live lengths as the engine's pool would report them, with pages
    handed out in the given order."""
    pool = PagePool(pool_pages, 16, max_pages)
    pool._free = list(order)
    for rid, n in enumerate(lengths):
        pool.admit(rid)
        pool.append_tokens(rid, n)
    pt, lens = pool.tables(list(range(len(lengths))))
    return pt, [int(x) for x in lens]


def test_paged_bytes_follow_live_lengths_not_page_table_or_grid():
    lengths = [17, 300, 1, 64]
    pt_a, la = _lengths_from(128, 32, lengths, range(128))
    pt_b, lb = _lengths_from(512, 64, lengths, reversed(range(512)))
    assert pt_a.shape != pt_b.shape  # another grid (max pages) and table
    assert dense.paged_attn_cost(M, la) == dense.paged_attn_cost(M, lb)
    f0, b0 = dense.paged_attn_cost(M, la)
    f1, b1 = dense.paged_attn_cost(M, [n + 1 for n in la])
    assert b1 - b0 == pytest.approx(len(la) * 2 * M["num_kv_heads"] * M["head_dim"] * 2)
    assert f1 > f0


def test_paged_cost_counts_kv_of_live_tokens_and_q_out_of_live_rows():
    f, b = dense.paged_attn_cost(M, [10, 20])
    assert f == 4 * 4 * 16 * 30
    assert b == 30 * 2 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2


def test_flash_cost_is_the_causal_half():
    f, b = dense.flash_attn_cost(M, 4)
    assert f == 4 * 4 * 16 * (4 * 5 // 2)
    assert b == 4 * (2 * 4 + 2 * 2) * 16 * 2


def test_decode_and_prefill_flops_count_real_work_only():
    per_row = 2 * (2 * dense.layer_matmul_params(M) + dense.head_params(M))
    assert dense.decode_step_flops(M, []) == 0
    assert dense.decode_step_flops(M, [5]) == per_row + 4 * 4 * 16 * 2 * 5
    assert dense.prefill_flops(M, 1) == (2 * 2 * dense.layer_matmul_params(M)
                                         + 4 * 4 * 16 * 2 + 2 * dense.head_params(M))


def test_layer_params_gated_and_plain_mlp():
    assert dense.layer_matmul_params(M) == 64 * 16 * (4 + 4) + 4 * 16 * 64 + 3 * 64 * 128
    plain = dict(M, mlp_gated=False)
    assert dense.layer_matmul_params(plain) == dense.layer_matmul_params(M) - 64 * 128
