"""Paged attention kernel + page-pool manager."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.paged_attention import (
    BLOCK_BYTES, paged_decode_attention, pages_per_block,
)
from repro.kernels.ref import paged_decode_attention_ref
from repro.serving.paged_cache import OutOfPages, PagePool


def rand(i, shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape)


@pytest.mark.parametrize(
    "B,H,KV,D,num_pages,page_size,max_pages,dtype,lengths",
    [
        pytest.param(2, 4, 2, 64, 8, 16, 3, "float32", None, id="2-4-2-64-8-16-3"),
        pytest.param(3, 8, 2, 64, 16, 32, 4, "float32", None, id="3-8-2-64-16-32-4"),
        pytest.param(1, 8, 1, 128, 8, 64, 2, "float32", None, id="1-8-1-128-8-64-2"),  # MQA
        pytest.param(2, 4, 4, 32, 12, 8, 6, "float32", None,
                     id="2-4-4-32-12-8-6"),  # MHA small pages
        # the benchmark cells' head shapes at 16-token pages; the lengths are
        # idle rows first and between live ones, exactly one block, one block
        # + 1 token and a full row.
        # phi4-mini GQA 24/8: 4 float32 pages (64 tokens) or 8 bf16 pages a block
        pytest.param(5, 24, 8, 128, 12, 16, 10, "float32", (0, 64, 65, 0, 160),
                     id="phi4-float32"),
        pytest.param(5, 24, 8, 128, 12, 16, 18, "bfloat16", (0, 128, 129, 0, 288),
                     id="phi4-bfloat16"),
        # granite-20b MQA 48/1: 32 float32 pages (512 tokens) or 64 bf16 pages
        pytest.param(5, 48, 1, 128, 24, 16, 40, "float32", (0, 512, 513, 0, 640),
                     id="granite-float32"),
        pytest.param(5, 48, 1, 128, 24, 16, 72, "bfloat16", (0, 1024, 1025, 0, 1152),
                     id="granite-bfloat16"),
    ],
)
def test_paged_kernel_matches_ref(B, H, KV, D, num_pages, page_size, max_pages,
                                  dtype, lengths):
    """The kernel reads one layer of a stacked (L, ...) pool.  With pinned
    lengths, rows 1 and 4 share every page id, and the page that holds row
    2's one token past its first block has V of 64 everywhere, so leaving
    that token out moves the row's output past any rounding."""
    rng = np.random.default_rng(0)
    L, layer = 3, 1
    dt = jnp.dtype(dtype)
    q = rand(0, (B, H, D)).astype(dt)
    pk = rand(1, (L, num_pages, page_size, KV, D)).astype(dt)
    pv = rand(2, (L, num_pages, page_size, KV, D)).astype(dt)
    if lengths is None:
        pt = rng.integers(0, num_pages, size=(B, max_pages))
        lengths = rng.integers(1, max_pages * page_size + 1, size=(B,))
    else:
        pt = rng.integers(0, num_pages - 1, size=(B, max_pages))
        pt[1] = pt[4]
        pt[2, (lengths[2] - 1) // page_size] = num_pages - 1
        pv = pv.at[layer, num_pages - 1].set(64)
    pt, lengths = jnp.asarray(pt, jnp.int32), jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(
        q, pk, pv, pt, lengths, jnp.int32(layer), interpret=True
    )
    f32 = jnp.float32
    ref = paged_decode_attention_ref(q.astype(f32), pk[layer].astype(f32),
                                     pv[layer].astype(f32), pt, lengths)
    idle = np.asarray(lengths) == 0
    out, ref = np.asarray(out.astype(f32)), np.asarray(ref)
    assert not out[idle].any()  # an idle row writes zeros
    # float32: the reference's own rounding; bf16: one rounding of the output
    tol = 3e-5 if dt == f32 else float(jnp.finfo(dt).eps)
    np.testing.assert_allclose(out[~idle], ref[~idle], atol=tol, rtol=tol)


def test_pages_per_block_fills_a_block_from_the_page_shape():
    """One block moves BLOCK_BYTES of K: 8 of phi4-mini's 32 KiB pages (GQA
    8 × 128, bf16), 64 of granite-20b's 4 KiB MQA pages; never more pages
    than a row holds, never fewer than one."""
    assert BLOCK_BYTES == 256 * 1024
    assert pages_per_block(16, 8, 128, 2, 128) == 8
    assert pages_per_block(16, 1, 128, 2, 128) == 64
    assert pages_per_block(16, 1, 128, 2, 40) == 40
    assert pages_per_block(16, 8, 128, 4, 128) == 4
    assert pages_per_block(1024, 8, 128, 4, 128) == 1


class TestPagePool:
    def test_alloc_grow_release_reuse(self):
        pool = PagePool(num_pages=4, page_size=8, max_pages_per_req=3)
        pool.admit(1)
        pool.append_tokens(1, 8)   # exactly one page
        assert pool.free_pages == 3
        pool.append_tokens(1, 1)   # crosses into page 2
        assert pool.free_pages == 2
        pt, lens = pool.tables([1])
        assert lens[0] == 9
        assert pt.shape == (1, 3)
        pool.release(1)
        assert pool.free_pages == 4

    def test_double_release_is_a_guarded_noop(self):
        """Releasing a rid twice (a preempt racing a finish, or a release
        after a crash swapped the pool) must not re-insert its pages into
        the free list — a double free would hand one page to two requests
        and silently corrupt both KV caches."""
        pool = PagePool(num_pages=4, page_size=8, max_pages_per_req=4)
        pool.admit(1)
        pool.append_tokens(1, 16)  # two pages
        assert pool.release(1) is True
        assert pool.free_pages == 4
        assert pool.release(1) is False  # second release: no-op
        assert pool.free_pages == 4  # and no free-list growth
        assert pool.release(99) is False  # never-admitted rid: same guard
        # the free list still hands out 4 distinct pages
        pool.admit(2)
        pool.append_tokens(2, 32)
        assert pool.free_pages == 0
        assert len(set(pool._requests[2].page_ids)) == 4

    def test_pool_exhaustion_signals_admission_control(self):
        pool = PagePool(num_pages=2, page_size=4, max_pages_per_req=4)
        pool.admit(1)
        pool.append_tokens(1, 8)  # both pages
        pool.admit(2)
        with pytest.raises(OutOfPages):
            pool.append_tokens(2, 1)

    def test_per_request_cap(self):
        pool = PagePool(num_pages=10, page_size=4, max_pages_per_req=2)
        pool.admit(1)
        with pytest.raises(OutOfPages):
            pool.append_tokens(1, 9)

    def test_hbm_budget_maps_to_slice_capacity(self):
        pool = PagePool(num_pages=1024, page_size=16, max_pages_per_req=64)
        b = pool.hbm_bytes(kv_heads=8, head_dim=128, n_layers=36)
        # qwen3-8b-ish: 2*1024*16*8*128*36*2 bytes
        assert b == 2 * 1024 * 16 * 8 * 128 * 36 * 2

    def test_tables_skip_idle_slots(self):
        """None entries (idle engine slots) produce the all-zero dummy row."""
        pool = PagePool(num_pages=8, page_size=4, max_pages_per_req=3)
        pool.admit(5)
        pool.append_tokens(5, 6)
        pt, lens = pool.tables([None, 5, None])
        assert lens.tolist() == [0, 6, 0]
        assert pt[0].tolist() == [0, 0, 0] and pt[2].tolist() == [0, 0, 0]
        assert pt[1, :2].tolist() == pool.request(5).page_ids

    def test_append_is_atomic_on_pool_exhaustion(self):
        """A failed grow must roll back mid-loop allocations: the request's
        record and the pool's free list are exactly as before the call."""
        pool = PagePool(num_pages=3, page_size=4, max_pages_per_req=8)
        pool.admit(1)
        pool.append_tokens(1, 4)  # 1 page
        pool.admit(2)
        pool.append_tokens(2, 1)  # 1 page
        free_before = list(pool._free)
        r = pool.request(1)
        pages_before, len_before = list(r.page_ids), r.length
        with pytest.raises(OutOfPages):
            pool.append_tokens(1, 12)  # needs 3 more pages, only 1 free
        assert pool._free == free_before
        assert r.page_ids == pages_before and r.length == len_before
        pool.append_tokens(1, 4)  # the single free page still works

    def test_append_is_atomic_on_per_request_cap(self):
        pool = PagePool(num_pages=16, page_size=4, max_pages_per_req=2)
        pool.admit(1)
        pool.append_tokens(1, 5)  # 2 pages
        free_before = pool.free_pages
        with pytest.raises(OutOfPages):
            pool.append_tokens(1, 8)
        assert pool.free_pages == free_before
        assert pool.request(1).length == 5

    @given(st.lists(st.integers(1, 30), min_size=1, max_size=12))
    @settings(max_examples=25, deadline=None)
    def test_no_page_leaks(self, growths):
        """Property: admit/grow/release conserves the page inventory."""
        pool = PagePool(num_pages=64, page_size=4, max_pages_per_req=16)
        rids = []
        for i, g in enumerate(growths):
            pool.admit(i)
            try:
                pool.append_tokens(i, g)
                rids.append(i)
            except OutOfPages:
                pool.release(i)
        for rid in rids:
            pool.release(rid)
        assert pool.free_pages == 64

    @given(st.lists(st.integers(1, 40), min_size=1, max_size=16),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_exhaustion_atomicity_property(self, growths, seed):
        """Property: every failed append leaves (free count, per-request
        lengths, per-request page counts) unchanged, and interleaved releases
        still conserve the inventory."""
        rng = np.random.default_rng(seed)
        pool = PagePool(num_pages=16, page_size=4, max_pages_per_req=6)
        live = {}
        for i, g in enumerate(growths):
            if live and rng.random() < 0.3:
                victim = sorted(live)[int(rng.integers(len(live)))]
                pool.release(victim)
                del live[victim]
            if i not in live:
                pool.admit(i)
                live[i] = True
            snapshot = (
                pool.free_pages,
                {r: (pool.request(r).length, len(pool.request(r).page_ids))
                 for r in live},
            )
            try:
                pool.append_tokens(i, g)
            except OutOfPages:
                after = (
                    pool.free_pages,
                    {r: (pool.request(r).length, len(pool.request(r).page_ids))
                     for r in live},
                )
                assert after == snapshot
        for r in list(live):
            pool.release(r)
        assert pool.free_pages == 16
