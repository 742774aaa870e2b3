"""Cached offloaded decode: the spill-able KV cache, bucketed compile-once
stepping, token-identical equivalence with the uncached path, and the
validated token contract."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import (ComputeOp, DecodeSpec, FetchOp, KVReadOp, KVWriteOp,
                        OffloadSession, PlanError, ReleaseOp, SpillableKVCache,
                        StreamPlan, memascend_policy)
from repro.core.buffer_pool import (KV_CLASS, AdaptiveBufferPool, PoolCensus,
                                    ShapeClass)
from repro.core.model_adapter import make_offloadable_lm
from repro.core.nvme import FilesystemEngine
from repro.core.pinned_alloc import AlignmentFreeAllocator
from repro.serve import OffloadedDecoder

CFG = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)


def _model(seed=0):
    return make_offloadable_lm(CFG, jax.random.PRNGKey(seed))


def _prompts(batch=2, seq=6, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(3, CFG.vocab, size=(batch, seq), dtype=np.int32)


# -- equivalence with the uncached path ---------------------------------------

@pytest.mark.parametrize("spec_kw", [
    {},                        # every page resident
    {"resident_blocks": 2},    # layer-equivalent budget
    {"resident_pages": 2},     # minimum paged budget: heavy spill traffic
    {"page_tokens": 4, "resident_pages": 3},   # pages finer than buckets
    {"page_tokens": 32, "resident_blocks": 2},  # whole-layer pages (PR 2)
])
def test_cached_matches_uncached_argmax(tmp_store_root, spec_kw):
    """Cached decode (all-resident AND spilling, across page sizes and
    budgets) emits token-identical greedy output to the full-prefix
    re-run path on a fixed prompt set."""
    prompts = _prompts()
    spec = DecodeSpec(batch=2, max_seq=32, bucket=8, **spec_kw)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "c",
                                                     lr=1e-3),
                          decode=spec) as dec:
        cached = dec.generate(prompts, 8)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "u",
                                                     lr=1e-3)) as dec:
        uncached = dec.generate(prompts, 8)
    np.testing.assert_array_equal(cached, uncached)


def test_use_cache_false_forces_uncached_path(tmp_store_root):
    prompts = _prompts()
    spec = DecodeSpec(batch=2, max_seq=32, bucket=8)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                     lr=1e-3),
                          decode=spec) as dec:
        cached = dec.generate(prompts, 4)
        uncached = dec.generate(prompts, 4, use_cache=False)
        np.testing.assert_array_equal(cached, uncached)
        assert dec.kv_stats is not None   # the cached run recorded stats


# -- bucketing: boundary crossings + compile-once ------------------------------

def test_bucket_boundary_crossing_stays_exact(tmp_store_root):
    """Generation crossing several time buckets (prompt pad, then two
    device-cache growths) matches the uncached path token for token."""
    prompts = _prompts(seq=3)
    spec = DecodeSpec(batch=2, max_seq=16, bucket=4)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "c",
                                                     lr=1e-3),
                          decode=spec) as dec:
        cached = dec.generate(prompts, 12)   # positions 3..14, buckets 4/8/12
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "u",
                                                     lr=1e-3)) as dec:
        uncached = dec.generate(prompts, 12)
    np.testing.assert_array_equal(cached, uncached)


def test_page_eviction_across_bucket_boundaries_stays_exact(tmp_store_root):
    """The page-table edge case: a minimum (2-slot) page budget forces
    evictions at every bucket/page boundary crossing while generation
    grows a fresh tail page — output must stay token-identical, and the
    paged spill traffic must be real (dirty writes AND free clean drops)."""
    prompts = _prompts(seq=3)
    spec = DecodeSpec(batch=2, max_seq=16, bucket=4, resident_pages=2)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "c",
                                                     lr=1e-3),
                          decode=spec) as dec:
        cached = dec.generate(prompts, 12)
        stats = dec.kv_stats
        assert stats["spills"] > 0 and stats["clean_drops"] > 0
        assert stats["refills"] > 0
        assert stats["spill_bytes"] < stats["refill_bytes"]  # clean drops
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "u",
                                                     lr=1e-3)) as dec:
        uncached = dec.generate(prompts, 12)
    np.testing.assert_array_equal(cached, uncached)


def test_second_sequence_over_reused_slots_stays_exact(tmp_store_root):
    """Page slots recycled across sequences (the 'one slot budget backs
    several short sequences' property): a second generate() with a
    different prompt set must not see the first sequence's K/V."""
    spec = DecodeSpec(batch=2, max_seq=32, bucket=8, resident_pages=2)
    p1, p2 = _prompts(seed=0), _prompts(seed=7)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "c",
                                                     lr=1e-3),
                          decode=spec) as dec:
        dec.generate(p1, 6)                  # dirties + spills slots
        second = dec.generate(p2, 6)         # reuses the same slots
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "u",
                                                     lr=1e-3)) as dec:
        uncached = dec.generate(p2, 6)
    np.testing.assert_array_equal(second, uncached)


def test_sync_and_full_overlap_decode_token_identical(tmp_store_root):
    """The KVReadOp split changes WHERE the gather + H2D run (inline on
    the compute thread vs staged on the worker), never the data: sync and
    full overlap must emit identical tokens, and only full uses staging."""
    from repro.core import OffloadPolicy

    spec = DecodeSpec(batch=2, max_seq=32, bucket=8, resident_pages=2)
    prompts = _prompts()

    def policy(sub, overlap):
        return (OffloadPolicy.preset("memascend")
                .with_store(tmp_store_root + sub).with_adam(lr=1e-3)
                .with_overlap(overlap).build())

    with OffloadedDecoder(_model(), policy("s", "sync"), decode=spec) as dec:
        sync_tokens = dec.generate(prompts, 8)
        assert dec.kv_overlap_stats["kv_stage_gets"] == 0   # inline path
    with OffloadedDecoder(_model(), policy("f", "full"), decode=spec) as dec:
        full_tokens = dec.generate(prompts, 8)
        assert dec.kv_overlap_stats["kv_stage_gets"] == 21  # 3 blocks x 7
    np.testing.assert_array_equal(sync_tokens, full_tokens)


def test_kv_h2d_runs_on_staging_worker_under_full_overlap(tmp_store_root):
    """The PR-3 leg extended to serving: under overlap="full" every decode
    step's KV window gather (page refill waits + host copies) runs on the
    H2D staging worker, never the compute thread, and the KVReadOps are
    served from staged futures."""
    import threading

    from repro.core.kv_cache import SpillableKVCache as KVC

    spec = DecodeSpec(batch=2, max_seq=32, bucket=8, resident_pages=2)
    policy = memascend_policy(tmp_store_root, lr=1e-3)
    assert policy.overlap == "full"
    gather_threads = []
    real_gather = KVC.gather_window

    def probe(self, unit, extent):
        gather_threads.append(threading.current_thread().name)
        return real_gather(self, unit, extent)

    with OffloadedDecoder(_model(), policy, decode=spec) as dec:
        try:
            KVC.gather_window = probe
            dec.generate(_prompts(), 6)
        finally:
            KVC.gather_window = real_gather
        snap = dec.session.overlap_snapshot()
    assert gather_threads and set(gather_threads) == {"offload-h2d"}
    # every block_step KVRead was served from the staging pipeline:
    # 3 blocks x 5 cached steps
    assert snap["kv_stage_gets"] == len(gather_threads) == 15
    assert snap["kv_stage_wait_seconds"] >= 0.0


def test_zero_retraces_after_first_token_per_bucket(tmp_store_root):
    """Each bucket traces once: a warm repeat of the same generation —
    which revisits every bucket — compiles nothing new, and within one
    bucket every step after the first reuses the trace."""
    prompts = _prompts(seq=3)
    spec = DecodeSpec(batch=2, max_seq=32, bucket=4)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                     lr=1e-3),
                          decode=spec) as dec:
        s = dec.session
        dec.generate(prompts, 10)
        warm = s.decode_compiles()
        dec.generate(prompts, 10)
        assert s.decode_compiles() == warm

        # step-by-step inside one fresh bucket: only the crossing retraces
        kv = s.open_kv_cache()
        try:
            logits = s.prefill(kv, prompts)           # length 3, bucket 4
            nxt = np.argmax(logits, axis=-1).astype(np.int32)[:, None]
            s.decode_step(kv, nxt)                    # length 3 -> 4
            s.decode_step(kv, nxt)                    # crosses into bucket 8
            after_crossing = s.decode_compiles()
            for _ in range(3):                        # stays inside bucket 8
                s.decode_step(kv, nxt)
            assert s.decode_compiles() == after_crossing
        finally:
            kv.close()


# -- the paged KV cache itself -------------------------------------------------

def _kv_fixture(tmp_store_root, units=("a", "b", "c"), resident=2,
                page_shape=(2, 1, 2, 1, 2), max_seq=4):
    """Paged cache over a real pool + store: pages of 2 tokens, 4-token
    capacity (2 pages per unit)."""
    from repro.core import MemoryTracker
    nbytes = int(np.prod(page_shape)) * 4
    census = PoolCensus((ShapeClass("w", 64, per_block=1),),
                        inflight_blocks=1).with_kv(nbytes, resident)
    alloc = AlignmentFreeAllocator(tracker=MemoryTracker(),
                                   component="pinned", backing="numpy")
    pool = AdaptiveBufferPool(census, alloc)
    store = FilesystemEngine(tmp_store_root)
    kv = SpillableKVCache(list(units), page_shape, max_seq, np.float32,
                          pool, store, resident_limit=resident)
    return kv, pool, store


def test_kv_page_spill_refill_round_trip(tmp_store_root):
    """Data written before a page spill comes back bit-identical through
    the real store — token-exact at page granularity, and only dirty
    pages pay a write."""
    kv, pool, store = _kv_fixture(tmp_store_root)
    rng = np.random.default_rng(0)
    k = rng.standard_normal((1, 3, 1, 2), dtype=np.float32)
    v = rng.standard_normal((1, 3, 1, 2), dtype=np.float32)
    # 3 units x 2 pages through a 2-slot budget: spill-after-use evicts
    kv.write_prefill("a", k, v)                # dirties pages 0 and 1
    assert kv.stats.spills >= 1
    assert store.contains("kv/a/p0000") and store.contains("kv/a/p0001")
    kg, vg = kv.gather_window("a", 3)          # sync page refills from SSD
    np.testing.assert_array_equal(kg, k)
    np.testing.assert_array_equal(vg, v)
    assert kv.stats.refills == 2 and kv.stats.sync_refills == 2
    assert kv.stats.spill_bytes == kv.stats.spills * kv.page_nbytes
    # the refilled pages are clean now: re-evicting them writes nothing
    spills_before, drops_before = kv.stats.spills, kv.stats.clean_drops
    kv.write_prefill("b", k, v)                # pushes a's pages back out
    assert kv.stats.spills == spills_before + 2   # b's own dirty pages
    assert kv.stats.clean_drops == drops_before + 2   # a's clean pages
    kv.close()
    assert pool.in_use_payload == 0
    kv.close()   # idempotent


def test_kv_prefetch_window_overlaps_and_hits(tmp_store_root):
    kv, pool, store = _kv_fixture(tmp_store_root, resident=3)
    z = np.zeros((1, 4, 1, 2), np.float32)
    for u in ("a", "b", "c"):
        kv.write_prefill(u, z, z)              # all spilled (keep budget 1)
    kv.prefetch_window("b", 2)                 # page 0 only
    kg, _vg = kv.gather_window("b", 2)
    assert kg.shape == (1, 2, 1, 2)
    assert kv.stats.prefetch_refills == 1
    kv.prefetch_window("b", 2)                 # resident: no-op
    assert kv.stats.prefetch_refills == 1
    kv.close()
    assert pool.in_use_payload == 0
    store.close()      # joins the async-read threads the prefetch started


def test_kv_cache_full_and_length_bounds(tmp_store_root):
    kv, _pool, _store = _kv_fixture(tmp_store_root, units=("a",), resident=2)
    kv.set_length(4)
    one = np.zeros((1, 1, 1, 2), np.float32)
    with pytest.raises(ValueError, match="full"):
        kv.append("a", one, one)
    with pytest.raises(ValueError, match="outside"):
        kv.set_length(5)
    kv.close()


def test_kv_resident_limit_validation(tmp_store_root):
    with pytest.raises(ValueError, match="resident_limit"):
        _kv_fixture(tmp_store_root, units=("a", "b", "c"), resident=1)


def test_kv_eviction_at_page_boundary_appends(tmp_store_root):
    """Appends crossing a page boundary materialize the fresh tail page,
    spill the full cold page, and a gather stitches both back exactly."""
    kv, pool, store = _kv_fixture(tmp_store_root, units=("a", "b"),
                                  resident=2)
    rng = np.random.default_rng(1)
    toks = [(rng.standard_normal((1, 1, 1, 2), dtype=np.float32),
             rng.standard_normal((1, 1, 1, 2), dtype=np.float32))
            for _ in range(3)]
    for _t, (k1, v1) in enumerate(toks):       # positions 0, 1, then 2:
        for u in ("a", "b"):                   # 2 -> second page of each
            kv.append(u, k1, v1)
        kv.advance()
    assert kv.length == 3
    assert store.contains("kv/a/p0000")        # cold page 0 spilled
    for u in ("a", "b"):
        kg, vg = kv.gather_window(u, 3)
        np.testing.assert_array_equal(
            kg, np.concatenate([k for k, _ in toks], axis=1))
        np.testing.assert_array_equal(
            vg, np.concatenate([v for _, v in toks], axis=1))
    kv.close()
    assert pool.in_use_payload == 0


def test_kv_slot_reuse_reads_zero_not_stale(tmp_store_root):
    """A page slot recycled from a previous sequence must read as zeros:
    stale K/V would poison the masked softmax (0 x NaN) and leak state
    across requests sharing the slot budget."""
    kv, pool, store = _kv_fixture(tmp_store_root, units=("a", "b"),
                                  resident=2)
    junk = np.full((1, 4, 1, 2), 7.5, np.float32)
    kv.write_prefill("a", junk, junk)
    kv.close()                                 # sequence 1 done, slots back
    kv2 = SpillableKVCache(["a", "b"], (2, 1, 2, 1, 2), 4, np.float32,
                           pool, store, resident_limit=2)
    one = np.ones((1, 1, 1, 2), np.float32)
    kv2.append("a", one, one)                  # page 0 reuses a slot
    kg, vg = kv2.gather_window("a", 2)
    np.testing.assert_array_equal(kg[:, 0], one[:, 0])
    assert (kg[:, 1:] == 0).all() and (vg[:, 1:] == 0).all()  # not 7.5
    kv2.close()
    assert pool.in_use_payload == 0


def test_kv_gather_zero_pads_unmaterialized_pages(tmp_store_root):
    """Windows can extend past the pages that exist (bucket > page size):
    the gather zero-fills them instead of wasting slots on garbage."""
    kv, _pool, _store = _kv_fixture(tmp_store_root, units=("a",),
                                    resident=2)
    one = np.ones((1, 1, 1, 2), np.float32)
    kv.append("a", one, one)                   # only page 0 materializes
    kg, vg = kv.gather_window("a", 4)          # full-capacity window
    assert kg.shape == (1, 4, 1, 2)
    assert (kg[:, 1:] == 0).all() and (vg[:, 1:] == 0).all()
    kv.close()


def test_h2d_copy_survives_source_buffer_reuse(tmp_store_root):
    """The H2D materialization barrier: a pool slot is reacquired (and
    overwritten by the next unit's SSD pread) the moment it is released,
    so ``_h2d_copy`` must have fully read the host view *before it
    returns* — ``copy=True`` alone dispatches asynchronously.  Without the
    barrier, decode computes with another tensor's weights (caught live as
    nondeterministic logits at bench scale)."""
    with OffloadSession(_model(), memascend_policy(tmp_store_root, lr=1e-3),
                        mode="serve") as s:
        src = np.arange(4096, dtype=np.float32)
        view = src[256:2304]                 # a slot-interior view, as used
        dev = s._h2d_copy(view)
        expect = view.copy()
        view[:] = -1.0                       # slot recycled: pread lands
        np.testing.assert_array_equal(np.asarray(dev), expect)


# -- pool integration ----------------------------------------------------------

def test_session_census_reserves_kv_slots(tmp_store_root):
    spec = DecodeSpec(batch=2, max_seq=16, bucket=8, resident_blocks=2)
    with OffloadSession(_model(), memascend_policy(tmp_store_root, lr=1e-3),
                        mode="serve", decode=spec) as s:
        stats = s.pool.stats()
        # page-granular AND per-slot: 2 layer-equivalents x (16/8 =) 2 pages
        # per seq x batch 2 slots; each page holds one request's rows
        assert stats["slots"][KV_CLASS] == 8
        expected = 2 * 1 * 8 * CFG.n_kv_heads * CFG.head_dim * 2  # bf16 page
        assert stats["slot_size"][KV_CLASS] == expected


def test_session_census_reserves_explicit_page_budget(tmp_store_root):
    spec = DecodeSpec(batch=2, max_seq=16, bucket=8, page_tokens=4,
                      resident_pages=3)
    with OffloadSession(_model(), memascend_policy(tmp_store_root, lr=1e-3),
                        mode="serve", decode=spec) as s:
        stats = s.pool.stats()
        # resident_pages caps the per-request budget; the census scales it
        # by the batch's slot count
        assert stats["slots"][KV_CLASS] == 3 * 2
        expected = 2 * 1 * 4 * CFG.n_kv_heads * CFG.head_dim * 2  # bf16 page
        assert stats["slot_size"][KV_CLASS] == expected


def test_pool_slots_released_on_mid_generate_failure(tmp_store_root):
    """A block_step failure mid-generate must leak nothing: weight slots
    drain via the executor's error path, KV slots via generate's finally."""
    prompts = _prompts()
    spec = DecodeSpec(batch=2, max_seq=32, bucket=8, resident_blocks=2)
    dec = OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                      lr=1e-3), decode=spec)
    s = dec.session
    calls = {"n": 0}
    real_step = s._jit_block_step

    def flaky_step(params, h, k, v, cache_len, **kw):
        calls["n"] += 1
        if calls["n"] == 4:     # second decode step, mid-stack
            raise RuntimeError("injected step failure")
        return real_step(params, h, k, v, cache_len, **kw)

    s._jit_block_step = flaky_step
    with pytest.raises(RuntimeError, match="injected"):
        dec.generate(prompts, 8)
    assert s.pool.in_use_payload == 0          # weights AND kv slots back
    assert len(s.swapper._inflight) == 0
    assert dec.kv_stats is not None
    # the session is still usable: a fresh cache can be opened
    s._jit_block_step = real_step
    gen = dec.generate(prompts, 2)
    assert gen.shape == (2, 2)
    dec.close()
    s.tracker.assert_quiescent()


def test_only_one_open_kv_cache(tmp_store_root):
    spec = DecodeSpec(batch=1, max_seq=8, bucket=8)
    with OffloadSession(_model(), memascend_policy(tmp_store_root, lr=1e-3),
                        mode="serve", decode=spec) as s:
        kv = s.open_kv_cache()
        with pytest.raises(RuntimeError, match="already open"):
            s.open_kv_cache()
        kv.close()
        s.open_kv_cache().close()


# -- the validated token contract ---------------------------------------------

def test_token_contract_rejects_bad_inputs(tmp_store_root):
    spec = DecodeSpec(batch=2, max_seq=16, bucket=8)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                     lr=1e-3),
                          decode=spec) as dec:
        good = _prompts(seq=4)
        with pytest.raises(TypeError, match="integer"):
            dec.step_logits(good.astype(np.float32))
        with pytest.raises(ValueError, match=r"\(batch, time\)"):
            dec.step_logits(good[0])
        with pytest.raises(ValueError, match="negative"):
            dec.generate(good - 500, 2)
        with pytest.raises(ValueError, match="new_tokens"):
            dec.generate(good, 0)
        with pytest.raises(ValueError, match="batch"):
            dec.generate(_prompts(batch=3, seq=4), 2)
        with pytest.raises(ValueError, match="max_seq"):
            dec.generate(good, 13)
        # int64 ids are fine — converted, not rejected
        gen = dec.generate(good.astype(np.int64), 2)
        assert gen.dtype == np.int32 and gen.shape == (2, 2)


def test_use_cache_requires_decode_spec(tmp_store_root):
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                     lr=1e-3)) as dec:
        assert dec.decode_spec is None
        with pytest.raises(RuntimeError, match="DecodeSpec"):
            dec.generate(_prompts(), 2, use_cache=True)


def test_decoder_rejects_session_plus_decode(tmp_store_root):
    with OffloadSession(_model(), memascend_policy(tmp_store_root, lr=1e-3),
                        mode="serve") as s, \
            pytest.raises(ValueError, match="decode="):
        OffloadedDecoder(None, None, session=s,
                         decode=DecodeSpec(batch=1, max_seq=8, bucket=8))


def test_decode_spec_validation():
    with pytest.raises(ValueError, match="resident_blocks"):
        DecodeSpec(batch=1, max_seq=8, bucket=8, resident_blocks=1)
    with pytest.raises(ValueError, match="bucket"):
        DecodeSpec(batch=1, max_seq=8, bucket=16)
    with pytest.raises(ValueError, match="batch"):
        DecodeSpec(batch=0, max_seq=8, bucket=8)
    spec = DecodeSpec(batch=1, max_seq=20, bucket=8)
    assert spec.bucket_len(1) == 8
    assert spec.bucket_len(8) == 8
    assert spec.bucket_len(9) == 16
    assert spec.bucket_len(17) == 20   # clamped to capacity
    with pytest.raises(ValueError, match="exceeds"):
        spec.bucket_len(21)


def test_decode_spec_page_knobs():
    # defaults: pages are bucket-sized
    spec = DecodeSpec(batch=1, max_seq=20, bucket=8)
    assert spec.page_size == 8 and spec.pages_per_seq == 3
    assert spec.page_budget(n_blocks=4) == 12       # all resident
    assert DecodeSpec(batch=1, max_seq=20, bucket=8,
                      resident_blocks=2).page_budget(4) == 6
    assert DecodeSpec(batch=1, max_seq=20, bucket=8,
                      resident_pages=5).page_budget(4) == 5
    # page finer than bucket, and whole-layer pages (the PR-2 ablation)
    assert DecodeSpec(batch=1, max_seq=16, bucket=8,
                      page_tokens=4).pages_per_seq == 4
    assert DecodeSpec(batch=1, max_seq=16, bucket=8,
                      page_tokens=16).pages_per_seq == 1
    with pytest.raises(ValueError, match="align"):
        DecodeSpec(batch=1, max_seq=16, bucket=8, page_tokens=6)
    with pytest.raises(ValueError, match="page_tokens"):
        DecodeSpec(batch=1, max_seq=16, bucket=8, page_tokens=32)
    with pytest.raises(ValueError, match="resident_pages"):
        DecodeSpec(batch=1, max_seq=16, bucket=8, resident_pages=1)
    with pytest.raises(ValueError, match="not both"):
        DecodeSpec(batch=1, max_seq=16, bucket=8, resident_blocks=2,
                   resident_pages=4)


def test_session_requires_cached_applies(tmp_store_root):
    headless = dataclasses.replace(_model(), block_step=None)
    with pytest.raises(ValueError, match="cached-decode applies"):
        OffloadSession(headless, memascend_policy(tmp_store_root, lr=1e-3),
                       mode="serve",
                       decode=DecodeSpec(batch=1, max_seq=8, bucket=8))


# -- plan validator: the KV lifecycle ------------------------------------------

def test_validator_step_without_kv_read():
    with pytest.raises(PlanError, match="no KV read"):
        StreamPlan("bad", (FetchOp("u"), ComputeOp("u", "block_step"),
                           KVWriteOp("u"), ReleaseOp("u")))


def test_validator_double_kv_read():
    with pytest.raises(PlanError, match="double KV read"):
        StreamPlan("bad", (KVReadOp("u"), KVReadOp("u")))


def test_validator_kv_write_without_produce():
    with pytest.raises(PlanError, match="no K/V produced"):
        StreamPlan("bad", (KVWriteOp("u"),))


def test_validator_kv_write_mode_must_match_producer():
    with pytest.raises(PlanError, match="does not match its producing"):
        StreamPlan("bad", (FetchOp("u"),
                           ComputeOp("u", "block_prefill"),
                           KVWriteOp("u", "step"), ReleaseOp("u")))
    with pytest.raises(PlanError, match="does not match its producing"):
        StreamPlan("bad", (FetchOp("u"), KVReadOp("u"),
                           ComputeOp("u", "block_step"),
                           KVWriteOp("u", "prefill"), ReleaseOp("u")))
    with pytest.raises(PlanError, match="unknown KV write mode"):
        StreamPlan("bad", (FetchOp("u"),
                           ComputeOp("u", "block_prefill"),
                           KVWriteOp("u", "scatter"), ReleaseOp("u")))


def test_validator_kv_read_never_consumed():
    with pytest.raises(PlanError, match="never consumed"):
        StreamPlan("bad", (KVReadOp("u"),))


def test_validator_kv_never_written():
    with pytest.raises(PlanError, match="never written"):
        StreamPlan("bad", (FetchOp("u"),
                           ComputeOp("u", "block_prefill"),
                           ReleaseOp("u")))
