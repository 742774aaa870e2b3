"""Speculative decoding over the paged serve path: per-slot KV rollback
(truncation across page/bucket boundaries, spilled-page no-resurrection,
in-flight transfer safety), verify-window bitwise identity with the
sequential step chain, and end-to-end token identity of ``generate(spec=)``
and the spec-decoding ServingEngine with plain greedy."""

import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import (DecodeSpec, MemoryTracker, PlanError,
                        SpillableKVCache, memascend_policy)
from repro.core.buffer_pool import (AdaptiveBufferPool, PoolCensus,
                                    ShapeClass)
from repro.core.model_adapter import make_offloadable_lm
from repro.core.nvme import FilesystemEngine
from repro.core.pinned_alloc import AlignmentFreeAllocator
from repro.core.session import verify_bucket
from repro.core.stream_plan import (ComputeOp, KVReadOp, KVWriteOp,
                                    compile_decode_verify)
from repro.serve import (NGramDraft, OffloadedDecoder, Request,
                         ServingEngine, SpecConfig)

CFG = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)


def _model(seed=0):
    return make_offloadable_lm(CFG, jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    return _model()


def _slotted_kv(tmp_store_root, units=("a",), slots=2, resident=4,
                max_seq=8, store=None):
    """Paged cache with batch slots over a real pool + store: per-slot
    single-row pages of 2 tokens, so rollback boundaries land mid-page,
    on-page, and across pages within a handful of tokens."""
    page_shape = (2, 1, 2, 1, 2)
    nbytes = int(np.prod(page_shape)) * 4
    census = PoolCensus((ShapeClass("w", 64, per_block=1),),
                        inflight_blocks=1).with_kv(nbytes, resident)
    alloc = AlignmentFreeAllocator(tracker=MemoryTracker(),
                                   component="pinned", backing="numpy")
    pool = AdaptiveBufferPool(census, alloc)
    store = store or FilesystemEngine(tmp_store_root)
    kv = SpillableKVCache(list(units), page_shape, max_seq, np.float32,
                          pool, store, resident_limit=resident, slots=slots)
    return kv, pool, store


def _window(batch, k, base=1.0):
    """(batch, k, 1, 2) K/V windows with per-(slot, position) unique
    values so truncation and resurrection are detectable bitwise."""
    arr = np.zeros((batch, k, 1, 2), np.float32)
    for b in range(batch):
        for t in range(k):
            arr[b, t] = base + 10 * b + t
    return arr


# -- rollback: truncation mechanics -------------------------------------------

def test_rollback_truncates_across_page_boundary(tmp_store_root):
    """Rolling back from 3 tokens to 1 drops page 1 entirely (its slot
    returns to the pool, the page rereads as zeros) while page 0 keeps
    the surviving prefix bitwise; a later append overwrites the stale
    tail byte of the kept partial page."""
    kv, pool, _store = _slotted_kv(tmp_store_root)
    k3, v3 = _window(2, 3), _window(2, 3, base=100.0)
    kv.append_window("a", k3, v3)
    for s in (0, 1):
        kv.rollback(s, 3)                      # commit all 3 (pure advance)
    assert kv.stats.rollback_pages == 0        # advance drops nothing
    kv.rollback(0, 1)                          # truncate: page 1 dropped
    assert kv.slot_length(0) == 1 and kv.slot_length(1) == 3
    assert kv.stats.rollback_pages == 1
    kg, vg = kv.gather_window("a", 4)
    np.testing.assert_array_equal(kg[0, 0], k3[0, 0])      # kept prefix
    assert (kg[0, 2:] == 0).all()                          # dropped page
    np.testing.assert_array_equal(kg[1, :3], k3[1])        # other slot
    np.testing.assert_array_equal(vg[1, :3], v3[1])
    # the kept partial page's stale tail byte is overwritten by the next
    # append, exactly as a sequential decode would have written it
    one_k, one_v = _window(2, 1, base=50.0), _window(2, 1, base=60.0)
    kv.append_window("a", one_k, one_v)
    kg2, _ = kv.gather_window("a", 4)
    np.testing.assert_array_equal(kg2[0, 1], one_k[0, 0])
    kv.close()
    assert pool.in_use_payload == 0


def test_rollback_across_bucket_boundary(tmp_store_root):
    """A rollback crossing a time-bucket boundary (4 -> 1 with 2-token
    pages) drops every page past the new tail and the cache keeps
    serving appends from the truncated length."""
    kv, pool, _store = _slotted_kv(tmp_store_root, resident=6)
    k4, v4 = _window(2, 4), _window(2, 4, base=100.0)
    kv.append_window("a", k4, v4)
    kv.rollback(0, 4)
    kv.rollback(1, 1)                          # 2 pages -> partial page 0
    assert kv.stats.rollback_pages == 1
    assert kv.slot_length(1) == 1
    k2, v2 = _window(2, 2, base=200.0), _window(2, 2, base=300.0)
    kv.append_window("a", k2, v2)              # slot1 writes at 1..2
    kv.rollback(0, 5)
    kv.rollback(1, 3)
    kg, _ = kv.gather_window("a", 6)
    np.testing.assert_array_equal(kg[1, 0], k4[1, 0])
    np.testing.assert_array_equal(kg[1, 1:3], k2[1])
    np.testing.assert_array_equal(kg[0, :4], k4[0])
    np.testing.assert_array_equal(kg[0, 4], k2[0, 0])
    kv.close()
    assert pool.in_use_payload == 0


def test_rollback_dirty_spilled_page_not_resurrected(tmp_store_root):
    """A dirty page that reached the SSD before its tokens were rejected
    must NOT come back: rollback forgets the spilled key, so the page
    rereads as zeros even though the store may still hold the bytes."""
    kv, pool, store = _slotted_kv(tmp_store_root, resident=2)
    k4, v4 = _window(2, 4), _window(2, 4, base=100.0)
    kv.append_window("a", k4, v4)              # 4 pages through 2 slots
    assert kv.stats.spills >= 1
    spilled_keys = [f"kv/a/s{s:02d}/p{p:04d}" for s in (0, 1)
                    for p in (0, 1) if store.contains(
                        f"kv/a/s{s:02d}/p{p:04d}")]
    assert spilled_keys                         # something hit the SSD
    kv.rollback(0, 1)                           # reject slot 0's page 1
    kv.rollback(1, 4)
    kg, vg = kv.gather_window("a", 4)
    assert (kg[0, 2:] == 0).all() and (vg[0, 2:] == 0).all()
    np.testing.assert_array_equal(kg[1], k4[1])  # slot 1 survives, bitwise
    kv.close()
    assert pool.in_use_payload == 0


def test_rollback_waits_for_pinned_page(tmp_store_root):
    """Rollback while a dropped-range page is pinned (staging worker
    mid-copy) blocks until the pin clears instead of yanking the buffer
    or raising — the 'un-pin in-flight gathers safely' contract."""
    kv, pool, _store = _slotted_kv(tmp_store_root)
    k2, v2 = _window(2, 2), _window(2, 2, base=100.0)
    kv.append_window("a", k2, v2)
    kv.ensure_page("a", 0, slot=0, pin=True)    # reader holds the page
    done = threading.Event()

    def _roll():
        kv.rollback(0, 0)                       # drops page 0 -> must wait
        done.set()

    t = threading.Thread(target=_roll)
    t.start()
    time.sleep(0.05)
    assert not done.is_set()                    # blocked on the pin
    kv.unpin("a", 0, slot=0)
    t.join(timeout=10.0)
    assert done.is_set()
    assert kv.slot_length(0) == 0
    kg, _ = kv.gather_window("a", 2)
    assert (kg[0] == 0).all()
    kv.close()
    assert pool.in_use_payload == 0


def test_rollback_with_inflight_refill_future(tmp_store_root):
    """Rollback of a page whose async SSD refill is still in flight on
    the transfer worker: the future is settled and its buffer released —
    the refilled bytes never land back in the cache."""
    class GatedStore(FilesystemEngine):
        def __init__(self, root):
            super().__init__(root)
            self.gate = threading.Event()

        def read_async(self, key, view):
            inner = super().read_async
            from concurrent.futures import ThreadPoolExecutor
            pool = ThreadPoolExecutor(1)

            def _wait_then_read():
                assert self.gate.wait(timeout=10.0)
                return inner(key, view).result()
            fut = pool.submit(_wait_then_read)
            pool.shutdown(wait=False)
            return fut

    store = GatedStore(tmp_store_root)
    kv, pool, _ = _slotted_kv(tmp_store_root, resident=5, store=store)
    k4, v4 = _window(2, 4), _window(2, 4, base=100.0)
    kv.append_window("a", k4, v4)
    kv.rollback(0, 4)
    kv.rollback(1, 4)
    target = ("a", 0, 1)
    with kv._lock:                     # force-spill exactly the target page
        kv._use_order.remove(target)
        kv._use_order.append(target)
        assert kv._try_spill_one(set())
        assert target in kv._spilled
    kv.prefetch_window("a", 4)         # async refill: gated in flight
    with kv._lock:
        assert target in kv._futures
    done = threading.Event()

    def _roll():
        kv.rollback(0, 1)
        done.set()

    t = threading.Thread(target=_roll)
    t.start()
    time.sleep(0.05)
    store.gate.set()                           # let the refill finish
    t.join(timeout=10.0)
    assert done.is_set()
    kg, _ = kv.gather_window("a", 4)
    assert (kg[0, 2:] == 0).all()              # refill did not resurrect
    kv.close()
    assert pool.in_use_payload == 0
    store.close()      # joins the async-read threads the refill started


def test_rollback_validation(tmp_store_root):
    kv, _pool, _store = _slotted_kv(tmp_store_root)
    kv.retire(1)
    with pytest.raises(RuntimeError, match="retired"):
        kv.rollback(1, 0)
    with pytest.raises(ValueError, match="length"):
        kv.rollback(0, 99)                     # beyond capacity
    with pytest.raises(ValueError, match="slot"):
        kv.rollback(7, 0)
    kv.close()
    with pytest.raises(RuntimeError, match="closed"):
        kv.rollback(0, 0)


# -- verify plan + bucketing ---------------------------------------------------

def test_verify_bucket_powers_of_two():
    assert [verify_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        verify_bucket(0)


def test_decode_verify_plan_structure(model):
    plan = compile_decode_verify(model)
    blocks = [f"block_{i:03d}" for i in range(CFG.n_layers)]
    assert plan.fetch_order == tuple(["embed"] + blocks + ["head"])
    for b in blocks:
        kinds = [op for op in plan.ops
                 if getattr(op, "unit", None) == b
                 and isinstance(op, (KVReadOp, ComputeOp, KVWriteOp))]
        assert isinstance(kinds[0], KVReadOp)
        assert isinstance(kinds[1], ComputeOp)
        assert kinds[1].kind == "block_verify"
        assert isinstance(kinds[2], KVWriteOp)
        assert kinds[2].mode == "verify"


def test_decode_verify_plan_requires_block_verify(model):
    import dataclasses
    headless = dataclasses.replace(model, block_verify=None)
    with pytest.raises(PlanError, match="block_verify"):
        compile_decode_verify(headless)


# -- verify step: bitwise identity with the sequential chain -------------------

def test_verify_logits_match_sequential_steps(tmp_store_root):
    """Every window position's verify logits are bitwise the sequential
    decode_step chain's, and neither lengths nor output drift after a
    partial-commit rollback."""
    from repro.core import OffloadSession
    spec = DecodeSpec(batch=2, max_seq=64, bucket=16)
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, CFG.vocab, (2, 7)).astype(np.int32)
    window = rng.integers(3, CFG.vocab, (2, 5)).astype(np.int32)

    with OffloadSession(_model(), memascend_policy(tmp_store_root + "a",
                                                   lr=1e-3),
                        mode="serve", decode=spec) as sess:
        kv = sess.open_kv_cache()
        sess.prefill(kv, prompt)
        seq = [sess.decode_step(kv, window[:, j:j + 1]) for j in range(5)]
        kv.close()

    with OffloadSession(_model(), memascend_policy(tmp_store_root + "b",
                                                   lr=1e-3),
                        mode="serve", decode=spec) as sess:
        kv = sess.open_kv_cache()
        sess.prefill(kv, prompt)
        base = kv.length
        vlg = sess.verify_step(kv, window)     # padded to 8 internally
        assert vlg.shape == (2, 5, CFG.vocab)
        for j in range(5):
            np.testing.assert_array_equal(vlg[:, j], seq[j])
        assert kv.length == base               # no advance
        for s in sorted(kv.active):
            kv.rollback(s, base + 3)           # commit 3, reject the tail
        after = sess.decode_step(kv, window[:, 3:4])
        np.testing.assert_array_equal(after, seq[3])
        kv.close()


def test_verify_step_slots_ragged_lengths(tmp_store_root):
    """Per-slot verify at ragged lengths matches each lane's sequential
    chain and leaves every slot's length untouched."""
    from repro.core import OffloadSession
    spec = DecodeSpec(batch=2, max_seq=64, bucket=16)
    rng = np.random.default_rng(1)
    prompt = rng.integers(3, CFG.vocab, (2, 6)).astype(np.int32)
    step1 = rng.integers(3, CFG.vocab, (2, 1)).astype(np.int32)
    w = rng.integers(3, CFG.vocab, (2, 3)).astype(np.int32)

    def drive(sess, kv):
        sess.prefill(kv, prompt)
        sess.decode_step_slots(kv, step1)
        kv.rollback(0, kv.slot_length(0) - 1)   # make lengths ragged

    with OffloadSession(_model(), memascend_policy(tmp_store_root + "a",
                                                   lr=1e-3),
                        mode="serve", decode=spec) as sess:
        kv = sess.open_kv_cache()
        drive(sess, kv)
        ref = [sess.decode_step_slots(kv, w[:, j:j + 1]) for j in range(3)]
        kv.close()

    with OffloadSession(_model(), memascend_policy(tmp_store_root + "b",
                                                   lr=1e-3),
                        mode="serve", decode=spec) as sess:
        kv = sess.open_kv_cache()
        drive(sess, kv)
        lens = {s: kv.slot_length(s) for s in sorted(kv.active)}
        vlg = sess.verify_step_slots(kv, w)
        for j in range(3):
            np.testing.assert_array_equal(vlg[:, j], ref[j])
        assert {s: kv.slot_length(s) for s in sorted(kv.active)} == lens
        kv.close()


# -- draft sources -------------------------------------------------------------

def test_ngram_draft_most_recent_match_wins():
    d = NGramDraft(gram=2)
    ctx = np.array([5, 6, 7, 8, 5, 6, 9, 1, 5, 6], np.int32)
    np.testing.assert_array_equal(d.propose(ctx, 2), [9, 1])
    np.testing.assert_array_equal(d.propose(ctx, 4), [9, 1, 5, 6])


def test_ngram_draft_no_match_and_bounds():
    d = NGramDraft(gram=3)
    assert d.propose(np.array([1, 2, 3], np.int32), 4).size == 0
    assert d.propose(np.array([1, 2, 3, 1, 2, 3], np.int32), 0).size == 0
    np.testing.assert_array_equal(
        d.propose(np.array([1, 2, 3, 9, 1, 2, 3], np.int32), 2), [9, 1])
    with pytest.raises(ValueError):
        NGramDraft(gram=0)
    with pytest.raises(ValueError):
        SpecConfig(k=0)


# -- end to end: token identity ------------------------------------------------

def test_generate_spec_matches_plain_greedy(tmp_store_root):
    """The acceptance gate for the joint path: generate(spec=) emits
    bit-identical tokens to the plain cached greedy loop, while actually
    committing more than one token per streamed pass."""
    rng = np.random.default_rng(1)
    pat = rng.integers(3, 40, 6)
    prompt = np.tile(pat, 4)[None, :].repeat(2, axis=0).astype(np.int32)
    spec = DecodeSpec(batch=2, max_seq=96, bucket=16)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "p",
                                                     lr=1e-3),
                          decode=spec) as dec:
        plain = dec.generate(prompt, 48)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "s",
                                                     lr=1e-3),
                          decode=spec) as dec:
        fast = dec.generate(prompt, 48, spec=SpecConfig(k=4))
        st = dec.spec_stats
    np.testing.assert_array_equal(plain, fast)
    assert st.rounds < 47            # fewer passes than plain's steps
    assert st.accepted_per_step > 1.0
    assert st.committed_tokens == 47 * 2   # everything after the prefill


def test_generate_spec_rejects_uncached(tmp_store_root):
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root,
                                                     lr=1e-3),
                          decode=DecodeSpec(batch=1, max_seq=32,
                                            bucket=8)) as dec, \
            pytest.raises(ValueError, match="cached"):
        dec.generate(np.ones((1, 4), np.int32), 4, use_cache=False,
                     spec=SpecConfig())


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


def test_serving_engine_spec_matches_plain(tmp_store_root):
    """Mixed accept/reject across slots: the spec-decoding engine serves
    ragged arrivals with per-slot rollback and emits, per request, the
    same tokens as the plain engine (itself pinned to solo greedy)."""
    rng = np.random.default_rng(2)
    pat = rng.integers(3, 40, 5)

    def reqs():
        return [Request(rid=f"r{i}",
                        prompt=np.tile(pat, 2 + i).astype(np.int32),
                        max_new_tokens=8 + 3 * i,
                        arrival=0.05 * i) for i in range(4)]

    spec = DecodeSpec(batch=2, max_seq=96, bucket=16)
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "p",
                                                     lr=1e-3),
                          decode=spec) as dec:
        clk = _FakeClock()
        plain = ServingEngine(dec, clock=clk, sleep=clk.sleep).run(reqs())
    with OffloadedDecoder(_model(), memascend_policy(tmp_store_root + "s",
                                                     lr=1e-3),
                          decode=spec) as dec:
        clk = _FakeClock()
        fast = ServingEngine(dec, spec=SpecConfig(k=4), clock=clk,
                             sleep=clk.sleep).run(reqs())
        assert dec.spec_stats is not None
    assert len(fast.completed) == len(plain.completed) == 4
    for rp, rs in zip(plain.completed, fast.completed, strict=True):
        assert rp.rid == rs.rid
        assert rp.output == rs.output
    assert fast.spec_rounds > 0
    # every token after each request's prefill-emitted first one came
    # through a spec round
    total = sum(r.metrics.tokens_out for r in fast.completed)
    assert fast.spec_committed == total - len(fast.completed)
    assert fast.accepted_per_step > 0.0
    assert fast.kv_stats["rollbacks"] > 0
