"""Host Adam: streamed subgroups vs in-memory reference; bf16 state mode;
the tiled update against the whole-array formula."""

import os
import sys
import threading
import tracemalloc

import numpy as np
import pytest

from repro.core import (AdamConfig, DirectNVMeEngine, MemoryTracker,
                        OffloadedAdam, adam_update, optimizer)
from repro.core.optimizer import TILE, StagedSubgroup, TilePool
from repro.core.overlap import OverlapStats


def reference_adam(w0, grads, cfg):
    """Plain in-memory Adam over a list of per-step grads."""
    m = np.zeros_like(w0)
    v = np.zeros_like(w0)
    w = w0.copy()
    for t, g in enumerate(grads, start=1):
        adam_update(w, g, m, v, t, cfg)
    return w


def test_streamed_matches_reference(tmp_store_root, rng):
    eng = DirectNVMeEngine(tmp_store_root, n_devices=2,
                           device_capacity=1 << 24)
    cfg = AdamConfig(lr=1e-2, weight_decay=0.01)
    opt = OffloadedAdam(eng, cfg, tracker=MemoryTracker())
    w0 = rng.standard_normal((64, 48)).astype(np.float32)
    grads = [rng.standard_normal((64, 48)).astype(np.float32)
             for _ in range(5)]
    opt.register("w", w0)
    for g in grads:
        opt.begin_step()
        opt.step_subgroup("w", g)
    ref = reference_adam(w0, grads, cfg)
    got = eng.read_new("w.master", np.float32, w0.shape)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
    opt.close()   # shuts the write-back executor down (leak guard)
    eng.close()


def test_bf16_state_mode_tracks_fp32(tmp_store_root, rng):
    eng = DirectNVMeEngine(tmp_store_root, n_devices=1,
                           device_capacity=1 << 24)
    cfg32 = AdamConfig(lr=1e-2)
    cfg16 = AdamConfig(lr=1e-2, state_dtype="bfloat16")
    o32 = OffloadedAdam(eng, cfg32, tracker=MemoryTracker())
    o16 = OffloadedAdam(eng, cfg16, tracker=MemoryTracker())
    w0 = rng.standard_normal(2048).astype(np.float32)
    o32.register("a", w0)
    o16.register("b", w0)
    for _ in range(3):
        g = rng.standard_normal(2048).astype(np.float32)
        o32.begin_step(); w_a = o32.step_subgroup("a", g)
        o16.begin_step(); w_b = o16.step_subgroup("b", g)
    # bf16 states track fp32 within truncation error
    err = np.abs(w_a.astype(np.float32) - w_b.astype(np.float32)).max()
    assert err < 0.05
    # and cut the I/O volume roughly in half (paper Fig. 20)
    assert o16.last_io_bytes < 0.6 * o32.last_io_bytes
    o32.close()
    o16.close()
    eng.close()


def test_io_accounting_matches_formula(tmp_store_root, rng):
    eng = DirectNVMeEngine(tmp_store_root, n_devices=1,
                           device_capacity=1 << 24)
    for state_dtype in ("float32", "bfloat16"):
        cfg = AdamConfig(state_dtype=state_dtype)
        opt = OffloadedAdam(eng, cfg, tracker=MemoryTracker())
        n = 4096
        opt.register(f"w-{state_dtype}", np.zeros(n, np.float32))
        opt.begin_step()
        opt.step_subgroup(f"w-{state_dtype}", np.zeros(n, np.float32))
        s = cfg.state_np_dtype.itemsize
        c = cfg.compute_np_dtype.itemsize
        assert opt.last_io_bytes == n * (6 * s + c)
        opt.close()
    eng.close()


def test_skipped_step_changes_nothing(tmp_store_root, rng):
    """Overflow-skipped steps must leave SSD state untouched (the engine
    simply doesn't call step_subgroup)."""
    eng = DirectNVMeEngine(tmp_store_root, n_devices=1,
                           device_capacity=1 << 24)
    opt = OffloadedAdam(eng, AdamConfig(), tracker=MemoryTracker())
    w0 = rng.standard_normal(128).astype(np.float32)
    opt.register("w", w0)
    before = eng.read_new("w.master", np.float32, w0.shape).copy()
    opt.begin_step()   # begun but no subgroup streamed = skipped
    np.testing.assert_array_equal(
        eng.read_new("w.master", np.float32, w0.shape), before)
    opt.close()
    eng.close()


def test_split_halves_compose_to_step_subgroup(tmp_store_root, rng):
    """issue/compute/commit run separately must be byte-identical to the
    one-call step_subgroup (the pipelined executor uses the halves)."""
    cfg = AdamConfig(lr=1e-2, weight_decay=0.01)
    w0 = rng.standard_normal((32, 24)).astype(np.float32)
    grads = [rng.standard_normal((32, 24)).astype(np.float32)
             for _ in range(3)]
    masters = {}
    for mode in ("fused", "split"):
        eng = DirectNVMeEngine(f"{tmp_store_root}/{mode}", n_devices=1,
                               device_capacity=1 << 24)
        opt = OffloadedAdam(eng, cfg, tracker=MemoryTracker())
        opt.register("w", w0)
        for g in grads:
            opt.begin_step()
            if mode == "fused":
                opt.step_subgroup("w", g)
            else:
                staged = opt.issue_subgroup("w")
                opt.compute_subgroup(staged, g)
                opt.commit_subgroup(staged)
        assert opt.staging_idle()
        masters[mode] = eng.read_new("w.master", np.float32, w0.shape)
        opt.close()
        eng.close()
    np.testing.assert_array_equal(masters["fused"].view(np.uint8),
                                  masters["split"].view(np.uint8))


def test_staging_arena_charge_and_bf16_scratch(tmp_store_root, rng):
    """The double-buffered arena is one tracked allocation sized
    2 x (3 x max-subgroup fp32 + truncation scratch); the former untracked
    astype transients are gone.  bf16 state mode needs a scratch (reads
    and write-backs pass through it); pure-fp32 mode needs none."""
    for state_dtype, compute_dtype, scratch_per_elem in (
            ("float32", "float32", 0),
            ("float32", "bfloat16", 2),
            # bf16 states: 3 concurrently-written bf16 regions + compute
            ("bfloat16", "bfloat16", 3 * 2 + 2)):
        t = MemoryTracker()
        eng = DirectNVMeEngine(
            f"{tmp_store_root}/{state_dtype}-{compute_dtype}",
            n_devices=1, device_capacity=1 << 24)
        opt = OffloadedAdam(eng, AdamConfig(state_dtype=state_dtype,
                                            compute_dtype=compute_dtype),
                            tracker=t)
        opt.register("small", rng.standard_normal(100).astype(np.float32))
        opt.register("big", rng.standard_normal(1000).astype(np.float32))
        opt.begin_step()
        opt.step_subgroup("big", np.zeros(1000, np.float32))
        opt.step_subgroup("small", np.zeros(100, np.float32))
        comp = t.component("optimizer_stream")
        assert comp.peak_allocated == 2 * (3 * 1000 * 4
                                           + 1000 * scratch_per_elem)
        assert comp.n_allocs == 1           # the arena, once — not per call
        opt.close()
        assert t.component("optimizer_stream").live_allocated == 0
        t.assert_quiescent()
        eng.close()


def test_failed_issue_releases_staging_buffer(tmp_store_root, rng):
    eng = DirectNVMeEngine(tmp_store_root, n_devices=1,
                           device_capacity=1 << 24)
    opt = OffloadedAdam(eng, AdamConfig(), tracker=MemoryTracker())
    opt.register("w", rng.standard_normal(64).astype(np.float32))
    real_read = eng.read

    def flaky_read(key, out):
        if key.endswith(".v"):
            raise IOError("boom")
        return real_read(key, out)

    eng.read = flaky_read
    opt.begin_step()
    with pytest.raises(IOError, match="boom"):
        opt.issue_subgroup("w")
    assert opt.staging_idle()
    opt.close()
    eng.close()


# -- the tiled update --------------------------------------------------------

def _whole_array_adam(master, grad, m, v, step, cfg):
    """The untiled formula, one whole-array numpy pass after another: the
    tiled update must match it bit for bit."""
    b1, b2 = cfg.beta1, cfg.beta2
    m *= b1
    m += (1.0 - b1) * grad
    v *= b2
    v += (1.0 - b2) * np.square(grad)
    bias1 = 1.0 - b1 ** step
    bias2 = 1.0 - b2 ** step
    denom = np.sqrt(v / bias2) + cfg.eps
    update = (m / bias1) / denom
    if cfg.weight_decay:
        update += cfg.weight_decay * master
    master -= cfg.lr * update


def _adam_case(n, seed):
    rng = np.random.default_rng(seed)
    master = rng.standard_normal(n, dtype=np.float32)
    m = rng.standard_normal(n, dtype=np.float32) * np.float32(0.01)
    v = np.square(rng.standard_normal(n, dtype=np.float32)) * np.float32(1e-4)
    grad = rng.standard_normal(n, dtype=np.float32) * np.float32(1024.0)
    return master, grad, m, v


def _tile_pool(workers):
    return TilePool(workers, TILE, MemoryTracker(), "tiles")


@pytest.mark.parametrize("workers", [None, 1, 3],
                         ids=["no-pool", "1-worker", "3-workers"])
@pytest.mark.parametrize("grad_scale", [None, 1 / 1024],
                         ids=["unscaled", "scale-1/1024"])
@pytest.mark.parametrize("step", [1, 2, 1000])
@pytest.mark.parametrize("weight_decay", [0.0, 0.01])
@pytest.mark.parametrize("n", [1, TILE - 1, TILE, TILE + 1, 3 * TILE + 5])
def test_tiled_update_is_bit_identical(n, weight_decay, step, grad_scale,
                                       workers):
    cfg = AdamConfig(lr=1e-3, weight_decay=weight_decay)
    master, grad, m, v = _adam_case(n, seed=n + step)
    want = [a.copy() for a in (master, m, v)]
    unit_grad = grad if grad_scale is None else grad * np.float32(grad_scale)
    _whole_array_adam(want[0], unit_grad, want[1], want[2], step, cfg)
    pool = _tile_pool(workers) if workers else None
    try:
        adam_update(master, grad, m, v, step, cfg, grad_scale=grad_scale,
                    pool=pool)
    finally:
        if pool is not None:
            pool.close()
    for got, ref in zip((master, m, v), want, strict=True):
        assert got.dtype == np.float32
        assert np.array_equal(got, ref)


def test_tiled_update_many_workers_under_fast_switching():
    """More workers than cores, switching threads every microsecond: each
    worker keeps to its own tiles and scratch."""
    n = 17 * TILE + 3
    cfg = AdamConfig(lr=1e-2, weight_decay=0.01)
    master, grad, m, v = _adam_case(n, seed=7)
    want = [a.copy() for a in (master, m, v)]
    _whole_array_adam(want[0], grad * np.float32(0.5), want[1], want[2], 3,
                      cfg)
    pool = _tile_pool(2 * os.cpu_count())
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        adam_update(master, grad, m, v, 3, cfg, grad_scale=0.5, pool=pool)
    finally:
        sys.setswitchinterval(interval)
        pool.close()
    for got, ref in zip((master, m, v), want, strict=True):
        assert np.array_equal(got, ref)


def test_tiled_update_allocates_no_full_size_temporaries():
    """A 4 M-entry update over a warm pool allocates less than one tile;
    the whole-array formula allocates several times the tensor."""
    n = 4 << 20
    cfg = AdamConfig(weight_decay=0.01)
    master, grad, m, v = _adam_case(n, seed=3)
    pool = _tile_pool(2)
    try:
        adam_update(master, grad, m, v, 1, cfg, grad_scale=0.5, pool=pool)
        tracemalloc.start()
        try:
            adam_update(master, grad, m, v, 2, cfg, grad_scale=0.5,
                        pool=pool)
            tiled_peak = tracemalloc.get_traced_memory()[1]
            tracemalloc.reset_peak()
            _whole_array_adam(master, grad * np.float32(0.5), m, v, 3, cfg)
            whole_peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
    finally:
        pool.close()
    assert tiled_peak < TILE * 4, tiled_peak
    assert whole_peak > 2 * n * 4, whole_peak


@pytest.mark.parametrize("cpus,workers", [(1, 1), (2, 1), (8, 2), (13, 4),
                                          (64, 4)])
def test_tile_workers_follow_the_cpus_the_process_may_use(monkeypatch, cpus,
                                                          workers):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(cpus)))
    assert optimizer.tile_workers() == workers


def _tile_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("offload-adam-tile")]


def test_tile_pool_lifecycle(tmp_store_root, rng, monkeypatch):
    """The optimizer's tile pool: made at the first update, its scratch
    tracker-charged once; close() stops its threads and frees the charge;
    a second close() is a no-op; an update after close() raises."""
    monkeypatch.setattr(optimizer, "tile_workers", lambda: 3)
    eng = DirectNVMeEngine(tmp_store_root, n_devices=1,
                           device_capacity=1 << 26)
    t = MemoryTracker()
    stats = OverlapStats()
    opt = OffloadedAdam(eng, AdamConfig(lr=1e-2), tracker=t, stats=stats)
    n = 2 * TILE + 3                       # 3 tiles: one per worker
    w0 = rng.standard_normal(n).astype(np.float32)
    opt.register("big", w0)
    opt.register("small", w0[:100])
    tiles = t.component("optimizer_stream_tiles")
    assert tiles.n_allocs == 0             # nothing before the first update
    opt.begin_step()
    opt.step_subgroup("small", np.ones(100, np.float32))
    opt.step_subgroup("big", np.ones(n, np.float32))
    assert tiles.live_allocated == 3 * 3 * TILE * 4
    assert tiles.n_allocs == 1
    snap = stats.snapshot()
    assert snap["adam_update_elems"] == n + 100
    assert snap["adam_parallel_elems"] == n      # the small one ran inline
    assert _tile_threads()
    opt.close()
    assert not _tile_threads()
    assert tiles.live_allocated == 0
    opt.close()                            # idempotent
    assert tiles.n_frees == 1
    staged = StagedSubgroup("big", 0, *(np.zeros(n, np.float32)
                                        for _ in range(3)), io_read=0)
    with pytest.raises(RuntimeError, match="closed"):
        opt.compute_subgroup(staged, np.ones(n, np.float32))
    t.assert_quiescent()
    eng.close()
