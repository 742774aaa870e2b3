"""OffloadSession: owns the offload lifecycle and executes StreamPlans.

One session = one open store/allocator/pool/swapper(/optimizer) stack over
an :class:`~repro.core.offload_engine.OffloadableModel`.  It is a context
manager — ``with OffloadSession(model, policy) as s: s.train_step(...)`` —
so the pinned arena, gradient flat buffer, and in-flight SSD reads are
always drained and returned, success or error.

Execution is plan-driven (:mod:`repro.core.stream_plan`) with **lookahead-N
pipelining**: when the executor reaches a :class:`FetchOp` it first issues
async SSD reads for the next ``lookahead`` units in the plan's fetch order,
then blocks only on the unit it needs *now*.  Block *i+1*'s read therefore
overlaps block *i*'s H2D + compute; depth is bounded by
``policy.inflight_blocks``, which is exactly what sizes the pool (paper
§IV-B), so the prefetch window can never oversubscribe pool slots — the
pool's own backpressure is the safety net.  ``lookahead=1`` degenerates to
the seed engine's synchronous per-unit fetches (the benchmark baseline).

On top of the read pipeline, ``policy.overlap`` turns on the remaining legs
of the paper's Fig. 6 full overlap (see :mod:`repro.core.overlap`):

* ``"h2d"``  — FetchOp splits into an issue half and a wait half.  An H2D
  worker stages completed SSD reads into double-buffered device slots
  (two units' worth per shape class) under the previous block's compute;
  the FetchOp then only waits for staged device weights.
* ``"full"`` — additionally, GradWriteOp enqueues its D2H + flat-buffer
  scatter on a bounded writer thread (backward D2H overlaps the next
  block's re-fetch/recompute), and the plan's OptimStepOps run on an
  optimizer worker: step *k*'s subgroup-streamed host Adam interleaves
  with step *k+1*'s forward prefetch window, with per-unit readiness
  futures gating the next step's fetch (weights must be post-update on
  the store) and grad write-back (the flat-buffer region must have been
  consumed).  The Adam stage is itself pipelined: a state-prefetch worker
  streams subgroup *k+1*'s (master, m, v) into a double-buffered staging
  arena while subgroup *k*'s arithmetic runs, and subgroup *k−1*'s
  write-backs drain behind them; readiness futures resolve at commit.
  SSDTrain (arXiv 2408.10013) pipelines across steps the same way.
  Fused-check policies also screen each unit's flat-buffer region for
  Inf/NaN as its write-back lands (on the writer thread), so the overflow
  barrier only ORs per-region verdicts instead of scanning the whole
  buffer.  Numerics are identical in every mode — the same float ops run
  in the same order, only the thread that pays the wait changes.

Activation checkpoints stream the same way (``policy.act_policy``): each
block's ActSaveOp runs its D2H + optional SSD write on the gradient-writer
thread under full overlap (the forward no longer pays a blocking
``np.asarray`` on the executor), and the backward's ActFetchOps split into
issue/wait halves riding the H2D staging worker under a dedicated
ACT-class device slot, so block *i−1*'s checkpoint streams back under
block *i*'s ``block_bwd``.  ``recompute``-tier blocks save nothing and
re-run the previous block's forward instead (see
:func:`repro.core.stream_plan.resolve_act_policy`).

The session runs four workloads through the same machinery:

* ``train_step``   — compile_train plan: forward/backward streaming +
                     overflow screen + loss scaler + subgroup-streamed
                     host Adam, all as plan ops,
* ``eval_loss``    — compile_eval plan (jitted head loss cached once),
* ``decode_logits``— compile_decode plan (weight-streamed serving,
                     uncached full-prefix pass; see
                     :mod:`repro.serve.offloaded`),
* ``prefill`` / ``decode_step`` — cached decode over a *paged* spill-able
                     KV cache (:mod:`repro.core.kv_cache`): sessions built
                     with ``decode=DecodeSpec(...)`` reserve page-granular
                     ``kv``-class pool slots in the census, stream each
                     layer's KV pages next to its weights, and bucket the
                     time axis so every jitted stage compiles once per
                     bucket.  Under ``overlap`` ≠ ``"sync"`` the KVReadOp
                     splits like FetchOp: the attended window's page
                     gather + H2D runs on the staging worker under the
                     previous block's compute, double-buffered by a ``kv``
                     device-slot class — no synchronous transfer is left
                     in the serving hot loop.

``mode="serve"`` opens a leaner session: no optimizer state is written to
the store and no gradient flat buffer is pinned — only the compute-precision
weights stream.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from .buffer_pool import KV_CLASS
from .kv_cache import DecodeSpec, SpillableKVCache
from .loss_scale import DynamicLossScaler
from .memory_tracker import MemoryTracker
from .optimizer import OffloadedAdam
from .overflow import check_region, flat_overflow_check
from .overlap import (ACT_CLASS, EXPERT_CLASS, SPANS, DeviceSlots,
                      OverlapStats, SerialWorker, done_future)
from .paged import ExpertPageCache
from .stream_plan import (ActFetchOp, ActSaveOp, ComputeOp, ExpertFetchOp,
                          ExpertReleaseOp, FetchOp, GradWriteOp, KVReadOp,
                          KVWriteOp, OptimStepOp, OverflowCheckOp, ReleaseOp,
                          StreamPlan, compile_decode, compile_decode_cached,
                          compile_decode_verify, compile_eval,
                          compile_prefill, compile_train,
                          resolve_act_policy)
from .swapper import ParameterSwapper

COMPUTE_SUFFIX = OffloadedAdam.COMPUTE


def jit_cache_size(fn) -> int:
    """Compiled-trace count of one ``jax.jit`` callable.

    jax exposes this only through the private ``_cache_size`` probe on the
    jitted wrapper — stable across the versions this repo pins, but not
    public API.  Guarded here (the single place the repo touches it) so a
    jax upgrade that removes the probe fails with a pointed message at the
    probe site instead of an ``AttributeError`` deep inside a benchmark.
    """
    probe = getattr(fn, "_cache_size", None)
    if not callable(probe):
        raise RuntimeError(
            "this jax build exposes no jit trace-count probe (the private "
            "_cache_size method); update repro.core.session.jit_cache_size "
            "for its replacement")
    return int(probe())


def verify_bucket(n: int) -> int:
    """Speculative-verify window K bucketed to the next power of two.

    The verify plan's jitted stages are shape-polymorphic over the window
    width K, so K is time-bucketed like every other decode shape: padding
    a draft of ``n`` real tokens to the covering power of two keeps the
    warm trace set bounded by ``{1, 2, 4, ...} × extent buckets`` no
    matter how ragged the drafts run.  Padding token K/V is appended and
    then rolled back with the rejected tail (the accept prefix can never
    reach into the padding — a draft's real length bounds it)."""
    if n < 1:
        raise ValueError(f"verify window must be >= 1 token, got {n}")
    return 1 << (n - 1).bit_length()


class _ActCkpt:
    """One block's activation checkpoint, tracked through its tiers.

    ``tier`` walks ``device`` (just saved: ``value`` is the device array)
    → ``host`` (ActSaveOp D2H'd it: ``value`` is a host ndarray, ``handle``
    its tracker allocation) → ``ssd`` (the store holds the bytes; only
    ``shape``/``np_dtype`` remain) → ``ready`` (ActFetchOp staged it back:
    ``value`` is a device array again, ``slot`` set if it holds an
    ACT_CLASS device slot).  ``fut`` is the in-flight ActSaveOp future
    while the gradient-writer thread runs the offload; the executor only
    reads the tier fields after ``fut`` resolves (the Future is the
    happens-before edge), or after an inline save on its own thread."""

    __slots__ = ("unit", "tier", "value", "handle", "shape", "np_dtype",
                 "dtype", "fut", "slot")

    def __init__(self, unit, value):
        self.unit = unit
        self.tier = "device"
        self.value = value
        self.handle = None      # tracker handle while a host copy is live
        self.shape = None       # ssd tier: host array shape
        self.np_dtype = None    # ssd tier: host array dtype
        self.dtype = value.dtype
        self.fut = None         # pending ActSaveOp (writer-thread) future
        self.slot = False       # value holds an ACT_CLASS device slot


class _ExecState:
    """Per-plan-run bindings and carried activations/cotangents."""

    __slots__ = ("tokens", "labels", "scale", "grad_scale", "h", "dh",
                 "loss", "logits", "live", "live_slots", "h2d", "grads",
                 "checkpoints", "overflowed", "apply", "optim_begun",
                 "kv", "kv_live", "kv_append", "kv_time", "cache_len",
                 "last_pos", "kv_stage", "kv_slots", "kv_write_slots",
                 "stage_seq", "act_order", "act_next", "act_stage",
                 "act_reads", "act_slots_out", "expert_route",
                 "expert_stage", "expert_live", "expert_slots",
                 "expert_slots_out")

    def __init__(self, tokens=None, labels=None, scale=1.0):
        self.tokens = None if tokens is None else jnp.asarray(tokens)
        self.labels = None if labels is None else jnp.asarray(labels)
        self.scale = jnp.asarray(scale, dtype=jnp.float32)
        self.grad_scale = float(scale)   # host copy for the optimizer ops
        self.h = self.dh = self.loss = self.logits = None
        self.live: dict[str, dict] = {}     # unit -> device params
        self.live_slots: dict[str, tuple] = {}  # unit -> device-slot tokens
        self.h2d: dict[str, deque] = {}     # unit -> staged-fetch futures
        self.grads: dict[str, dict] = {}    # unit -> device grads
        self.checkpoints: dict[str, tuple] = {}  # unit -> saved block input
        self.overflowed: bool | None = None  # set by OverflowCheckOp
        self.apply: bool | None = None       # set by OverflowCheckOp
        self.optim_begun = False             # begin_step() sequenced once
        # cached-decode bindings (prefill / decode_cached plans only)
        self.kv: SpillableKVCache | None = None
        self.kv_live: dict[str, tuple] = {}    # unit -> device (k, v) window
        self.kv_append: dict[str, tuple] = {}  # unit -> device (k, v) to land
        self.kv_stage: dict[str, Future] = {}  # unit -> staged-KV future
        self.kv_slots: dict[str, tuple] = {}   # unit -> kv device-slot tokens
        self.kv_time = 0          # device-cache bucket extent this run
        self.cache_len = None     # traced: tokens already cached (scalar on
        #                           the joint path, (B,) per-slot vector on
        #                           the continuous-batching path)
        self.last_pos = None      # traced: last prompt index (prefill head;
        #                           scalar or (B,) like cache_len)
        self.kv_write_slots = None  # prefill-scatter target slots (runtime
        #                             state, NOT plan state: plans stay
        #                             static across join/retire churn)
        # (kind, unit) per staging-worker submission, in FIFO order —
        # "w" weight stages, "kv" window stages, and "act" checkpoint
        # stages interleave on ONE worker, so the abort path must drain
        # them in this exact order
        self.stage_seq: list[tuple[str, str]] = []
        # activation-checkpoint streaming (train plans with host/ssd tiers)
        self.act_order: list[str] = []   # plan's ActFetchOp units, in order
        self.act_next = 0                # first act fetch not yet issued
        self.act_stage: dict[str, Future] = {}  # unit -> staged-ckpt future
        self.act_reads: dict[str, tuple] = {}   # unit -> (fut, buf, handle)
        #                                         sync-mode SSD act reads
        self.act_slots_out = 0   # ACT_CLASS submissions not yet consumed —
        #                          capped at the slot depth so the staging
        #                          worker's acquire can never block
        # expert paging (paged-MoE plans only): the routing indices persist
        # for the WHOLE plan run — the backward's ExpertFetchOp reuses the
        # forward's routing decision, so its prestage is an exact hit
        self.expert_route: dict[str, np.ndarray] = {}  # unit -> host (T,k)
        self.expert_stage: dict[str, deque] = {}  # unit -> staged-stack futs
        self.expert_live: dict[str, tuple] = {}   # unit -> device stacks
        self.expert_slots: dict[str, tuple] = {}  # unit -> EXPERT_CLASS tokens
        self.expert_slots_out = 0  # EXPERT_CLASS submissions whose slot has
        #                            not been returned yet — capped at the
        #                            slot depth so the staging worker's
        #                            acquire can never block the pipeline


class OffloadSession:
    """Executes StreamPlans over one open offload stack (context manager)."""

    def __init__(self, model, policy, *, tracker: MemoryTracker | None = None,
                 mode: str = "train",
                 decode: DecodeSpec | None = None) -> None:
        if mode not in ("train", "serve"):
            raise ValueError(f"mode must be 'train' or 'serve', got {mode!r}")
        self.model = model
        self.policy = policy
        self.mode = mode
        self.tracker = tracker or MemoryTracker()
        self.store = policy.store_factory()
        # The store is open from here on: if any later construction step
        # fails (disk-full while seeding optimizer state, MemoryError on
        # the flat buffer), __enter__ never runs and no caller can close()
        # — release whatever was acquired before re-raising.
        self._closed = False
        try:
            self._construct(model, policy, mode, decode)
        except BaseException:
            self.close()
            raise

    # pre-share: runs inside __init__, before any worker thread exists
    def _construct(self, model, policy, mode: str,  # analyze: pre-share
                   decode: DecodeSpec | None) -> None:
        self.allocator = policy.allocator_cls(
            tracker=self.tracker, component="pinned", backing="numpy")
        # Expert paging (paged MoE): resolved before the census because the
        # paged units' per-expert tensors leave the per-block streaming
        # counts and become standalone expert-page slots instead.
        self._expert_mode = policy.expert_paging
        self._expert_meta = getattr(model, "expert_meta", None) or {}
        if self._expert_mode != "off" and not self._expert_meta:
            raise ValueError(
                f"expert_paging={self._expert_mode!r} but the model has no "
                f"paged-MoE units; build it with make_offloadable_lm(..., "
                f"expert_paging=...) so expert tensors split into pages")
        if self._expert_mode == "off" and self._expert_meta:
            raise ValueError(
                "model was built with per-expert pages (expert_meta set) "
                "but the policy streams experts densely "
                "(expert_paging='off'); the dense block apply would miss "
                "the stacked expert weights — align the two knobs")
        self._paged_params: dict[str, frozenset] = (
            {u: frozenset(model.expert_params(u)) for u in self._expert_meta}
            if self._expert_mode != "off" else {})
        expert_pages: dict[tuple[str, str], tuple] = {}
        if self._expert_mode != "off":
            for uname in self._expert_meta:
                unit = next(u for u in model.units if u.name == uname)
                for pname in self._paged_params[uname]:
                    expert_pages[(uname, pname)] = unit.params[pname].shape
            budget = policy.expert_page_slots or len(expert_pages)
        self._expert_cache: ExpertPageCache | None = None
        self._expert_prior: dict[str, np.ndarray] = {}
        census = model.census(
            policy.inflight_blocks,
            bytes_per_elem=policy.adam.compute_np_dtype.itemsize,
            expert_page_slots=(budget if self._expert_mode != "off"
                               else None))
        # Cached decode: the KV cache draws slots from the same pool arena
        # the weights stream through, so its residency budget is part of
        # the census (paper §IV-B sizing, extended to decode state).
        self.decode_spec = decode
        self._kv_units = tuple(u.name for u in model.units[1:-1])
        self._kv_page_shape = None
        self._kv_resident = 0
        self._kv_cache: SpillableKVCache | None = None
        if decode is not None:
            if model.block_step is None or model.kv_shape is None:
                raise ValueError(
                    "model has no cached-decode applies (block_step/"
                    "kv_shape); decode=DecodeSpec(...) needs an attention-"
                    "mixer family (see model_adapter.make_offloadable_lm)")
            if not self._kv_units:
                raise ValueError("model has no block units to cache KV for")
            # Page-granular census: one kv-class slot per page of
            # ``spec.page_size`` tokens; the budget is the paged cache's
            # host-residency limit (paper §IV-B sizing, extended to decode
            # state at block-table granularity).  Pages are per batch slot
            # (single-row) so continuous batching can reclaim one request's
            # pages without touching its neighbours'; the spec's
            # per-request budget scales by batch to keep the same bytes.
            self._kv_resident = (decode.page_budget(len(self._kv_units))
                                 * decode.batch)
            self._kv_page_shape = tuple(
                model.kv_shape(1, decode.page_size))
            kv_nbytes = int(policy.adam.compute_np_dtype.itemsize * np.prod(
                self._kv_page_shape, dtype=np.int64))
            census = census.with_kv(kv_nbytes, self._kv_resident)
        self.pool = policy.pool_cls(census, self.allocator)
        # Paged expert tensors are NOT swapper-streamed: they go through
        # the expert page cache below, one page per (unit, param).
        self.swapper = ParameterSwapper(self.store, self.pool, class_of={
            f"{unit.name}/{key}{COMPUTE_SUFFIX}": model.class_of(key)
            for unit in model.units for key in unit.params
            if key not in self._paged_params.get(unit.name, frozenset())})
        if self._expert_mode != "off":
            # lazy reads: pages are born spilled against the {key}.compute
            # store copies the registration loop below writes, so creating
            # the cache before them is safe — nothing reads until a fetch
            self._expert_cache = ExpertPageCache(
                expert_pages, policy.adam.compute_np_dtype, self.pool,
                self.store, resident_limit=budget,
                store_suffix=COMPUTE_SUFFIX)
        self.scaler = DynamicLossScaler()
        if policy.adam.compute_dtype != "float16":
            self.scaler.scale = 1.0  # only fp16 needs scaling; check stays on
        self.compute_dtype = {"bfloat16": jnp.bfloat16,
                              "float16": jnp.float16,
                              "float32": jnp.float32}[
            policy.adam.compute_dtype]
        lookahead = policy.lookahead or policy.inflight_blocks
        self.lookahead = max(1, min(lookahead, policy.inflight_blocks))

        # Per-block activation-checkpoint tiers (train mode): resolved once
        # so a bad act_policy fails here, not at the first train_step.
        # offload_checkpoints=False keeps every checkpoint on device.
        block_names = [u.name for u in model.units[1:-1]]
        self._act_tiers: tuple[str, ...] = ()
        if mode == "train" and block_names:
            self._act_tiers = resolve_act_policy(
                block_names,
                policy.act_policy if policy.offload_checkpoints
                else "device")

        # Full-overlap machinery (policy.overlap; see module docstring and
        # repro.core.overlap).  Created before the store writes below so a
        # mid-construction failure still finds them on the close() path.
        self.overlap = policy.overlap
        self._ostats = OverlapStats()
        self._optim_lock = threading.Lock()
        self._optim_futures: dict[str, Future] = {}  # guarded-by: _optim_lock
        self._optim_io_completed = 0                 # guarded-by: _optim_lock
        self._device_slots: DeviceSlots | None = None
        self._h2d: SerialWorker | None = None
        self._grad_writer: SerialWorker | None = None
        self._optim_worker: SerialWorker | None = None
        self._optim_prefetch: SerialWorker | None = None
        # Adam-stage subgroup pipeline bookkeeping (see _exec_optim):
        # _adam_work is appended by the executor thread under _adam_lock
        # and read by the optimizer worker; the issue counter and in-flight
        # deque are touched by the optimizer worker only (tasks are FIFO
        # on its single thread).
        self._adam_lock = threading.Lock()
        # (unit, param key) pairs:
        self._adam_work: list[tuple[str, str]] = []   # guarded-by: _adam_lock
        self._adam_issued = 0
        self._adam_inflight: deque = deque()          # (index, staged fut)
        self._adam_poison: BaseException | None = None
        # per-subgroup overflow screen: verdicts land per unit (writer
        # thread under full overlap) and are OR-ed at the barrier.
        self._screen_lock = threading.Lock()
        self._region_verdicts: dict[str, bool] = {}  # guarded-by: _screen_lock
        self._screen_regions = policy.fused_overflow and mode == "train"
        if policy.overlap in ("h2d", "full"):
            per_unit: dict[str, int] = {}
            for unit in model.units:
                paged = self._paged_params.get(unit.name, frozenset())
                counts: dict[str, int] = {}
                for key in unit.params:
                    if key in paged:
                        continue   # staged as (E, ...) stacks, not per-key
                    cls = model.class_of(key)
                    counts[cls] = counts.get(cls, 0) + 1
                for cls, c in counts.items():
                    per_unit[cls] = max(per_unit.get(cls, 0), c)
            # Two units' worth of device buffers per shape class: one in
            # use by compute, one being staged — the Fig. 6 double buffer.
            depths = {cls: 2 * c for cls, c in per_unit.items()}
            if decode is not None:
                # staged KV windows double-buffer too: one block's (K, V)
                # in use by compute, one being gathered + H2D'd
                depths[KV_CLASS] = 2
            if any(t in ("host", "ssd") for t in self._act_tiers):
                # staged activation checkpoints double-buffer the same way:
                # one consumed by the current block_bwd, one being staged
                depths[ACT_CLASS] = 2
            if self._expert_mode != "off":
                # staged expert (E, ...) stacks double-buffer: one triple
                # feeding the current block_moe, one being staged ahead
                depths[EXPERT_CLASS] = 2
            self._device_slots = DeviceSlots(depths)
            # latch=False: every staging future is awaited by the executor
            # (FetchOp wait half, or the abort path), which delivers any
            # failure — a close()-time re-raise would double-report it.
            self._h2d = SerialWorker("offload-h2d", latch=False)
        if policy.overlap == "full" and mode == "train":
            self._grad_writer = SerialWorker("offload-gradwrite", maxsize=4)
            self._optim_worker = SerialWorker("offload-optim")
            # The Adam stage's own I/O thread: issues (state reads into the
            # double-buffered staging arena) and commits (write-backs)
            # both run here, submitted in an order that keeps the arena's
            # blocking acquire always satisfiable (see
            # _optim_unit_pipelined).  latch=False: every future is
            # awaited by the optimizer worker, which delivers failures
            # through the unit readiness future — a close()-time re-raise
            # would double-report.
            self._optim_prefetch = SerialWorker("offload-optim-prefetch",
                                                latch=False)

        # Register every parameter.  Train mode seeds master weights + Adam
        # moments on the store; serve mode writes only compute weights.
        self.optimizer = (OffloadedAdam(self.store, policy.adam,
                                        tracker=self.tracker,
                                        stats=self._ostats)
                          if mode == "train" else None)
        if self.optimizer is not None:
            # stale-read guard on the Adam commit's compute-weight write:
            # the per-unit readiness gates guarantee no prefetched read of
            # a unit's weights is in flight while its commit writes them —
            # assert it at the write site (see swapper.assert_not_in_flight)
            self.optimizer.write_guard = self._guard_compute_write
        cd = policy.adam.compute_np_dtype
        self._unit_param_meta: list[tuple] = []
        self._units: dict[str, tuple] = {}
        total_params = 0
        for unit in model.units:
            meta = {}
            for key, value in unit.params.items():
                if self.optimizer is not None:
                    self.optimizer.register(f"{unit.name}/{key}", value)
                else:
                    self.store.write(f"{unit.name}/{key}{COMPUTE_SUFFIX}",
                                     value.astype(cd))
                meta[key] = (value.shape, value.size)
                total_params += value.size
            self._unit_param_meta.append((unit, meta))
            self._units[unit.name] = (unit, meta)
        self.total_params = total_params

        # Gradient flat buffer: fp32, whole partition, lives for the session
        # (train mode only — serving never materializes gradients).
        if mode == "train":
            self._flat_buf = self.allocator.alloc(total_params * 4,
                                                  tag="gradient_flat_buffer")
            self.flat = self._flat_buf.view(np.float32, (total_params,))
            self._flat_offsets: dict[str, tuple[int, int, tuple]] = {}
            self._unit_flat_region: dict[str, tuple[int, int]] = {}
            off = 0
            for unit, meta in self._unit_param_meta:
                lo = off
                for key, (shape, size) in meta.items():
                    self._flat_offsets[f"{unit.name}/{key}"] = (
                        off, size, shape)
                    off += size
                # a unit's parameters are contiguous in the flat buffer:
                # [lo, off) is the region its per-subgroup screen covers
                self._unit_flat_region[unit.name] = (lo, off)
        else:
            self._flat_buf = None
            self.flat = None

        # jitted per-stage functions (shared across blocks of equal shapes);
        # the eval head loss is jitted ONCE here, not per eval_loss call.
        self._jit_embed = jax.jit(model.embed_apply)
        self._jit_block = jax.jit(model.block_apply)
        self._jit_head = jax.jit(self._head_loss_and_grads)
        self._jit_head_loss = jax.jit(model.head_loss)
        self._jit_block_bwd = jax.jit(self._block_bwd)
        self._jit_embed_bwd = jax.jit(
            lambda p, t, dy: jax.vjp(model.embed_apply, p, t)[1](dy)[0])
        self._jit_head_logits = (jax.jit(model.head_logits)
                                 if getattr(model, "head_logits", None)
                                 else None)
        self._jit_block_prefill = (jax.jit(model.block_prefill)
                                   if getattr(model, "block_prefill", None)
                                   else None)
        # chunk is static: it selects the reduction grid that makes a
        # row's attention bitwise invariant to the shared device extent
        # (without it, a co-lane crossing a bucket boundary regroups the
        # softmax/PV reductions and can flip a near-tie greedy argmax)
        self._jit_block_step = (jax.jit(model.block_step,
                                        static_argnames=("chunk",))
                                if getattr(model, "block_step", None)
                                else None)
        self._jit_block_verify = (jax.jit(model.block_verify,
                                          static_argnames=("chunk",))
                                  if getattr(model, "block_verify", None)
                                  else None)
        # expert-paged MoE stages (route half / expert half / backward,
        # plus the cached-decode route variants)
        paged_moe = self._expert_mode != "off"
        self._jit_block_route = (jax.jit(model.block_route)
                                 if paged_moe else None)
        self._jit_block_moe = (jax.jit(model.block_moe)
                               if paged_moe else None)
        self._jit_block_moe_bwd = (jax.jit(model.block_moe_bwd)
                                   if paged_moe else None)
        self._jit_prefill_route = (
            jax.jit(model.block_prefill_route) if paged_moe
            and getattr(model, "block_prefill_route", None) else None)
        self._jit_step_route = (
            jax.jit(model.block_step_route, static_argnames=("chunk",))
            if paged_moe and getattr(model, "block_step_route", None)
            else None)
        self._jit_verify_route = (
            jax.jit(model.block_verify_route, static_argnames=("chunk",))
            if paged_moe and getattr(model, "block_verify_route", None)
            else None)
        self._jit_head_last = None
        if self._jit_head_logits is not None and \
                self._jit_block_prefill is not None:
            def _head_last(params, h, pos):
                # pos is traced: slicing the last valid prompt position out
                # of the padded bucket costs no retrace per prompt length.
                # A scalar pos selects one position for the whole batch
                # (joint prefill); a (B,) pos selects per row (serving
                # prefill, where joiners' prompt lengths differ).
                h_last = (
                    jax.lax.dynamic_slice_in_dim(h, pos, 1, axis=1)
                    if pos.ndim == 0
                    else jnp.take_along_axis(h, pos[:, None, None], axis=1))
                return model.head_logits(params, h_last)
            self._jit_head_last = jax.jit(_head_last)

        self._plans: dict[str, StreamPlan] = {}
        self.metrics: dict = {}
        # devices holding the last plan run's jitted output (loss or
        # logits): how a caller confirms the blocks ran on the accelerator
        self.output_devices: frozenset = frozenset()

    # -- lifecycle ----------------------------------------------------------

    def __enter__(self) -> "OffloadSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def close(self) -> None:  # thread: executor
        """Drain in-flight reads and pipeline workers, return the arena +
        flat buffer, close the store.  Idempotent; runs on the error path
        via ``__exit__`` and on partially-constructed sessions (attributes
        may not exist yet).

        Worker order matters: the H2D worker goes first (its queued jobs
        own swapper tickets), then the gradient writer (its tasks may gate
        on optimizer futures, so the optimizer worker must still be alive),
        then the optimizer worker (whose unit tasks wait on state-prefetch
        futures, so that worker must still be alive), then the
        state-prefetch worker, and only then the swapper drain that sweeps
        any ticket nobody claimed.  The optimizer's staging arena is freed
        after every worker that touches it has stopped."""
        if getattr(self, "_closed", True):
            return
        self._closed = True
        steps = []
        if getattr(self, "_kv_cache", None) is not None:
            steps.append(self._kv_cache.close)
        if getattr(self, "_expert_cache", None) is not None:
            steps.append(self._expert_cache.close)
        for worker_attr in ("_h2d", "_grad_writer", "_optim_worker",
                            "_optim_prefetch"):
            worker = getattr(self, worker_attr, None)
            if worker is not None:
                steps.append(worker.close)
        if getattr(self, "optimizer", None) is not None:
            steps.append(self.optimizer.close)
        if getattr(self, "swapper", None) is not None:
            steps.append(self.swapper.drain)
        if getattr(self, "pool", None) is not None:
            steps.append(self.pool.close)
        if getattr(self, "_flat_buf", None) is not None:
            steps.append(self._flat_buf.free)
        steps.append(self.store.close)
        # every step must run even if an earlier one raises (e.g. an
        # interrupt re-raised out of drain) — otherwise the arena/flat
        # buffer/store leak with no way to retry; first failure re-raises.
        failure = None
        for step in steps:
            try:
                step()
            except BaseException as e:
                if failure is None:
                    failure = e
        if failure is not None:
            raise failure

    def synchronize(self) -> None:  # thread: executor
        """Drain the cross-step pipeline: wait out queued gradient
        write-backs and the in-flight optimizer stage, re-raising their
        failures.  The executor's per-unit readiness gates make this
        unnecessary for correctness between train steps; call it to close
        a timing window, read complete ``optimizer_io_bytes``, or compare
        state across overlap modes."""
        if self._grad_writer is not None:
            self._grad_writer.drain()
        if self._optim_worker is not None:
            self._optim_worker.drain()
        if self._optim_prefetch is not None:
            # empty by construction once the optimizer worker drained (unit
            # tasks wait out their own commits); drained for completeness
            self._optim_prefetch.drain()

    # -- plans --------------------------------------------------------------

    def plan(self, name: str) -> StreamPlan:
        """The session's compiled plan for ``name``
        (train/eval/decode/prefill/decode_cached/decode_verify)."""
        if name not in self._plans:
            if name == "train":
                # the resolved per-block tiers ARE the policy (a dict/
                # sequence spec was normalized at construction)
                self._plans[name] = compile_train(
                    self.model, act_policy=self._act_tiers or None)
            else:
                compiler = {"eval": compile_eval,
                            "decode": compile_decode,
                            "prefill": compile_prefill,
                            "decode_cached": compile_decode_cached,
                            "decode_verify": compile_decode_verify}[name]
                self._plans[name] = compiler(self.model)
        return self._plans[name]

    # -- jitted helpers ------------------------------------------------------

    def _head_loss_and_grads(self, params, h, labels, scale):
        def scaled(params, h):
            return self.model.head_loss(params, h, labels) * scale
        sloss, vjp = jax.vjp(scaled, params, h)
        dparams, dh = vjp(jnp.ones((), sloss.dtype))
        return sloss / scale, dparams, dh

    def _block_bwd(self, params, x, dy):
        _, vjp = jax.vjp(self.model.block_apply, params, x)
        dparams, dx = vjp(dy)
        return dparams, dx

    # -- weight streaming ----------------------------------------------------

    def _param_keys(self, unit_name: str):
        unit, meta = self._units[unit_name]
        cd = self.policy.adam.compute_np_dtype
        paged = self._paged_params.get(unit_name, frozenset())
        for key, (shape, _size) in meta.items():
            if key in paged:
                continue   # streamed as expert pages, not with the unit
            yield key, f"{unit.name}/{key}{COMPUTE_SUFFIX}", cd, shape

    def _prefetch_unit(self, unit_name: str) -> None:
        for _key, skey, cd, shape in self._param_keys(unit_name):
            self.swapper.prefetch(skey, cd, shape)

    def _unit_in_flight(self, unit_name: str) -> bool:
        return any(self.swapper.in_flight(skey)
                   for _key, skey, _cd, _shape in
                   self._param_keys(unit_name))

    def _h2d_copy(self, host_view):
        """H2D transfer.  ``copy=True`` alone is NOT enough: jax dispatches
        the copy asynchronously, so without the barrier the pool slot can be
        released, reacquired, and overwritten by the next unit's SSD pread
        before the bytes were actually read — the caller then computes with
        another tensor's weights.  ``block_until_ready`` pins the slot's
        contents until the copy has landed; it blocks the *staging* worker
        (or, in sync mode, the compute thread that was going to wait
        anyway), never an overlapped compute."""
        with self._ostats.timed("h2d_copy_seconds"):
            arr = jnp.array(host_view, copy=True)
            arr.block_until_ready()
        return arr

    def _submit_h2d(self, unit_name: str, state: _ExecState) -> None:
        """Issue half of the split FetchOp: queue SSD-read-wait + H2D onto
        the staging worker; the wait half pops the future in fetch order."""
        fut = self._h2d.submit(
            functools.partial(self._h2d_stage_unit, unit_name))
        state.h2d.setdefault(unit_name, deque()).append(fut)
        state.stage_seq.append(("w", unit_name))

    def _submit_kv_stage(self, unit_name: str, state: _ExecState) -> None:
        """Issue half of the split KVReadOp: queue page-refill waits +
        window gather + H2D onto the staging worker, behind the same
        unit's weight staging; KVReadOp pops the future (wait half)."""
        fut = self._h2d.submit(functools.partial(
            self._stage_kv_unit, state.kv, unit_name, state.kv_time))
        state.kv_stage[unit_name] = fut
        state.stage_seq.append(("kv", unit_name))

    def _stage_kv_unit(self, kv: SpillableKVCache, unit_name: str,  # thread: h2d-worker
                       extent: int) -> tuple:
        """H2D-worker body for one unit's KV window: gather the attended
        window's pages (waiting out / refilling spilled ones) and stage
        device copies under a counted ``kv`` device slot.  The acquire
        blocks the *worker*, never the compute thread, until ReleaseOp
        returns the older window's slot — the same Fig. 6 rotation as the
        weight double buffer."""
        k_host, v_host = kv.gather_window(unit_name, extent)
        self._device_slots.acquire(KV_CLASS)
        try:
            return self._h2d_copy(k_host), self._h2d_copy(v_host)
        except BaseException:
            self._device_slots.release_all([KV_CLASS])
            raise

    def _h2d_stage_unit(self, unit_name: str) -> tuple[dict, list]:  # thread: h2d-worker
        """H2D-worker body: claim the unit's tickets, wait each read,
        stage into device slots, release the pool slots.  Returns
        ``(device_params, slot_tokens)``; on any failure every claimed
        ticket and acquired slot token is returned before re-raising."""
        claims = []
        device_params: dict = {}
        tokens: list[str] = []
        try:
            # Claiming inside the try: a claim pops the ticket out of the
            # swapper's in-flight map (drain() can no longer see it), so a
            # mid-loop failure must release the earlier claims here.
            for key, skey, cd, shape in self._param_keys(unit_name):
                ticket, hit, fallback = self.swapper.claim(skey, cd, shape)
                claims.append([key, skey, ticket, hit, fallback, cd, shape])
            for entry in claims:
                key, skey, ticket, hit, fallback, cd, shape = entry
                t0 = time.perf_counter()
                host_view = ticket.wait()
                self.swapper.record_get(
                    hit=hit, fallback=fallback,
                    wait_seconds=time.perf_counter() - t0)
                self._device_slots.acquire(self.swapper.class_of[skey])
                tokens.append(self.swapper.class_of[skey])
                try:
                    device_params[key] = self._h2d_copy(host_view)
                finally:
                    ticket.release()
                    entry[2] = None       # consumed: skip in cleanup
        except BaseException:
            for entry in claims:
                ticket = entry[2]
                if ticket is None:
                    continue
                try:
                    ticket.wait()
                except BaseException:
                    pass          # data is being discarded
                finally:
                    ticket.release()
            self._device_slots.release_all(tokens)
            raise
        return device_params, tokens

    def _fetch_unit(self, unit_name: str, state: _ExecState) -> dict:
        """Blocking half of the lifecycle: wait for staged device weights
        (overlap mode) or wait the reads + H2D inline (sync mode)."""
        pending = state.h2d.get(unit_name)
        if pending:
            fut = pending.popleft()
            if not pending:
                del state.h2d[unit_name]
            hit = fut.done()
            t0 = time.perf_counter()
            device_params, tokens = fut.result()
            self._ostats.h2d_wait_seconds += time.perf_counter() - t0
            self._ostats.h2d_gets += 1
            self._ostats.h2d_hits += int(hit)
            state.live_slots[unit_name] = tuple(tokens)
            return device_params
        device_params = {}
        for key, skey, cd, shape in self._param_keys(unit_name):
            ticket = self.swapper.get(skey, cd, shape)
            try:
                device_params[key] = self._h2d_copy(
                    ticket.buf.view(cd, shape))
            finally:
                ticket.release()                          # slot back to pool
        return device_params

    # -- cross-step optimizer readiness --------------------------------------

    def _guard_compute_write(self, key: str) -> None:  # thread: executor, optim-worker
        """Adam-commit hook: refreshing ``key``'s compute weights on the
        store while a prefetched read of them is in flight would race the
        pread (the readiness gates forbid it; this asserts it)."""
        self.swapper.assert_not_in_flight(key + COMPUTE_SUFFIX)

    def _optim_ready(self, unit_name: str) -> bool:  # thread: executor
        """True when the unit's previous-step Adam landed *successfully* —
        a done-with-exception future is NOT ready (the store still holds
        pre-update weights), so the window stalls on it until the head
        position's :meth:`_optim_wait` delivers the failure."""
        with self._optim_lock:
            fut = self._optim_futures.get(unit_name)
        return fut is None or (fut.done() and fut.exception() is None)

    def _optim_wait(self, unit_name: str) -> None:  # thread: executor
        """Block until the unit's previous-step Adam write-back landed
        (re-raising an optimizer-worker failure here, at the point the
        stale weights would otherwise have been read)."""
        with self._optim_lock:
            fut = self._optim_futures.get(unit_name)
        if fut is None:
            return
        t0 = time.perf_counter()
        try:
            fut.result()
        except BaseException as e:
            if self._optim_worker is not None:
                self._optim_worker.consume_error(e)   # delivered here
            raise
        self._ostats.optim_gate_seconds += time.perf_counter() - t0

    # -- activation-checkpoint streaming -------------------------------------
    #
    # Lifecycle (mirrors the weight stream's split issue/wait halves):
    #
    #   save    ComputeOp(save_input) binds the device array as an _ActCkpt;
    #           ActSaveOp runs _act_offload on the gradient-writer thread
    #           under full overlap (the D2H + SSD write hide under the next
    #           block's forward compute) and inline otherwise,
    #   fetch   _act_issue_ahead (called inside the FetchOp lookahead
    #           window and at each ActFetchOp) starts the SSD read + H2D
    #           staging for upcoming act fetches, bounded by the ACT_CLASS
    #           device-slot budget; ActFetchOp's _act_fetch only waits,
    #   consume block_bwd takes the device array and returns the slot.
    #
    # Deadlock-freedom of the staged path: the executor never submits an
    # act stage while act_slots_out >= the ACT_CLASS depth, so the staging
    # worker's ACT acquire is always immediately satisfiable — it can
    # never wedge the shared FIFO worker behind an unreleasable slot.

    def _act_key(self, unit: str, nbytes: int) -> str:
        # nbytes in the key: DirectNVMeEngine reuses an existing key's
        # extents and rejects size changes, so a seq-length change must
        # land under a fresh key (keys are overwritten per step, never
        # deleted — the store reuses their extents)
        return f"__act__/{unit}/{nbytes}"

    def _exec_act_save(self, op: ActSaveOp, state: _ExecState) -> None:  # thread: executor
        """ActSaveOp: offload the unit's just-saved checkpoint — on the
        gradient-writer thread (full overlap; idle during the forward
        pass) or inline."""
        rec = state.checkpoints[op.unit]
        if self._grad_writer is not None:
            rec.fut = self._grad_writer.submit(
                functools.partial(self._act_offload, rec, op.tier))
        else:
            t0 = time.perf_counter()
            self._act_offload(rec, op.tier)
            self._ostats.act_save_wait_seconds += time.perf_counter() - t0

    def _act_offload(self, rec: _ActCkpt, tier: str) -> None:  # thread: executor, writer
        """D2H the checkpoint and, for the ssd tier, write it onward to
        the store and free the host copy.  A failed SSD write degrades
        gracefully: the host copy stays live (tracked) and the checkpoint
        serves from the host tier — no data loss, no raised step."""
        host = np.asarray(rec.value)   # D2H
        handle = self.tracker.alloc("activation_checkpoints",
                                    host.nbytes, tag="block_input")
        try:
            if tier == "ssd":
                try:
                    self.store.write(self._act_key(rec.unit, host.nbytes),
                                     host)
                except Exception:
                    self._ostats.bump("act_write_failures")
                else:
                    self.tracker.free(handle)
                    rec.shape, rec.np_dtype = host.shape, host.dtype
                    rec.value, rec.handle = None, None
                    rec.tier = "ssd"
                    return
            rec.value, rec.handle = host, handle
            rec.tier = "host"
        except BaseException:
            # rec stays device-tier; the abort path discards it safely
            self.tracker.free(handle)
            raise

    def _act_issue_ahead(self, state: _ExecState) -> None:  # thread: executor
        """Issue half of upcoming ActFetchOps: start SSD reads + H2D
        staging for the next offloaded checkpoints, in plan order, so
        block *i−1*'s checkpoint streams back under block *i*'s
        ``block_bwd``.  Stops at a checkpoint whose save is still in
        flight (or failed — the failure surfaces at its ActFetchOp gate)
        and at the ACT slot / lookahead budget."""
        order = state.act_order
        while state.act_next < len(order):
            unit = order[state.act_next]
            rec = state.checkpoints.get(unit)
            if rec is None:
                break              # forward has not saved this one yet
            fut = rec.fut
            if fut is not None:
                if not fut.done():
                    break          # save still in flight on the writer
                if fut.exception() is not None:
                    break          # delivered at the ActFetchOp gate
            if rec.tier not in ("host", "ssd") or unit in state.act_stage \
                    or unit in state.act_reads:
                state.act_next += 1
                continue
            if self._h2d is not None:
                if state.act_slots_out >= 2:
                    break          # ACT_CLASS budget: acquire never blocks
                self._issue_act_stage(unit, rec, state)
            elif rec.tier == "ssd":
                if len(state.act_reads) >= self.lookahead:
                    break
                self._issue_act_read(unit, rec, state)
            # sync-mode host tier: nothing to issue — the H2D is the wait
            state.act_next += 1

    def _issue_act_stage(self, unit: str, rec: _ActCkpt,  # thread: executor
                         state: _ExecState) -> None:
        """Queue one checkpoint's H2D staging (and, for ssd, its async
        store read) on the staging worker, behind the backward pass's
        weight stages."""
        if rec.tier == "ssd":
            buf = np.empty(rec.shape, rec.np_dtype)
            handle = self.tracker.alloc("activation_checkpoints", buf.nbytes,
                                        tag="act_fetch_staging")
            try:
                read_fut = self.store.read_async(
                    self._act_key(unit, buf.nbytes), buf)
            except BaseException:
                self.tracker.free(handle)
                raise
            task = functools.partial(self._act_stage_ssd, read_fut, buf,
                                     handle)
        else:
            task = functools.partial(self._act_stage_host, rec)
        state.act_stage[unit] = self._h2d.submit(task)
        state.stage_seq.append(("act", unit))
        state.act_slots_out += 1

    def _act_stage_ssd(self, read_fut: Future, buf: np.ndarray,  # thread: h2d-worker
                       handle) -> object:
        """Staging-worker body: wait the SSD read, H2D under a counted ACT
        device slot, free the staging buffer.  On failure the slot is
        returned here; the read buffer's tracker handle is always freed
        (the bytes live on device or nowhere)."""
        self._device_slots.acquire(ACT_CLASS)
        try:
            try:
                read_fut.result()
                return self._h2d_copy(buf)
            finally:
                self.tracker.free(handle)
        except BaseException:
            self._device_slots.release_all([ACT_CLASS])
            raise

    def _act_stage_host(self, rec: _ActCkpt) -> object:  # thread: h2d-worker
        """Staging-worker body for a host-tier checkpoint: H2D under a
        counted ACT device slot (the host copy's tracker handle is freed
        by the executor when the staged array is consumed)."""
        self._device_slots.acquire(ACT_CLASS)
        try:
            return self._h2d_copy(rec.value)
        except BaseException:
            self._device_slots.release_all([ACT_CLASS])
            raise

    def _issue_act_read(self, unit: str, rec: _ActCkpt,  # thread: executor
                        state: _ExecState) -> None:
        """Sync-mode issue half: async SSD read into a tracked host
        buffer; the ActFetchOp waits it out and H2Ds inline."""
        buf = np.empty(rec.shape, rec.np_dtype)
        handle = self.tracker.alloc("activation_checkpoints", buf.nbytes,
                                    tag="act_fetch_staging")
        try:
            fut = self.store.read_async(self._act_key(unit, buf.nbytes), buf)
        except BaseException:
            self.tracker.free(handle)
            raise
        state.act_reads[unit] = (fut, buf, handle)

    def _act_fetch(self, op: ActFetchOp, state: _ExecState) -> None:  # thread: executor
        """Wait half of the split ActFetchOp: surface a failed save
        exactly once, top up the issue window, then make the checkpoint
        device-resident from whichever tier it landed in."""
        unit = op.unit
        rec = state.checkpoints[unit]
        if rec.fut is not None:
            t0 = time.perf_counter()
            try:
                rec.fut.result()
            except BaseException as e:
                if self._grad_writer is not None:
                    self._grad_writer.consume_error(e)  # delivered here
                raise
            finally:
                rec.fut = None
                self._ostats.act_save_wait_seconds += \
                    time.perf_counter() - t0
        self._act_issue_ahead(state)
        t0 = time.perf_counter()
        staged = state.act_stage.pop(unit, None)
        if staged is not None:
            try:
                arr = staged.result()
            finally:
                # satellite fix: free under finally — a failed stage must
                # not leak the host copy's tracker handle
                if rec.handle is not None:
                    self.tracker.free(rec.handle)
                    rec.handle = None
            rec.value, rec.tier, rec.slot = arr, "ready", True
        elif unit in state.act_reads:
            read_fut, buf, handle = state.act_reads.pop(unit)
            try:
                read_fut.result()
                arr = jnp.asarray(buf, dtype=rec.dtype)
            finally:
                self.tracker.free(handle)
            rec.value, rec.tier = arr, "ready"
        elif rec.tier == "host":
            # inline H2D; free under try/finally — the pre-PR-9 restore
            # leaked the tracker handle when jnp.asarray raised
            try:
                arr = jnp.asarray(rec.value, dtype=rec.dtype)
            finally:
                self.tracker.free(rec.handle)
                rec.handle = None
            rec.value, rec.tier = arr, "ready"
        elif rec.tier == "ssd":
            # cold path (defensive): read + H2D inline
            buf = np.empty(rec.shape, rec.np_dtype)
            handle = self.tracker.alloc("activation_checkpoints", buf.nbytes,
                                        tag="act_fetch_staging")
            try:
                self.store.read(self._act_key(unit, buf.nbytes), buf)
                arr = jnp.asarray(buf, dtype=rec.dtype)
            finally:
                self.tracker.free(handle)
            rec.value, rec.tier = arr, "ready"
        self._ostats.act_fetch_wait_seconds += time.perf_counter() - t0

    def _consume_checkpoint(self, unit: str, state: _ExecState):  # thread: executor
        """block_bwd's checkpoint take: pop the record, return its device
        array, and give back its ACT device slot."""
        rec = state.checkpoints.pop(unit)
        if rec.slot:
            self._device_slots.release_all([ACT_CLASS])
            state.act_slots_out -= 1
            rec.slot = False
        if rec.tier in ("device", "ready"):
            return rec.value
        # validated at plan build (block_bwd only consumes saved/ready);
        # defensive
        raise RuntimeError(f"checkpoint for {unit!r} is {rec.tier!r}, not "
                           f"device-resident")

    def _discard_checkpoint(self, rec: _ActCkpt,  # thread: executor
                            state: _ExecState) -> None:
        """Abort-path release of one checkpoint record: wait out an
        in-flight save (the writer thread may still be mutating the
        record), return its device slot, free its host handle."""
        if rec.fut is not None:
            with contextlib.suppress(BaseException):
                rec.fut.result()
            rec.fut = None
        if rec.slot:
            self._device_slots.release_all([ACT_CLASS])
            state.act_slots_out -= 1
            rec.slot = False
        if rec.handle is not None:
            self.tracker.free(rec.handle)
            rec.handle = None

    # -- expert-page streaming (paged MoE) -----------------------------------
    #
    # Lifecycle (mirrors the weight stream's split issue/wait halves):
    #
    #   route   block_route (or a cached-decode route variant) computes the
    #           expert assignment; the executor reads the indices back and
    #           binds them for the WHOLE plan run (the backward reuses the
    #           forward's routing),
    #   issue   the FetchOp lookahead window prestages the PREDICTED
    #           routed set — this plan's own routing when already known
    #           (backward: exact), else the previous step's actual set,
    #           or every expert under expert_paging="all" — as zero-
    #           initialized (E, ...) host stacks H2D'd under a counted
    #           __expert__ device slot on the staging worker,
    #   wait    ExpertFetchOp resolves the ACTUAL routed set; a staged set
    #           that covers it is a hit, otherwise the stale stacks are
    #           dropped (slot returned) and the actual set is staged
    #           on demand,
    #   consume block_moe / block_moe_bwd read the stacks; ExpertReleaseOp
    #           returns the device slot and trims the page cache back
    #           under its residency budget.
    #
    # Deadlock-freedom of the staged path: the executor never submits an
    # expert stage while expert_slots_out >= the EXPERT_CLASS depth, so
    # the staging worker's acquire is always immediately satisfiable — it
    # can never wedge the shared FIFO worker behind an unreleasable slot.
    # Unrouted experts are never read by moe_ffn's combine (dropped slots
    # carry weight zero), so routed-only stacks are bit-identical to
    # all-resident ones by construction.

    def _expert_predict(self, unit: str, state: _ExecState):  # thread: executor
        """Predicted routed set for a window prestage: every expert under
        "all", this plan's own routing when the route already ran (the
        backward re-fetch — exact by construction), else the previous
        step's actual set (None before any step routed this unit)."""
        if self._expert_mode == "all":
            return np.arange(self._expert_meta[unit]["n_experts"])
        route = state.expert_route.get(unit)
        if route is not None:
            return np.unique(route.reshape(-1))
        return self._expert_prior.get(unit)

    def _build_expert_stacks(self, unit: str, ids) -> list:  # thread: executor, h2d-worker
        """Zero-initialized (E, ...) host stacks with the routed experts'
        pages memcpy'd in (pinned across each copy).  Rows of unrouted
        experts stay zero — never read by the combine — so the stacks are
        shape-identical to the all-resident ones and the jitted program
        is shared.  Byte accounting lands here: only routed pages cost
        SSD/memcpy traffic."""
        meta = self._expert_meta[unit]
        triples = meta["experts"]
        _unit, umeta = self._units[unit]
        cd = self.policy.adam.compute_np_dtype
        stacks = [np.zeros((meta["n_experts"],) + tuple(umeta[pname][0]), cd)
                  for pname in triples[0]]
        nbytes = 0
        for i in ids:
            for j, pname in enumerate(triples[int(i)]):
                view = self._expert_cache.ensure(unit, pname, pin=True)
                try:
                    stacks[j][int(i)] = view
                finally:
                    self._expert_cache.unpin(unit, pname)
                nbytes += view.nbytes
        self._ostats.bump("expert_fetch_bytes", nbytes)
        return stacks

    def _stage_experts(self, unit: str, ids: tuple) -> tuple:  # thread: h2d-worker
        """Staging-worker body: build the routed stacks, then H2D under a
        counted __expert__ device slot.  The stacks are built BEFORE the
        acquire so a failed expert SSD read surfaces at the fetch gate
        with no device slot held."""
        stacks = self._build_expert_stacks(unit, ids)
        self._device_slots.acquire(EXPERT_CLASS)
        try:
            return (frozenset(int(i) for i in ids),
                    tuple(self._h2d_copy(a) for a in stacks))
        except BaseException:
            self._device_slots.release_all([EXPERT_CLASS])
            raise

    def _submit_expert_stage(self, unit: str, ids,  # thread: executor
                             state: _ExecState) -> None:
        """Issue half: queue one unit's expert staging on the staging
        worker, behind the same unit's weight (and KV) stages."""
        fut = self._h2d.submit(
            functools.partial(self._stage_experts, unit, tuple(ids)))
        state.expert_stage.setdefault(unit, deque()).append(fut)
        state.stage_seq.append(("ex", unit))
        state.expert_slots_out += 1

    def _expert_fetch_now(self, unit: str, ids,  # thread: executor
                          state: _ExecState) -> tuple:
        """On-demand stage (miss, or no prestage was issued): through the
        staging worker when an EXPERT slot is guaranteed free — the
        executor is about to block on the result, so the worker's acquire
        must not be able to block — else built + copied inline without a
        slot (transient, accounted to the fetch wait)."""
        if self._h2d is not None and state.expert_slots_out < 2:
            state.expert_slots_out += 1
            fut = self._h2d.submit(
                functools.partial(self._stage_experts, unit, tuple(ids)))
            # NOT in stage_seq: consumed synchronously right here, even on
            # error (the worker released any slot it held before raising)
            try:
                _ids, stacks = fut.result()
            except BaseException:
                state.expert_slots_out -= 1
                raise
            return stacks, (EXPERT_CLASS,)
        stacks = tuple(self._h2d_copy(a)
                       for a in self._build_expert_stacks(unit, ids))
        return stacks, ()

    def _expert_fetch(self, op: ExpertFetchOp,  # thread: executor
                      state: _ExecState) -> None:
        """Wait half of the split ExpertFetchOp: resolve the actual routed
        set, take a covering staged prediction, restage on a miss."""
        unit = op.unit
        if self._expert_mode == "all":
            actual = np.arange(self._expert_meta[unit]["n_experts"])
        else:
            actual = np.unique(state.expert_route[unit].reshape(-1))
        self._expert_prior[unit] = actual
        t0 = time.perf_counter()
        stacks = tokens = None
        pending = state.expert_stage.get(unit)
        if pending:
            fut = pending.popleft()
            if not pending:
                del state.expert_stage[unit]
            self._ostats.expert_stage_gets += 1
            try:
                staged_ids, staged = fut.result()
            except BaseException:
                # a failed expert SSD read surfaces exactly once, here;
                # the worker held no slot (stacks build precedes acquire)
                state.expert_slots_out -= 1
                raise
            if set(int(i) for i in actual) <= staged_ids:
                self._ostats.expert_stage_hits += 1
                stacks, tokens = staged, (EXPERT_CLASS,)
            else:
                # stale prediction: drop the stacks, return the slot, and
                # stage the actual routed set on demand
                del staged
                self._device_slots.release_all([EXPERT_CLASS])
                state.expert_slots_out -= 1
        if stacks is None:
            stacks, tokens = self._expert_fetch_now(unit, actual, state)
        state.expert_live[unit] = tuple(stacks)
        state.expert_slots[unit] = tokens
        self._ostats.expert_fetch_wait_seconds += time.perf_counter() - t0

    def _expert_release(self, op: ExpertReleaseOp,  # thread: executor
                        state: _ExecState) -> None:
        """ExpertReleaseOp: drop the staged device stacks, return the
        __expert__ slot, and trim the page cache over its keep line (the
        host pages themselves stay cached for future steps)."""
        state.expert_live.pop(op.unit, None)
        tokens = state.expert_slots.pop(op.unit, ())
        if tokens:
            self._device_slots.release_all(tokens)
            state.expert_slots_out -= 1
        self._expert_cache.release_round()

    # -- the executor --------------------------------------------------------

    def execute(self, plan: StreamPlan, state: _ExecState) -> _ExecState:  # thread: executor
        """Walk the plan with lookahead-N prefetch; drain on any error."""
        if self._closed:
            raise RuntimeError("session is closed")
        fetch_order = plan.fetch_order
        fetch_pos = 0       # index of the FetchOp being executed
        next_prefetch = 0   # first fetch position not yet issued async
        # Units whose KV window this plan reads (decode_cached blocks):
        # only they get KV refill prefetch + staged-gather submissions —
        # prefill plans overwrite whole pages, so refilling ahead of a
        # write would be wasted I/O.
        kv_read_units = (frozenset(
            op.unit for op in plan.ops if isinstance(op, KVReadOp))
            if state.kv is not None else frozenset())
        expert_units = frozenset(
            op.unit for op in plan.ops if isinstance(op, ExpertFetchOp))
        state.act_order = [op.unit for op in plan.ops
                           if isinstance(op, ActFetchOp)]
        state.act_next = 0
        try:
            for op in plan.ops:
                if isinstance(op, FetchOp):
                    if state.act_order:
                        # checkpoint fetches ride the same window — issued
                        # BEFORE this dispatch's weight stages so they are
                        # not queued behind a weight stage that is parked
                        # on a device slot the backward has yet to release
                        self._act_issue_ahead(state)
                    limit = min(fetch_pos + self.lookahead, len(fetch_order))
                    while next_prefetch < limit:
                        unit = fetch_order[next_prefetch]
                        head = next_prefetch == fetch_pos
                        # Cross-step gate: the unit's previous-step Adam
                        # write-back must land before its weights are
                        # re-read from the store.  Ahead-of-need positions
                        # stall the window instead of blocking compute; the
                        # head position always goes through the wait, which
                        # is also where a failed Adam stage is delivered
                        # (a done-with-exception future is NOT ready —
                        # fetching would read stale weights).
                        if head:
                            self._optim_wait(unit)
                        elif not self._optim_ready(unit):
                            break
                        # A unit can appear twice inside the window (forward
                        # + backward re-fetch).  prefetch() is idempotent per
                        # key, so issuing the later position while the earlier
                        # ticket is still in flight would alias onto it and
                        # the later FetchOp would fall back to a synchronous
                        # read.  Stall the window here; the position is
                        # re-tried at the next FetchOp, after the earlier
                        # fetch has been consumed.
                        if not head and self._unit_in_flight(unit):
                            break
                        self._prefetch_unit(unit)
                        if self._h2d is not None:
                            self._submit_h2d(unit, state)
                        if unit in kv_read_units:
                            # ride the same window: block i+1's KV page
                            # refills + window gather/H2D overlap block
                            # i's compute (refill is a no-op for pages
                            # that are resident or never spilled)
                            state.kv.prefetch_window(unit, state.kv_time)
                            if self._h2d is not None and \
                                    unit not in state.kv_stage:
                                self._submit_kv_stage(unit, state)
                        if unit in expert_units and self._h2d is not None \
                                and state.expert_slots_out < 2:
                            # prestage the predicted routed set behind the
                            # unit's weight/KV stages; skipped when the
                            # prediction is unknown (first step) or the
                            # EXPERT slot budget is out — the ExpertFetchOp
                            # then stages on demand
                            pred = self._expert_predict(unit, state)
                            if pred is not None and len(pred):
                                self._submit_expert_stage(unit, pred, state)
                        next_prefetch += 1
                    t_fetch = time.perf_counter()
                    state.live[op.unit] = self._fetch_unit(op.unit, state)
                    self._ostats.fetch_seconds += \
                        time.perf_counter() - t_fetch
                    fetch_pos += 1
                elif isinstance(op, ComputeOp):
                    self._compute(op, state)
                elif isinstance(op, KVReadOp):
                    self._read_kv(op.unit, state)
                elif isinstance(op, KVWriteOp):
                    self._write_kv(op, state)
                elif isinstance(op, ActSaveOp):
                    self._exec_act_save(op, state)
                elif isinstance(op, ActFetchOp):
                    self._act_fetch(op, state)
                elif isinstance(op, ExpertFetchOp):
                    self._expert_fetch(op, state)
                elif isinstance(op, ExpertReleaseOp):
                    self._expert_release(op, state)
                elif isinstance(op, GradWriteOp):
                    self._dispatch_grad_write(op.unit, state)
                elif isinstance(op, OverflowCheckOp):
                    self._exec_overflow(op, state)
                elif isinstance(op, OptimStepOp):
                    self._exec_optim(op.unit, state)
                elif isinstance(op, ReleaseOp):
                    state.live.pop(op.unit, None)
                    tokens = state.live_slots.pop(op.unit, None)
                    if tokens:
                        self._device_slots.release_all(tokens)
                    kv_tokens = state.kv_slots.pop(op.unit, None)
                    if kv_tokens:
                        self._device_slots.release_all(kv_tokens)
                    if state.act_order:
                        # a block_bwd just gave an ACT slot back — top the
                        # issue window up ahead of the next weight stages
                        self._act_issue_ahead(state)
        except BaseException:
            self._abort_execute(state)
            raise
        out = state.loss if state.loss is not None else state.logits
        if out is not None:
            self.output_devices = frozenset(out.devices())
        return state

    def _abort_execute(self, state: _ExecState) -> None:
        """Error path: nothing may leak.  Device-slot tokens are returned
        (resident units first, so a staging worker blocked on a slot can
        finish), staged fetches waited out, the gradient writer drained
        (resolving in-flight activation saves), host-held checkpoints and
        staged act reads freed, and outstanding reads drained back to the
        pool.  (KV pool slots belong to the SpillableKVCache, whose owner
        — generate()'s finally — closes it.)"""
        for tokens in state.live_slots.values():
            self._device_slots.release_all(tokens)
        state.live_slots.clear()
        for tokens in state.kv_slots.values():
            self._device_slots.release_all(tokens)
        state.kv_slots.clear()
        for tokens in state.expert_slots.values():
            if tokens:
                self._device_slots.release_all(tokens)
        state.expert_slots.clear()
        state.expert_live.clear()
        state.live.clear()
        # Staged fetches/KV windows/act checkpoints must settle before the
        # swapper drain: a queued staging job that ran *after* the drain
        # would re-issue its reads and leak device slots.  All three kinds
        # interleave on ONE FIFO worker, so waits must follow stage_seq's
        # submission order — waiting a later weight future while an
        # earlier KV task still blocks on a kv device slot would deadlock.
        # (Act stages never block on their slot: the executor's
        # act_slots_out cap guarantees a free ACT slot per submission.)
        # Consumed submissions have empty deques / absent keys and are
        # skipped; each released token keeps the worker's next blocked
        # acquire satisfiable.
        for kind, unit in state.stage_seq:
            if kind == "w":
                pending = state.h2d.get(unit)
                if not pending:
                    continue
                fut = pending.popleft()
                try:
                    _params, tokens = fut.result()
                except BaseException:
                    continue      # the worker released its own claims
                self._device_slots.release_all(tokens)
            elif kind == "kv":
                fut = state.kv_stage.pop(unit, None)
                if fut is None:
                    continue
                try:
                    fut.result()
                except BaseException:
                    continue      # the worker released its own slot
                self._device_slots.release_all([KV_CLASS])
            elif kind == "ex":
                pending = state.expert_stage.get(unit)
                if not pending:
                    continue
                fut = pending.popleft()
                try:
                    fut.result()
                except BaseException:
                    continue      # the worker released its own slot
                self._device_slots.release_all([EXPERT_CLASS])
            else:   # "act"
                fut = state.act_stage.pop(unit, None)
                if fut is None:
                    continue
                try:
                    fut.result()
                except BaseException:
                    continue      # the worker released its own slot
                self._device_slots.release_all([ACT_CLASS])
        state.stage_seq.clear()
        state.h2d.clear()
        state.kv_live.clear()
        state.kv_append.clear()
        state.act_stage.clear()
        state.expert_stage.clear()
        state.expert_route.clear()
        state.expert_slots_out = 0
        if self._grad_writer is not None:
            # the original executor error propagates; the drain also
            # resolves in-flight activation saves, so the checkpoint
            # discard below sees settled records
            with contextlib.suppress(BaseException):
                self._grad_writer.drain()
        for rec in state.checkpoints.values():
            self._discard_checkpoint(rec, state)
        state.checkpoints.clear()
        for read_fut, _buf, handle in state.act_reads.values():
            with contextlib.suppress(BaseException):
                read_fut.result()   # the async pread targets the buffer
            self.tracker.free(handle)
        state.act_reads.clear()
        state.act_slots_out = 0
        self.swapper.drain()

    def _compute(self, op: ComputeOp, state: _ExecState) -> None:
        params = state.live[op.unit]
        if op.kind == "embed":
            state.h = self._jit_embed(params, state.tokens)
        elif op.kind == "block":
            if op.save_input:
                # bind the device array only — the D2H (and SSD write)
                # happen at the unit's ActSaveOp, off the executor thread
                # under full overlap; device-tier plans keep it as-is
                state.checkpoints[op.unit] = _ActCkpt(op.unit, state.h)
            state.h = self._jit_block(params, state.h)
        elif op.kind == "head_loss_grad":
            state.loss, head_grads, state.dh = self._jit_head(
                params, state.h, state.labels, state.scale)
            state.grads[op.unit] = head_grads
        elif op.kind == "head_loss":
            state.loss = self._jit_head_loss(params, state.h, state.labels)
        elif op.kind == "head_logits":
            state.logits = self._jit_head_logits(params, state.h)
        elif op.kind == "head_logits_last":
            state.logits = self._jit_head_last(params, state.h,
                                               state.last_pos)
        elif op.kind == "block_prefill":
            state.h, k, v = self._jit_block_prefill(params, state.h)
            state.kv_append[op.unit] = (k, v)
        elif op.kind == "block_step":
            k_dev, v_dev = state.kv_live.pop(op.unit)
            state.h, k, v = self._jit_block_step(
                params, state.h, k_dev, v_dev, state.cache_len,
                chunk=self.decode_spec.bucket)
            state.kv_append[op.unit] = (k, v)
        elif op.kind == "block_verify":
            k_dev, v_dev = state.kv_live.pop(op.unit)
            state.h, k, v = self._jit_block_verify(
                params, state.h, k_dev, v_dev, state.cache_len,
                chunk=self.decode_spec.bucket)
            state.kv_append[op.unit] = (k, v)
        elif op.kind == "block_route":
            if op.save_input:
                state.checkpoints[op.unit] = _ActCkpt(op.unit, state.h)
            state.h, idx = self._jit_block_route(params, state.h)
            # host readback: the fetch decision (unavoidable — the routed
            # set IS host control flow); the same indices are fed back to
            # block_moe so decision and compute agree by construction
            state.expert_route[op.unit] = np.asarray(idx)
        elif op.kind == "block_moe":
            gate, up, down = state.expert_live[op.unit]
            state.h = self._jit_block_moe(
                params, gate, up, down,
                jnp.asarray(state.expert_route[op.unit]), state.h)
        elif op.kind == "block_moe_bwd":
            x = self._consume_checkpoint(op.unit, state)
            gate, up, down = state.expert_live[op.unit]
            dparams, dgate, dup, ddown, state.dh = self._jit_block_moe_bwd(
                params, gate, up, down,
                jnp.asarray(state.expert_route[op.unit]), x, state.dh)
            # merge the stacked expert grads back under their per-expert
            # param keys (the flat-buffer layout); unrouted experts' rows
            # are exactly zero — their weights were never read
            grads = dict(dparams)
            for i, triple in enumerate(
                    self._expert_meta[op.unit]["experts"]):
                for g, pname in zip((dgate, dup, ddown), triple):
                    grads[pname] = g[i]
            state.grads[op.unit] = grads
        elif op.kind == "block_prefill_route":
            state.h, k, v, idx = self._jit_prefill_route(params, state.h)
            state.kv_append[op.unit] = (k, v)
            state.expert_route[op.unit] = np.asarray(idx)
        elif op.kind == "block_step_route":
            k_dev, v_dev = state.kv_live.pop(op.unit)
            state.h, k, v, idx = self._jit_step_route(
                params, state.h, k_dev, v_dev, state.cache_len,
                chunk=self.decode_spec.bucket)
            state.kv_append[op.unit] = (k, v)
            state.expert_route[op.unit] = np.asarray(idx)
        elif op.kind == "block_verify_route":
            k_dev, v_dev = state.kv_live.pop(op.unit)
            state.h, k, v, idx = self._jit_verify_route(
                params, state.h, k_dev, v_dev, state.cache_len,
                chunk=self.decode_spec.bucket)
            state.kv_append[op.unit] = (k, v)
            state.expert_route[op.unit] = np.asarray(idx)
        elif op.kind == "block_bwd":
            x = self._consume_checkpoint(op.unit, state)
            state.grads[op.unit], state.dh = self._jit_block_bwd(
                params, x, state.dh)
        elif op.kind == "block_recompute":
            # re-run this block's forward from its own (peeked, not
            # consumed — its block_bwd still needs it) checkpoint to
            # re-derive the successor's dropped checkpoint
            src = state.checkpoints[op.unit]
            if src.tier not in ("device", "ready"):  # validated; defensive
                raise RuntimeError(f"recompute source for {op.unit!r} is "
                                   f"{src.tier!r}, not device-resident")
            state.checkpoints[op.recompute_for] = _ActCkpt(
                op.recompute_for, self._jit_block(params, src.value))
        elif op.kind == "embed_bwd":
            state.grads[op.unit] = self._jit_embed_bwd(
                params, state.tokens, state.dh)
        else:  # validated at plan build; defensive
            raise ValueError(f"unknown compute kind {op.kind!r}")

    def _read_kv(self, unit_name: str, state: _ExecState) -> None:
        """Wait half of the split KVReadOp: take the staged device K/V
        window (overlap modes — the gather + H2D already ran on the
        staging worker under the previous block's compute) or gather and
        H2D inline (sync mode)."""
        fut = state.kv_stage.pop(unit_name, None)
        if fut is not None:
            hit = fut.done()
            t0 = time.perf_counter()
            k_dev, v_dev = fut.result()
            self._ostats.kv_stage_wait_seconds += time.perf_counter() - t0
            self._ostats.kv_stage_gets += 1
            self._ostats.kv_stage_hits += int(hit)
            state.kv_slots[unit_name] = (KV_CLASS,)
            state.kv_live[unit_name] = (k_dev, v_dev)
            return
        # Inline path (sync overlap): the gather already copies out of the
        # pool pages under pins, and _h2d_copy copies again into jax — the
        # page slots are free to be spilled (and their memory reused)
        # while the jitted step still reads the device buffer.
        k_host, v_host = state.kv.gather_window(unit_name, state.kv_time)
        state.kv_live[unit_name] = (self._h2d_copy(k_host),
                                    self._h2d_copy(v_host))

    def _write_kv(self, op: KVWriteOp, state: _ExecState) -> None:
        """Land this unit's new K/V in its host pages (D2H): one token
        appended to the tail page (``step``), a K-token draft window
        appended past each slot's length (``verify`` — lengths advance
        only when the host commits the accepted prefix), or the whole
        padded prompt window scattered across pages (``prefill``); the
        cache spills dirty pages onward if the residency budget is
        exceeded."""
        k, v = state.kv_append.pop(op.unit)
        if op.mode == "prefill":
            state.kv.write_prefill(op.unit, np.asarray(k), np.asarray(v),
                                   slots=state.kv_write_slots)
        elif op.mode == "verify":
            state.kv.append_window(op.unit, np.asarray(k), np.asarray(v))
        else:
            state.kv.append(op.unit, np.asarray(k), np.asarray(v))

    # -- gradient write-back -------------------------------------------------

    def _dispatch_grad_write(self, unit_name: str, state: _ExecState) -> None:
        """Run the D2H + flat-buffer scatter inline (sync/h2d modes) or
        enqueue it on the writer thread (full overlap), gated on the
        previous step's Adam having consumed the unit's flat region."""
        grads = state.grads.pop(unit_name)
        if self._grad_writer is None:
            self._write_grads(unit_name, grads)
            return
        with self._optim_lock:
            gate = self._optim_futures.get(unit_name)
        self._grad_writer.submit(
            functools.partial(self._write_grads, unit_name, grads, gate))

    def _write_grads(self, unit_name: str, grads: dict,  # thread: executor, writer
                     gate: Future | None = None) -> None:
        """Accumulate device grads into the fp32 host flat buffer, then
        screen the unit's region for Inf/NaN (fused policies only): the
        per-subgroup half of the overflow check runs right here — on the
        writer thread under full overlap — and the barrier only ORs the
        verdicts."""
        if self.flat is None:
            raise RuntimeError("serve-mode session has no gradient buffer")
        if gate is not None:
            gate.result()   # step k-1's Adam must consume flat[unit] first
        _unit, meta = self._units[unit_name]
        with self._ostats.timed("grad_d2h_seconds"):
            for key in meta:
                off, size, shape = self._flat_offsets[f"{unit_name}/{key}"]
                g = np.asarray(grads[key], dtype=np.float32).reshape(-1)  # D2H
                self.flat[off:off + size] = g
        if self._screen_regions:
            self._screen_unit_region(unit_name)

    def _screen_unit_region(self, unit_name: str) -> None:  # thread: executor, writer
        lo, hi = self._unit_flat_region[unit_name]
        t0 = time.perf_counter()
        verdict = bool(check_region(self.flat, lo, hi, fused=True,
                                    tracker=self.tracker))
        self._ostats.add_worker_seconds("overflow_screen_seconds",
                                        time.perf_counter() - t0)
        with self._screen_lock:
            self._region_verdicts[unit_name] = verdict

    # -- overflow + optimizer plan ops ---------------------------------------

    def _exec_overflow(self, op: OverflowCheckOp, state: _ExecState) -> None:
        """OverflowCheckOp: drain the writer (the barrier that makes every
        GradWriteOp visible), combine the step verdict, update the scaler.

        With ``op.regions`` under a fused policy the verdict is the OR of
        the per-region screens that already ran as each write-back landed
        (equal to the whole-buffer scan by the partition invariant —
        property-tested); the chained-baseline policy, whose 2.25x
        temporary peak is the thing being measured, keeps the legacy
        whole-buffer scan here."""
        if self.flat is None:
            raise RuntimeError("serve-mode session has no gradient buffer")
        if self._grad_writer is not None:
            t0 = time.perf_counter()
            self._grad_writer.drain()
            self._ostats.gradwrite_drain_seconds += time.perf_counter() - t0
        with self._screen_lock:
            verdicts, self._region_verdicts = self._region_verdicts, {}
        if op.regions and self._screen_regions:
            overflow = False
            for unit in op.regions:
                verdict = verdicts.get(unit)
                if verdict is None:
                    # a write-back that bypassed the screen (e.g. a test
                    # stubbing _write_grads): screen the region now so the
                    # verdict still covers every gradient
                    lo, hi = self._unit_flat_region[unit]
                    t0 = time.perf_counter()
                    verdict = bool(check_region(self.flat, lo, hi,
                                                fused=True,
                                                tracker=self.tracker))
                    self._ostats.add_worker_seconds(
                        "overflow_screen_seconds", time.perf_counter() - t0)
                overflow = overflow or verdict
        else:
            overflow = bool(flat_overflow_check(
                self.flat, fused=self.policy.fused_overflow,
                tracker=self.tracker))
        state.overflowed = overflow
        state.apply = self.scaler.update(state.overflowed)

    def _exec_optim(self, unit_name: str, state: _ExecState) -> None:
        """OptimStepOp: stream one unit's subgroups through the host Adam —
        inline, or pipelined across the optimizer + state-prefetch workers
        with a readiness future that resolves when the unit's **last
        write-back lands** (commit), gating the next step's fetch and
        grad-write for this unit.

        An overflow-skipped step (``state.apply`` false) returns before
        anything is enqueued, so no state is prefetched for it and nothing
        is left in flight to corrupt."""
        if self.optimizer is None:
            raise RuntimeError("serve-mode session has no optimizer")
        if state.apply is None:   # validated at plan build; defensive
            raise RuntimeError("OptimStepOp before OverflowCheckOp")
        if not state.apply:
            return                # skipped step: weights unchanged
        if not state.optim_begun:
            state.optim_begun = True
            if self._optim_worker is not None:
                # previous-step Adam tasks have all resolved (every unit's
                # grad write this step gated on its step k-1 future and the
                # barrier drained the writer), so the pipeline bookkeeping
                # can be reset from this thread before new work lands
                with self._adam_lock:
                    self._adam_work = []
                self._adam_issued = 0
                self._adam_inflight = deque()
                self._adam_poison = None
                self._optim_worker.submit(self.optimizer.begin_step)
            else:
                self.optimizer.begin_step()
        inv_scale = np.float32(1.0 / state.grad_scale)
        if self._optim_worker is not None:
            _unit, meta = self._units[unit_name]
            with self._adam_lock:
                lo = len(self._adam_work)
                self._adam_work.extend(
                    (unit_name, key) for key in meta)
                hi = len(self._adam_work)
            task = (self._optim_unit_paged
                    if unit_name in self._expert_meta
                    else self._optim_unit_pipelined)
            fut = self._optim_worker.submit(
                functools.partial(task, unit_name, lo, hi, inv_scale))
        else:
            self._optim_unit(unit_name, inv_scale)
            if unit_name in self._expert_meta:
                self._expert_cache.invalidate_unit(unit_name)
            fut = done_future()
        with self._optim_lock:
            self._optim_futures[unit_name] = fut

    def _optim_unit(self, unit_name: str, inv_scale: np.float32) -> None:  # thread: executor
        """Inline (sync/h2d) Adam stage: stream subgroups synchronously
        (the same three halves, composed back to back; no compute-weight
        return copy is materialized — the store holds it)."""
        _unit, meta = self._units[unit_name]
        for key in meta:
            staged = self.optimizer.issue_subgroup(f"{unit_name}/{key}")
            commit = self._update_subgroup(staged, inv_scale)
            with self._ostats.timed("adam_commit_wait_seconds"):
                commit.result()

    def _update_subgroup(self, staged, inv_scale: np.float32) -> Future:  # thread: executor, optim-worker
        """The arithmetic of one staged subgroup — unscale, Adam, and the
        commit's truncation and compute-copy casts — as one ``adam_update``
        interval; returns the commit's write-back future.  A failed update
        releases the staging buffer."""
        with self._ostats.timed("adam_update_seconds"):
            try:
                self.optimizer.compute_subgroup(
                    staged, self._unit_grad(staged.key),
                    grad_scale=inv_scale)
            except BaseException:
                self.optimizer.discard_staged(staged)
                raise
            return self.optimizer.commit_subgroup_async(staged)

    def _unit_grad(self, skey: str) -> np.ndarray:  # thread: executor, optim-worker
        """One subgroup's still-scaled gradient: a view of the flat buffer.

        The update unscales it tile by tile (``grad_scale``), with the
        scale the grads were produced under, not the post-update one — on
        a growth step they differ by 2x.  Reading the flat buffer in place
        is safe: the next step's gradient write-back into this region
        gates on the unit's readiness future, which resolves only after
        the unit's commits land, and so after its update has read the
        region.
        """
        off, size, shape = self._flat_offsets[skey]
        return self.flat[off:off + size].reshape(shape)

    # -- the pipelined Adam stage (full overlap) -----------------------------

    def _adam_ensure_issued(self, upto: int) -> None:  # thread: optim-worker
        """Submit state-prefetch issues for work indices < ``upto``.

        Runs on the optimizer worker only.  Deadlock-freedom of the
        arena's blocking acquire (inside the issue, on the state-prefetch
        worker): every held buffer is released by a write-completion
        callback on the store's async pool (commit), by the optimizer
        worker (error paths), or by the issue's own failure handler —
        never by a task queued *behind* the blocked issue on the
        state-prefetch worker itself.
        """
        with self._adam_lock:
            n = len(self._adam_work)
            pending = [self._adam_work[i]
                       for i in range(self._adam_issued, min(upto, n))]
        for unit_name, key in pending:
            fut = self._optim_prefetch.submit(functools.partial(
                self.optimizer.issue_subgroup, f"{unit_name}/{key}"))
            self._adam_inflight.append((self._adam_issued, fut))
            self._adam_issued += 1

    def _optim_unit_pipelined(self, unit_name: str, lo: int, hi: int,  # thread: optim-worker
                              inv_scale: np.float32) -> None:
        """Optimizer-worker task for one unit's subgroups [lo, hi):
        subgroup *k+1*'s (master, m, v) streams into the staging arena
        while *k*'s ``adam_update`` runs, and *k−1*'s write-backs drain
        asynchronously behind them on the optimizer's dedicated
        write-back executor.  Returns — resolving the unit's readiness
        future — only once every commit landed.

        On any failure the whole in-flight window is drained (commits
        waited, issued-but-uncomputed buffers released) so the staging
        arena is whole again, and the step is **poisoned**: the remaining
        unit tasks fail fast with the *same* exception instance before
        issuing anything, so a failure surfaces exactly once (the worker
        never re-latches a delivered instance) while every affected
        unit's readiness future still refuses to serve its un-updated
        weights."""
        if self._adam_poison is not None:
            raise self._adam_poison
        commits: list[Future] = []
        try:
            for g in range(lo, hi):
                self._adam_ensure_issued(g + 2)
                idx, staged_fut = self._adam_inflight.popleft()
                if idx != g:    # defensive; the reset/cleanup paths keep
                    raise RuntimeError(   # issue order == work order
                        f"adam pipeline out of order: staged {idx}, "
                        f"expected {g}")
                with self._ostats.timed("optim_prefetch_wait_seconds"):
                    staged = staged_fut.result()
                commits.append(self._update_subgroup(staged, inv_scale))
            with self._ostats.timed("adam_commit_wait_seconds"):
                for commit in commits:
                    commit.result()
        except BaseException as e:
            self._adam_poison = e
            self._adam_abort(commits, resume_at=hi)
            raise

    def _optim_unit_paged(self, unit_name: str, lo: int, hi: int,  # thread: optim-worker
                          inv_scale: np.float32) -> None:
        """Pipelined Adam for a paged-MoE unit, then expert-page
        invalidation (the commit rewrote the unit's SSD compute copies)
        BEFORE the readiness future resolves: the next step's fetch
        window — and therefore every expert prestage/ensure for this
        unit — gates on that future, so no page can be pinned while the
        invalidation drops it."""
        self._optim_unit_pipelined(unit_name, lo, hi, inv_scale)
        self._expert_cache.invalidate_unit(unit_name)

    def _adam_abort(self, commits: list[Future], *, resume_at: int) -> None:  # thread: optim-worker
        """Failure path of a unit task: wait out this unit's commits
        (each releases its own buffer), release every issued-but-never-
        computed staging buffer, and reset the issue counter to
        ``resume_at`` (the failed unit's end).  The reset is bookkeeping
        hygiene only: the step is poisoned, so the remaining unit tasks
        fail fast without ever issuing again — nothing is re-issued until
        the next step resets the pipeline wholesale."""
        for commit in commits:
            # the buffer was released in commit's finally
            with contextlib.suppress(BaseException):
                commit.result()
        while self._adam_inflight:
            _idx, staged_fut = self._adam_inflight.popleft()
            try:
                staged = staged_fut.result()
            except BaseException:
                continue        # a failed issue released its own buffer
            self.optimizer.discard_staged(staged)
        self._adam_issued = resume_at

    def _snapshot_optim_io(self) -> None:  # thread: optim-worker
        # queued after a step's last OptimStepOp: the completed-step ledger.
        # Locked: train_step reads it from the executor thread while this
        # worker task may still be landing the previous step's snapshot.
        io = self.optimizer.last_io_bytes
        with self._optim_lock:
            self._optim_io_completed = io

    # -- workloads -----------------------------------------------------------

    def train_step(self, tokens: np.ndarray, labels: np.ndarray) -> dict:  # thread: executor
        """One streamed training step; the whole pipeline — forward,
        backward, overflow screen, host Adam — executes as the train plan.

        Under ``overlap="full"`` the optimizer stage may still be streaming
        when this returns (it overlaps the *next* step's prefetch window);
        ``metrics["optimizer_io_bytes"]`` then reports the most recently
        *completed* step (0 until one completes) — call :meth:`synchronize`
        first for an exact up-to-date value.
        """
        if self.mode != "train":
            raise RuntimeError("train_step requires a train-mode session")
        wait0 = self.swapper.stats.wait_seconds
        hits0 = self.swapper.stats.prefetch_hits
        o0 = self._ostats.snapshot()
        grad_scale = self.scaler.scale   # the flat-buffer grads carry this
        state = self.execute(self.plan("train"),
                             _ExecState(tokens, labels, grad_scale))
        if self._optim_worker is not None and state.apply:
            self._optim_worker.submit(self._snapshot_optim_io)

        ssd_wait = self.swapper.stats.wait_seconds - wait0
        h2d_wait = self._ostats.h2d_wait_seconds - o0["h2d_wait_seconds"]
        if self._optim_worker is not None:
            with self._optim_lock:
                optim_io = self._optim_io_completed
        else:
            optim_io = self.optimizer.last_io_bytes
        self.metrics = {
            "loss": float(state.loss),
            "overflowed": state.overflowed,
            "applied": state.apply,
            "loss_scale": self.scaler.scale,
            "optimizer_io_bytes": optim_io,
            "peak_host_bytes": self.tracker.peak_allocated,
            # compute-thread stall obtaining device weights at FetchOps —
            # read wait + H2D inline (sync) or staged-future wait (overlap
            # modes).  Comparable across overlap levels by construction.
            "fetch_wait_s": self._ostats.fetch_seconds - o0["fetch_seconds"],
            "ssd_wait_s": ssd_wait,    # raw read waits, whichever thread
            "h2d_wait_s": h2d_wait,    # staged-future share of fetch_wait_s
            "prefetch_hits": (self.swapper.stats.prefetch_hits - hits0
                              + self._ostats.h2d_hits - o0["h2d_hits"]),
            "gradwrite_drain_s": (self._ostats.gradwrite_drain_seconds
                                  - o0["gradwrite_drain_seconds"]),
            "optim_gate_s": (self._ostats.optim_gate_seconds
                             - o0["optim_gate_seconds"]),
        }
        o1 = self._ostats.snapshot()
        # worker-side counters: the Adam stage of step k accrues these
        # while step k+1's window runs, so (like optim_gate_s) they are
        # attributed to the train_step whose wall-clock window they land
        # in.  The spanned busy counters (optim_prefetch_wait_s, adam_*_s,
        # grad_d2h_s, h2d_copy_s; docs/METRICS.md) are reported the same way.
        for name in SPANS:
            self.metrics[name.removesuffix("_seconds") + "_s"] = (
                o1[name] - o0[name])
        # the update's entry counts, attributed like adam_update_s
        for name in ("adam_update_elems", "adam_parallel_elems"):
            self.metrics[name] = o1[name] - o0[name]
        self.metrics["overflow_screen_s"] = (
            o1["overflow_screen_seconds"] - o0["overflow_screen_seconds"])
        # activation streaming: executor stall on checkpoint saves (gating
        # on a still-pending writer-thread save, or the inline D2H + store
        # write) and on staged checkpoint fetches at block_bwd gates
        self.metrics["act_save_wait_s"] = (
            o1["act_save_wait_seconds"] - o0["act_save_wait_seconds"])
        self.metrics["act_fetch_wait_s"] = (
            o1["act_fetch_wait_seconds"] - o0["act_fetch_wait_seconds"])
        self.metrics["act_write_failures"] = (
            o1["act_write_failures"] - o0["act_write_failures"])
        # expert paging: executor stall at ExpertFetchOp gates (staged-
        # stack waits, miss restages, and on-demand fetches)
        self.metrics["expert_fetch_wait_s"] = (
            o1["expert_fetch_wait_seconds"]
            - o0["expert_fetch_wait_seconds"])
        return self.metrics

    def eval_loss(self, tokens: np.ndarray, labels: np.ndarray) -> float:
        state = self.execute(self.plan("eval"), _ExecState(tokens, labels))
        return float(state.loss)

    def decode_logits(self, tokens: np.ndarray) -> np.ndarray:
        """One weight-streamed decode step: logits for every position.

        Uncached (full-prefix) path — O(T²) over a generation; kept as the
        ablation baseline and for models without cached-decode applies.
        """
        state = self.execute(self.plan("decode"), _ExecState(tokens))
        return np.asarray(state.logits)

    # -- cached decode (spill-able KV) ---------------------------------------

    def open_kv_cache(self) -> SpillableKVCache:
        """A fresh paged spill-able KV cache drawing from this session's
        pool.

        One at a time: the census reserves exactly the spec's page-slot
        budget, so a second open cache would deadlock on slot
        backpressure.  Close it (``finally:``) to return the slots.
        """
        if self.decode_spec is None:
            raise RuntimeError(
                "session was built without decode=DecodeSpec(...); cached "
                "decode needs its KV page slots sized into the pool census")
        if self._kv_cache is not None and not self._kv_cache.closed:
            raise RuntimeError("a KV cache is already open on this session; "
                               "close it first (its pool slots are shared)")
        self._kv_cache = SpillableKVCache(
            list(self._kv_units), self._kv_page_shape,
            self.decode_spec.max_seq,
            self.policy.adam.compute_np_dtype, self.pool, self.store,
            resident_limit=self._kv_resident,
            slots=self.decode_spec.batch)
        return self._kv_cache

    def _decode_state(self, kv: SpillableKVCache) -> DecodeSpec:
        if self.decode_spec is None:
            raise RuntimeError("session has no decode spec")
        if kv.closed:
            raise RuntimeError("KV cache is closed")
        return self.decode_spec

    def prefill(self, kv: SpillableKVCache, tokens: np.ndarray, *,
                slots: list[int] | None = None,
                lengths: list[int] | None = None) -> np.ndarray:
        """Prompt pass: cache every block's K/V, return the last valid
        position's logits as (batch, vocab).  Prompts are right-padded to
        the spec's time bucket so each prompt-length bucket compiles once.

        Joint path (``slots=None``): every lane carries the same prompt
        length and the whole cache must be empty.

        Joiner path (continuous batching): ``slots`` names the batch slots
        being prefilled — freshly :meth:`~SpillableKVCache.join`\\ ed, empty
        — and ``lengths`` their true per-request prompt lengths (``tokens``
        rows are right-padded to the longest).  Only those slots' pages are
        written (prefill-scatter); the other lanes' rows are computed and
        discarded, so mid-flight requests are untouched and the jitted
        shapes stay fixed.  Callers should group joiners by prompt
        *bucket*: a joiner then runs the exact trace a solo prefill of that
        request would, which is what makes continuously-batched greedy
        output bit-identical to decoding each request alone.
        """
        spec = self._decode_state(kv)
        tokens = np.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != spec.batch:
            raise ValueError(f"prompts must be (batch={spec.batch}, time), "
                             f"got {tokens.shape}")
        t0 = tokens.shape[1]
        if slots is None:
            if kv.length != 0:
                raise RuntimeError("prefill on a non-empty KV cache; open a "
                                   "fresh one per generation")
            last = jnp.asarray(t0 - 1, jnp.int32)
        else:
            if lengths is None or len(lengths) != len(slots):
                raise ValueError("joiner prefill needs lengths, one per slot")
            for s, n in zip(slots, lengths, strict=True):
                if s not in kv.active or kv.slot_length(s) != 0:
                    raise RuntimeError(
                        f"slot {s} is not a freshly joined empty slot")
                if not 1 <= n <= t0:
                    raise ValueError(f"prompt length {n} outside [1, {t0}]")
            # per-row last valid position; non-joiner rows read position 0
            # (their logits rows are discarded by the caller)
            pos = np.zeros(spec.batch, np.int32)
            for s, n in zip(slots, lengths, strict=True):
                pos[s] = n - 1
            last = jnp.asarray(pos)
        s_bucket = spec.bucket_len(t0)
        padded = np.zeros((spec.batch, s_bucket), np.int32)
        padded[:, :t0] = tokens
        state = _ExecState(padded)
        state.kv = kv
        state.kv_write_slots = slots
        state.last_pos = last
        state = self.execute(self.plan("prefill"), state)
        if slots is None:
            kv.set_length(t0)
        else:
            for s, n in zip(slots, lengths, strict=True):
                kv.set_slot_length(s, n)
        return np.asarray(state.logits)[:, 0]

    def decode_step(self, kv: SpillableKVCache,
                    tokens: np.ndarray) -> np.ndarray:
        """One cached decode step: append ``tokens`` (batch, 1) to the
        cache, return next-token logits as (batch, vocab).  Per-token cost
        is O(bucket) — independent of how many tokens were emitted — and
        every jitted stage retraces only on a bucket crossing.
        """
        spec = self._decode_state(kv)
        tokens = np.asarray(tokens)
        if tokens.shape != (spec.batch, 1):
            raise ValueError(f"step tokens must be (batch={spec.batch}, 1), "
                             f"got {tokens.shape}")
        if kv.length < 1:
            raise RuntimeError("decode_step before prefill")
        if kv.length + 1 > spec.max_seq:
            raise ValueError(f"KV cache full at max_seq={spec.max_seq}")
        state = _ExecState(tokens.astype(np.int32))
        state.kv = kv
        state.kv_time = spec.bucket_len(kv.length)
        state.cache_len = jnp.asarray(kv.length, jnp.int32)
        state = self.execute(self.plan("decode_cached"), state)
        kv.advance(1)
        return np.asarray(state.logits)[:, 0]

    def decode_step_slots(self, kv: SpillableKVCache,
                          tokens: np.ndarray) -> np.ndarray:
        """One cached decode step over per-slot lengths (continuous
        batching): every **active** slot's lane appends its token at that
        slot's own position; inactive lanes carry token 0 and are masked
        to self-attention only (``cache_len`` 0), their logits discarded.

        Same ``decode_cached`` plan and jitted stages as
        :meth:`decode_step` — ``cache_len`` is a traced (B,) vector, so
        join/retire churn costs no retrace; the device extent is the time
        bucket covering the *longest* active slot.  Masked-extent
        invariance of the attention step (tested) keeps each lane's output
        bit-identical to a solo decode of that request.
        """
        spec = self._decode_state(kv)
        tokens = np.asarray(tokens)
        if tokens.shape != (spec.batch, 1):
            raise ValueError(f"step tokens must be (batch={spec.batch}, 1), "
                             f"got {tokens.shape}")
        active = sorted(kv.active)
        if not active:
            raise RuntimeError("decode_step_slots with no active slots")
        for s in active:
            if kv.slot_length(s) < 1:
                raise RuntimeError(f"decode step before slot {s}'s prefill")
            if kv.slot_length(s) + 1 > spec.max_seq:
                raise ValueError(f"slot {s} full at max_seq={spec.max_seq}")
        state = _ExecState(tokens.astype(np.int32))
        state.kv = kv
        state.kv_time = spec.bucket_len(
            max(kv.slot_length(s) for s in active))
        lens = np.zeros(spec.batch, np.int32)
        for s in active:
            lens[s] = kv.slot_length(s)
        state.cache_len = jnp.asarray(lens)
        state = self.execute(self.plan("decode_cached"), state)
        kv.advance(1)
        return np.asarray(state.logits)[:, 0]

    def verify_step(self, kv: SpillableKVCache,
                    tokens: np.ndarray) -> np.ndarray:
        """Speculative-decode verify: step a ``(batch, n)`` draft window in
        ONE streamed pass over the weights and return all ``n`` positions'
        next-token logits as ``(batch, n, vocab)``.  Position ``j``'s row
        is bitwise what :meth:`decode_step` would have produced after the
        first ``j`` draft tokens were appended — the host compares each
        draft token against the previous position's argmax, commits the
        accepted prefix and rolls the cache back over the rejected tail
        (:meth:`~SpillableKVCache.rollback`).  The window is padded to
        :func:`verify_bucket` so warm traces stay bounded; slot lengths do
        NOT advance here (rollback's length-set is the commit).
        """
        spec = self._decode_state(kv)
        tokens = np.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != spec.batch:
            raise ValueError(f"verify window must be (batch={spec.batch}, "
                             f"n), got {tokens.shape}")
        n = tokens.shape[1]
        k_pad = verify_bucket(n)
        if kv.length < 1:
            raise RuntimeError("verify_step before prefill")
        if kv.length + k_pad > spec.max_seq:
            raise ValueError(
                f"KV cache full: length {kv.length} + padded window "
                f"{k_pad} exceeds max_seq={spec.max_seq}")
        padded = np.zeros((spec.batch, k_pad), np.int32)
        padded[:, :n] = tokens
        state = _ExecState(padded)
        state.kv = kv
        state.kv_time = spec.bucket_len(kv.length + k_pad)
        state.cache_len = jnp.asarray(kv.length, jnp.int32)
        state = self.execute(self.plan("decode_verify"), state)
        return np.asarray(state.logits)[:, :n]

    def verify_step_slots(self, kv: SpillableKVCache,
                          tokens: np.ndarray) -> np.ndarray:
        """:meth:`verify_step` over per-slot lengths (continuous
        batching): each **active** slot's lane steps its own draft window
        at that slot's position; inactive lanes carry token 0, masked to
        self-attention only, logits discarded.  Slots accept and roll
        back independently — one rejected lane costs the others nothing
        but the shared pass.  Extent is the time bucket covering the
        longest active slot plus the padded window."""
        spec = self._decode_state(kv)
        tokens = np.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[0] != spec.batch:
            raise ValueError(f"verify window must be (batch={spec.batch}, "
                             f"n), got {tokens.shape}")
        n = tokens.shape[1]
        k_pad = verify_bucket(n)
        active = sorted(kv.active)
        if not active:
            raise RuntimeError("verify_step_slots with no active slots")
        for s in active:
            if kv.slot_length(s) < 1:
                raise RuntimeError(f"verify step before slot {s}'s prefill")
            if kv.slot_length(s) + k_pad > spec.max_seq:
                raise ValueError(
                    f"KV cache full: slot {s} length {kv.slot_length(s)} + "
                    f"padded window {k_pad} exceeds max_seq={spec.max_seq}")
        padded = np.zeros((spec.batch, k_pad), np.int32)
        padded[:, :n] = tokens
        state = _ExecState(padded)
        state.kv = kv
        state.kv_time = spec.bucket_len(
            max(kv.slot_length(s) for s in active) + k_pad)
        lens = np.zeros(spec.batch, np.int32)
        for s in active:
            lens[s] = kv.slot_length(s)
        state.cache_len = jnp.asarray(lens)
        state = self.execute(self.plan("decode_verify"), state)
        return np.asarray(state.logits)[:, :n]

    def expert_cache_stats(self) -> dict:
        """Expert page cache spill/refill counters (see
        :class:`~repro.core.paged.PageStats`); empty when expert paging
        is off."""
        return ({} if self._expert_cache is None
                else self._expert_cache.stats.snapshot())

    def overlap_snapshot(self) -> dict:
        """Point-in-time copy of the overlap-pipeline stall counters
        (:class:`~repro.core.overlap.OverlapStats`), including the staged-
        KV numbers serving cares about: ``kv_stage_gets`` / ``_hits`` (was
        the window already on device when the KVReadOp asked?) and
        ``kv_stage_wait_seconds`` (executor stall when it was not).  See
        docs/METRICS.md for the full glossary."""
        return self._ostats.snapshot()

    def decode_compiles(self) -> int:
        """Total jit traces across the decode stages — the bench/test probe
        for "zero retraces after the first token per bucket".  Counts via
        :func:`jit_cache_size`, the repo's single guarded touch point for
        jax's private trace-count probe."""
        fns = (self._jit_embed, self._jit_head_logits, self._jit_head_last,
               self._jit_block_prefill, self._jit_block_step,
               self._jit_block_verify, self._jit_prefill_route,
               self._jit_step_route, self._jit_verify_route,
               self._jit_block_moe)
        return sum(jit_cache_size(f) for f in fns if f is not None)

    # -- weights access ------------------------------------------------------

    def master_param(self, unit_name: str, key: str) -> np.ndarray:
        if self.mode != "train":
            raise RuntimeError("serve-mode sessions hold no master weights")
        self.synchronize()    # an in-flight Adam stage may still be writing
        _unit, meta = self._units[unit_name]
        shape, _ = meta[key]
        sd = self.policy.adam.state_np_dtype
        return self.store.read_new(f"{unit_name}/{key}.master", sd, shape)
