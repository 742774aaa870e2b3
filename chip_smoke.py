"""Smoke run of the SSD-offloaded fine-tuning and serving path on one TPU chip.

Drives the main path once through the entry points a user calls, at
Qwen2.5-0.5B's published size (24 layers, every width as published, random
weights made from a fixed seed):

* train: :func:`repro.launch.train.run_offloaded`, the launcher's own
  offloaded loop — policy ``memascend``, overlap ``full``, batch 4 x 1024
  synthetic tokens, 3 steps;
* serve: ``OffloadedDecoder(decode=DecodeSpec(batch=4, max_seq=256,
  bucket=64))`` behind a ``ServingEngine``: 4 requests of 64-128 prompt
  tokens and 32 new tokens each, served once to compile and once more to
  time, after a check that cached-decode logits match the uncached
  full-prefix pass.

The run fails (non-zero exit, traceback, no result line) when a check
fails: a step's loss is not finite, the first step's loss is more than
``LOSS_BAND_NATS`` from ln(vocab), an activation write to the store failed,
cached and uncached logits differ by more than ``LOGIT_TOL``, a request
comes back short, the two serving runs disagree, or the jitted blocks'
outputs do not live on the default device.  It exits non-zero before any
work where JAX's default backend is not a TPU.

The readings it prints (wall, compile and steady time, tokens/s, fetch and
optimizer-gate waits, peak host and device bytes, the store's filesystem)
come from one smoke run and are not benchmark numbers.  The last line of
its output is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.

Run on a TPU host from the root of the checkout::

    python3 chip_smoke.py [--store-root DIR]

The store goes to ``--store-root`` (default ``.smoke_store/`` in the
checkout, listed in ``.gitignore``); what the run writes there is removed
at exit.  Compiles go to the persistent cache of
:mod:`repro.launch.compile_cache`.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import OffloadPolicy  # noqa: E402
from repro.core.model_adapter import make_offloadable_lm  # noqa: E402
from repro.core.nvme import filesystem_info  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.train import run_offloaded  # noqa: E402
from repro.serve import (DecodeSpec, OffloadedDecoder, Request,  # noqa: E402
                         ServingEngine)

ARCH = "qwen2.5-0.5b"
SEED = 0            # of the serve phase's weights and prompts
DEFAULT_STORE = REPO / ".smoke_store"

# Step-1 loss band around ln(vocab), the loss of a uniform prediction.  With
# random init the logits are small: the tied embedding (std 0.02) against a
# unit-RMS hidden state gives logits of std about 0.02 * sqrt(d_model), 0.6
# at d_model 896, which lifts the expected cross entropy over ln(vocab) by
# about half their variance, under 0.2 nat.  One nat leaves room for that
# and still catches a wrong head, a shifted label or a loss off by a factor.
LOSS_BAND_NATS = 1.0

# Cached vs uncached logits, in units of each row's max |logit| (floored at
# 1).  Both paths run the same math through different matmul shapes, so the
# reduction order differs and bf16 rounding compounds across the layers;
# the repo's own audit (benchmarks/bench_decode.py) bounds the difference at
# about 8 bf16 ULPs of the row max, 2**-8 being one ULP at unit scale.  A
# stale or misplaced K/V page moves logits on the scale of the row max.
LOGIT_TOL = 8.0 * 2.0**-8


class SmokeFailure(AssertionError):
    """A correctness check of the smoke run failed."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class CompileCounter:
    """XLA compiles and persistent-cache reads while open, counted through
    ``jax.monitoring``.  ``compile_s`` sums the backend compile events,
    which include the time to read an entry from the cache."""

    def __enter__(self) -> "CompileCounter":
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def reading(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def check_devices(devices, what: str) -> None:
    """The jitted blocks' output must live on the default device's
    platform (the TPU, once ``main`` has passed its backend gate)."""
    platform = jax.devices()[0].platform
    check(bool(devices), f"{what}: no jitted output was recorded")
    found = sorted({d.platform for d in devices})
    check(found == [platform],
          f"{what}: jitted output lives on {found}, expected [{platform!r}]")


def train_phase(cfg, store_root: Path, *, steps: int = 3, batch: int = 4,
                seq: int = 1024) -> dict:
    """Fine-tune ``cfg`` through the launcher's offloaded loop; check every
    step, return the readings."""
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        history = run_offloaded(cfg, policy="memascend",
                                store_root=str(store_root), steps=steps,
                                batch=batch, seq=seq, overlap="full")
        wall_s = time.perf_counter() - t0
    check(len(history) == steps,
          f"train: {len(history)} step records, expected {steps}")
    for i, m in enumerate(history, 1):
        check(math.isfinite(m["loss"]), f"train step {i}: loss {m['loss']}")
        check(m["act_write_failures"] == 0,
              f"train step {i}: {m['act_write_failures']} activation "
              f"writes to the store failed")
        check_devices(m["devices"], f"train step {i}")
    uniform = math.log(cfg.vocab)
    first = history[0]["loss"]
    check(abs(first - uniform) <= LOSS_BAND_NATS,
          f"train step 1: loss {first} is more than {LOSS_BAND_NATS} nat "
          f"from ln(vocab) = {uniform}")
    steady = history[1:] or history
    steady_s = sum(m["step_s"] for m in steady) / len(steady)
    return {
        **cc.reading(),
        "wall_s": wall_s,
        "first_step_s": history[0]["step_s"],
        "steady_step_s": steady_s,
        "drain_s": history[-1]["drain_s"],
        "tokens_per_s": batch * seq / steady_s,
        "losses": [m["loss"] for m in history],
        "fetch_wait_s": [m["fetch_wait_s"] for m in history],
        "optim_gate_s": [m["optim_gate_s"] for m in history],
        "peak_host_bytes": history[-1]["peak_host_bytes"],
    }


def cached_vs_uncached(dec: OffloadedDecoder, prompt: np.ndarray) -> float:
    """Largest row-scaled difference between the cached decode step's
    logits at the first generated position and the uncached full-prefix
    pass that ``generate(use_cache=False)`` runs, for one request (its
    prompt fills every lane of the batch; lane 0 is compared)."""
    session = dec.session
    tokens = np.tile(prompt, (dec.decode_spec.batch, 1))
    kv = session.open_kv_cache()
    try:
        first = session.prefill(kv, tokens)
        nxt = np.argmax(first, axis=-1).astype(np.int32)[:, None]
        cached = np.asarray(session.decode_step(kv, nxt), np.float32)[0]
    finally:
        kv.close()
    ref = np.asarray(dec.step_logits(np.concatenate([tokens, nxt], axis=1)),
                     np.float32)[0]
    scale = max(float(np.abs(ref).max()), 1.0)
    return float(np.abs(cached - ref).max()) / scale


def serve_requests(dec: OffloadedDecoder, prompts, new_tokens: int,
                   vocab: int):
    """One ServingEngine run over ``prompts``; checks every request came
    back whole and returns (report, outputs)."""
    requests = [Request(rid=f"r{i}", prompt=p, max_new_tokens=new_tokens)
                for i, p in enumerate(prompts)]
    report = ServingEngine(dec).run(requests)
    check(len(report.completed) == len(requests),
          f"serve: {len(report.completed)}/{len(requests)} requests "
          f"completed")
    outputs = []
    for r in report.requests:
        check(r.metrics.tokens_out == new_tokens and
              len(r.output) == new_tokens,
              f"serve: {r.rid} emitted {len(r.output)} tokens, expected "
              f"{new_tokens}")
        check(all(0 <= t < vocab for t in r.output),
              f"serve: {r.rid} emitted a token outside the vocabulary")
        outputs.append(list(r.output))
    return report, outputs


def serve_phase(cfg, store_root: Path, *, n_requests: int = 4,
                prompt_tokens: tuple[int, int] = (64, 128),
                new_tokens: int = 32, batch: int = 4, max_seq: int = 256,
                bucket: int = 64) -> dict:
    """Serve ``cfg`` through OffloadedDecoder + ServingEngine; check the
    cached path against the uncached one and every request's output,
    return the readings."""
    rng = np.random.default_rng(SEED)
    lo, hi = prompt_tokens
    prompts = [rng.integers(3, cfg.vocab, size=int(rng.integers(lo, hi + 1)),
                            dtype=np.int32) for _ in range(n_requests)]
    model = make_offloadable_lm(cfg, jax.random.PRNGKey(SEED))
    policy = OffloadPolicy.preset("memascend").with_store(
        str(store_root)).build()
    spec = DecodeSpec(batch=batch, max_seq=max_seq, bucket=bucket)
    with CompileCounter() as cc, \
            OffloadedDecoder(model, policy, decode=spec) as dec:
        t0 = time.perf_counter()
        logit_diff = cached_vs_uncached(dec, prompts[0])
        check(logit_diff <= LOGIT_TOL,
              f"serve: cached logits differ from the uncached pass by "
              f"{logit_diff} of the row max, more than {LOGIT_TOL}")
        _warm, warm_out = serve_requests(dec, prompts, new_tokens, cfg.vocab)
        t1 = time.perf_counter()
        report, outputs = serve_requests(dec, prompts, new_tokens, cfg.vocab)
        t2 = time.perf_counter()
        check(outputs == warm_out,
              "serve: the timed run's tokens differ from the warm run's")
        check_devices(dec.session.output_devices, "serve")
        failures = dec.session.overlap_snapshot()["act_write_failures"]
        check(failures == 0,
              f"serve: {failures} activation writes to the store failed")
        peak_host = dec.session.tracker.peak_allocated
        fetch = dec.fetch_stats
    return {
        **cc.reading(),
        "wall_s": time.perf_counter() - t0,
        "warm_run_s": t1 - t0,
        "timed_run_s": t2 - t1,
        "tokens_per_s": report.tokens_per_s,
        "ttft_p50_s": report.ttft_percentile(50),
        "decode_steps": report.decode_steps,
        "logit_diff": logit_diff,
        "fetch_wait_s": fetch["wait_seconds"],
        "peak_host_bytes": peak_host,
    }


def _gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def _device_peak(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else _gib(peak)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the offloaded train and serve path on "
                    "one TPU chip (see the module docstring).")
    ap.add_argument("--store-root", type=Path, default=DEFAULT_STORE,
                    help="directory for the SSD store (default: "
                         ".smoke_store/ in the checkout); what the run "
                         "writes there is removed at exit")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not "
              f"'tpu'; this smoke run needs the chip", file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    cfg = get_config(ARCH)
    print(f"device {dev.platform} {dev.device_kind} "
          f"(count {len(jax.devices())})")
    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}")
    print(f"compile cache {cache_dir}")
    print("readings below are from one smoke run, not benchmark numbers")

    root = args.store_root
    remove_root = root == DEFAULT_STORE or not root.exists()
    phases = [root / "train", root / "serve"]
    try:
        root.mkdir(parents=True, exist_ok=True)
        fs = filesystem_info(str(root))
        print(f"store root {fs['path']}: {fs['fstype']} mounted at "
              f"{fs['mount']}, {_gib(fs['free_bytes'])} free")

        tr = train_phase(cfg, phases[0])
        shutil.rmtree(phases[0])      # free the disk before serving
        print(f"train: wall {tr['wall_s']:.3f}s, compile {tr['compile_s']:.3f}s "
              f"over {tr['compiles']} programs (cache hits "
              f"{tr['cache_hits']}, misses {tr['cache_misses']}), step 1 "
              f"{tr['first_step_s']:.3f}s, steady step "
              f"{tr['steady_step_s']:.3f}s, final drain {tr['drain_s']:.3f}s")
        print(f"train: {tr['tokens_per_s']:.1f} tokens/s steady; losses "
              f"{[round(x, 4) for x in tr['losses']]}; fetch_wait_s "
              f"{[round(x, 4) for x in tr['fetch_wait_s']]}; optim_gate_s "
              f"{[round(x, 4) for x in tr['optim_gate_s']]}")
        print(f"train: peak host {_gib(tr['peak_host_bytes'])} (tracker "
              f"peak_allocated), device peak {_device_peak(dev)}")

        sv = serve_phase(cfg, phases[1])
        print(f"serve: wall {sv['wall_s']:.3f}s, compile "
              f"{sv['compile_s']:.3f}s over {sv['compiles']} programs (cache "
              f"hits {sv['cache_hits']}, misses {sv['cache_misses']}), warm "
              f"run {sv['warm_run_s']:.3f}s, timed run "
              f"{sv['timed_run_s']:.3f}s")
        print(f"serve: {sv['tokens_per_s']:.1f} tokens/s over "
              f"{sv['decode_steps']} decode steps, ttft p50 "
              f"{sv['ttft_p50_s']:.3f}s, fetch_wait_s "
              f"{sv['fetch_wait_s']:.4f}, cached-vs-uncached logit diff "
              f"{sv['logit_diff']:.3e} of row max (tol {LOGIT_TOL:.3e})")
        print(f"serve: peak host {_gib(sv['peak_host_bytes'])} (tracker "
              f"peak_allocated), device peak so far {_device_peak(dev)}")
    finally:
        for p in phases:
            shutil.rmtree(p, ignore_errors=True)
        if remove_root:
            shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
