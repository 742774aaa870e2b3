"""Driver ``finetune``: SSD-offloaded fine-tuning through
``OffloadSession.train_step``, the call the launcher's ``run_offloaded``
makes.

Set-up builds one session over the seed's weights and drives it through
two warm-up steps with the window's own call and feed: the first compiles
every program of the step and is drained, so that the optimizer's state
after one step can be read (the norm of each leaf's first gradient is its
first moment over ``1 - beta1``); the second leaves its host Adam running,
as every step does for the next.  The window opens at the start of step 3,
whose forward waits on step 2's Adam, and runs whole steps until one ends
at or after ``--seconds``.

After the window: the optimizer is drained, each leaf's change is read
from the store against the initial weights, the session is closed, and
the reference trains from the same seed on the same batches.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench import compare, flops, reference, traffic_gen
from bench.harness import sq_norm
from repro.core import OffloadPolicy
from repro.core.model_adapter import make_offloadable_lm
from repro.core.session import OffloadSession

WARMUP_STEPS = 2


def _leaves(model):
    for unit in model.units:
        for key, value in unit.params.items():
            yield unit.name, key, value


def first_gradient_norms(sess, model, adam) -> dict[str, float]:
    """Each leaf's first gradient as the optimizer got it, from its first
    moment after one step: ``m = (1 - beta1) * g``."""
    out = {}
    for unit, key, value in _leaves(model):
        m = sess.store.read_new(f"{unit}/{key}.m", adam.state_np_dtype,
                                value.shape)
        out[f"{unit}/{key}"] = math.sqrt(sq_norm(m)) / (1.0 - adam.beta1)
    return out


def update_norms(sess, model) -> dict[str, float]:
    """Each leaf's change from its initial weights, read back from the
    optimizer's master copy on the store."""
    out = {}
    for unit, key, value in _leaves(model):
        master = sess.master_param(unit, key)
        out[f"{unit}/{key}"] = math.sqrt(sq_norm(
            master.astype(np.float32) - value))
    return out


def run(ctx) -> dict:
    cfg, wl, mix = ctx.cfg, ctx.cell.workload, ctx.cell.traffic
    feed = traffic_gen.packed_batches(mix, ctx.rng("traffic"),
                                      vocab=cfg.vocab, eos=ctx.eos)
    fed: list = []
    ctx.log_rss("before the model")
    model = make_offloadable_lm(cfg, jax.random.PRNGKey(ctx.weight_seed))
    ctx.log_rss("after the model's weights")
    policy = (OffloadPolicy.preset(wl["policy"]).with_store(str(ctx.store))
              .with_adam(lr=wl["lr"]).with_overlap(wl["overlap"])
              .with_activations(wl["act_policy"]).build())
    adam = policy.adam
    steps: list[dict] = []
    with OffloadSession(model, policy) as sess:
        def step() -> None:
            tokens, labels = next(feed)
            fed.append((tokens, labels))
            io0 = sess.store.stats.snapshot()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.train_step"):
                m = dict(sess.train_step(tokens, labels))
            m["step_s"] = time.perf_counter() - t0
            io1 = sess.store.stats.snapshot()
            m["store_read_bytes"] = io1["bytes_read"] - io0["bytes_read"]
            m["store_written_bytes"] = (io1["bytes_written"]
                                        - io0["bytes_written"])
            m.pop("devices", None)
            steps.append(m)

        ctx.log_rss("with the session open")
        step()
        sess.synchronize()
        grad1 = first_gradient_norms(sess, model, adam)
        for _ in range(WARMUP_STEPS - 1):
            step()
        ctx.log_rss("after warm-up")
        ctx.window.open()
        while True:
            step()
            if ctx.window.elapsed() >= ctx.seconds:
                break
        ctx.window.close()
        ctx.read_device_peak()
        t0 = time.perf_counter()
        sess.synchronize()
        drain_s = time.perf_counter() - t0
        updates = update_norms(sess, model)
        tracker_peak = sess.tracker.peak_allocated
        output_devices = sorted({d.platform for d in sess.output_devices})
    del model, sess
    gc.collect()

    window = steps[WARMUP_STEPS:]
    tokens_per_step = mix["batch"] * mix["seq"]
    failed = sum(1 for m in window if not math.isfinite(m["loss"])
                 or m["act_write_failures"] or not m["applied"])
    for i, m in enumerate(steps, 1):
        where = "window" if i > WARMUP_STEPS else "warm-up"
        ctx.log(f"step {i} ({where}): {m['step_s']:.4f} s, loss "
                f"{m['loss']:.6f}, optim_gate_s {m['optim_gate_s']:.4f}, "
                f"fetch_wait_s {m['fetch_wait_s']:.4f}, store read "
                f"{m['store_read_bytes']} B, written "
                f"{m['store_written_bytes']} B")
    ctx.log(f"optimizer drain after the window {drain_s:.4f} s; jitted "
            f"outputs on {output_devices}")

    t0 = time.perf_counter()
    sizes = reference.Sizes(ctx.cell.config)
    ref = reference.train(
        sizes, ctx.weight_seed, fed,
        {"lr": adam.lr, "beta1": adam.beta1, "beta2": adam.beta2,
         "eps": adam.eps})
    prog = {"losses": [m["loss"] for m in steps], "grad1_norms": grad1,
            "update_norms": updates}
    checks = compare.train_checks(prog, ref, wl["limits"])
    counted = len(compare.counted_leaves(ref["grad1_norms"]))
    ctx.log(f"reference: {len(fed)} steps in {time.perf_counter() - t0:.3f}"
            f" s; losses program {prog['losses']} reference "
            f"{ref['losses']}; {counted} of {len(ref['grad1_norms'])} leaves "
            f"compared; worst leaves {checks['grad1_norm_gap']['leaf']}, "
            f"{checks['update_norm_gap']['leaf']}")

    window_s = ctx.window.seconds
    n_tokens = tokens_per_step * len(window)
    per_token = flops.train_flops_per_token(ctx.cell.config, mix["seq"])
    return {
        "attempted": len(window),
        "failed": failed,
        "checks": checks,
        "e2e": {"train_tokens_per_s": n_tokens / window_s},
        "window_steps": window,
        "window_s": window_s,
        "window_tokens": n_tokens,
        "store_read_bytes": sum(m["store_read_bytes"] for m in window),
        "store_written_bytes": sum(m["store_written_bytes"] for m in window),
        "tracker_peak_bytes": tracker_peak,
        "model_flops": per_token * n_tokens,
    }
