"""Distributed training launcher (pjit path + SSD-offloaded path).

The default path drives the (data, model) mesh via the jitted train_step;
on one device that is the 1x1 host mesh.  ``--offload POLICY`` instead runs
the arch through the SSD-offloaded OffloadSession (StreamPlan schedules,
lookahead prefetch, host Adam on NVMe-resident state), with the policy
selected by registry name and the store under ``--store-root``.

The arch runs at its published config; ``--reduced`` cuts it to the
2-layer, d_model-128 smoke shape for a CPU run.  ``main()`` turns on the
persistent compilation cache (:mod:`repro.launch.compile_cache`).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-0.5b --steps 3 \
      [--reduced] [--batch 4] [--seq 128] [--offload memascend] \
      [--store-root DIR]
"""

from __future__ import annotations

import argparse
import contextlib
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.loss_scale import DynamicLossScaler
from repro.core.nvme import filesystem_info
from repro.core.offload_engine import OffloadPolicy
from repro.core.session import OffloadSession
from repro.data import DataLoader, SyntheticTextDataset
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build
from repro.train.step import build_train_step


def run_offloaded(cfg, *, policy: str, store_root: str, steps: int,
                  batch: int, seq: int, lr: float = 1e-3,
                  overlap: str = "full") -> list[dict]:
    """The SSD-offloaded path: registry policy + OffloadSession.

    Trains ``cfg`` (random weights from seed 0) for ``steps`` steps on
    synthetic tokens with its store under ``store_root``, and returns each
    step's :meth:`OffloadSession.train_step` metrics plus ``step_s`` (the
    step's wall time) and ``devices`` (where its loss was computed).  The
    last record also carries ``drain_s``, the wait for the last step's
    optimizer stage, which full overlap leaves running past the step."""
    from repro.core.model_adapter import make_offloadable_lm
    model = make_offloadable_lm(cfg, jax.random.PRNGKey(0))
    dl = DataLoader(SyntheticTextDataset(vocab=cfg.vocab, seed=0),
                    batch=batch, seq_len=seq)
    built = (OffloadPolicy.preset(policy).with_store(store_root)
             .with_adam(lr=lr).with_overlap(overlap).build())
    history: list[dict] = []
    with OffloadSession(model, built) as sess:
        print(f"offload policy {built.name}: "
              f"{sess.total_params / 1e6:.1f}M params, "
              f"lookahead {sess.lookahead}, overlap {built.overlap}")
        t_start = time.perf_counter()
        for i in range(1, steps + 1):
            hb = dl.next_batch()
            t0 = time.perf_counter()
            m = dict(sess.train_step(hb["tokens"], hb["labels"]))
            m["step_s"] = time.perf_counter() - t0
            m["devices"] = sess.output_devices
            history.append(m)
            if i % 5 == 0 or i == 1 or i == steps:
                tput = i * batch * seq / (time.perf_counter() - t_start)
                print(f"step {i:4d} loss {m['loss']:.4f} "
                      f"step {m['step_s']:.2f}s "
                      f"fetch-wait {m['fetch_wait_s'] * 1e3:.0f}ms "
                      f"optim-gate {m['optim_gate_s'] * 1e3:.0f}ms "
                      f"optim-prefetch-wait "
                      f"{m['optim_prefetch_wait_s'] * 1e3:.0f}ms "
                      f"overflow-screen "
                      f"{m['overflow_screen_s'] * 1e3:.1f}ms "
                      f"{tput:.0f} tok/s")
        t0 = time.perf_counter()
        sess.synchronize()   # close the timing window on the last Adam
        if history:
            history[-1]["drain_s"] = time.perf_counter() - t0
    print("offloaded train loop done")
    return history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="cut the arch to its 2-layer, d_model-128 smoke "
                         "shape (default: the published config)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--offload", default=None,
                    choices=OffloadPolicy.names(),
                    help="run SSD-offloaded via this registry policy "
                         "instead of the pjit path")
    ap.add_argument("--overlap", default="full",
                    choices=["sync", "h2d", "full"],
                    help="offload pipeline overlap level (the Fig. 6 "
                         "ablation): sync H2D/gradwrite/optimizer, "
                         "async H2D only, or the full pipeline")
    ap.add_argument("--store-root", default=None,
                    help="directory for the offloaded path's SSD store "
                         "(default: a fresh temporary directory, removed "
                         "at exit)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}")
    if args.offload:
        with contextlib.ExitStack() as stack:
            root = args.store_root or stack.enter_context(
                tempfile.TemporaryDirectory(prefix="launch_offload_"))
            os.makedirs(root, exist_ok=True)
            fs = filesystem_info(root)
            print(f"store root {fs['path']} ({fs['fstype']} at "
                  f"{fs['mount']}, {fs['free_bytes'] / 2**30:.1f} GiB free)")
            run_offloaded(cfg, policy=args.offload, store_root=root,
                          steps=args.steps, batch=args.batch, seq=args.seq,
                          lr=args.lr, overlap=args.overlap)
        return
    impl = build(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())

    b, s = args.batch, args.seq
    batch_sds = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    extra = {}
    if cfg.family == "audio":
        batch_sds["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        extra["frames"] = jnp.ones((b, cfg.encoder_seq, cfg.d_model),
                                   jnp.bfloat16)
    if cfg.prefix_len:
        batch_sds["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.prefix_len, cfg.d_model), jnp.bfloat16)
        extra["image_embeds"] = jnp.ones((b, cfg.prefix_len, cfg.d_model),
                                         jnp.bfloat16)

    with mesh:
        fn, in_sh, out_sh = build_train_step(impl, mesh,
                                             batch_shape=batch_sds)
        step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        params = impl.init_params(jax.random.PRNGKey(0))
        scaler = DynamicLossScaler(scale=1.0)   # bf16 compute
        # simple on-device SGD-on-grads demo loop (the offloaded-Adam path
        # lives in examples/finetune_offloaded.py)
        dl = DataLoader(SyntheticTextDataset(vocab=cfg.vocab, seed=0),
                        batch=b, seq_len=s)
        lr = args.lr
        t0 = time.time()
        for i in range(1, args.steps + 1):
            hb = dl.next_batch()
            batch = {"tokens": jnp.asarray(hb["tokens"]),
                     "labels": jnp.asarray(hb["labels"]), **extra}
            loss, grads, overflow = step(params, batch,
                                         jnp.float32(scaler.scale))
            if scaler.update(bool(overflow)):
                inv = 1.0 / scaler.scale
                params = jax.tree.map(
                    lambda p, g: (p - lr * inv * g.astype(p.dtype)).astype(
                        p.dtype), params, grads)
            if i % 5 == 0 or i == 1:
                tput = i * b * s / (time.time() - t0)
                print(f"step {i:4d} loss {float(loss):.4f} "
                      f"overflow={bool(overflow)} {tput:.0f} tok/s")
    print("train loop done")


if __name__ == "__main__":
    main()
