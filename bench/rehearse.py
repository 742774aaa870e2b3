"""Compile the cells' device programs for a described TPU v5e, with no
chip attached, and print each one's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

It compiles, at the cells' sizes, what the chip's compiler would refuse
before any chip time is spent on it:

* ``qwen2.5-0.5b`` fine-tuning at 4 x 2048 tokens: the head's loss and
  gradient program and one block's backward, as the session jits them
  (bfloat16 weights), and the reference's training step and Adam update;
* ``qwen3-4b-l4`` serving at batch 8, ``max_seq`` 1024: one block's
  prefill at the largest bucket, one decode step at the full extent, the
  last-position logits, and the reference's logits.

The program's applies come from ``make_offloadable_lm`` over a one-layer,
256-row copy of each configuration (the applies read no size from it), and
every argument is a shape, so nothing of full size is made here.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness, reference  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _applies(config: dict):
    from repro.core.model_adapter import make_offloadable_lm
    cfg = harness.model_config(config)
    small = dataclasses.replace(cfg, n_layers=1, vocab=256)
    model = make_offloadable_lm(small, jax.random.PRNGKey(0))
    block = {k: v.shape for k, v in model.units[1].params.items()}
    return cfg, model, block


def _report(name: str, fn, *args, **static) -> None:
    compiled = jax.jit(fn, **static).lower(*args).compile()
    m = compiled.memory_analysis()
    print(f"{name}: arguments {m.argument_size_in_bytes} B, outputs "
          f"{m.output_size_in_bytes} B, temporaries {m.temp_size_in_bytes} "
          f"B, aliased {m.alias_size_in_bytes} B", flush=True)


def main() -> None:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def tree(shapes, dtype):
        return {k: sds(s, dtype) for k, s in shapes.items()}

    # fine-tuning: qwen2.5-0.5b at 4 x 2048
    config = harness.load_json(ROOT / "bench/configs/qwen2.5-0.5b.json")
    cfg, model, block = _applies(config)
    b, s, d, v = 4, 2048, cfg.d_model, cfg.vocab
    head = {"final_norm": sds((d,), BF16), "head": sds((d, v), BF16)}

    def head_loss_and_grads(params, h, labels, scale):
        def scaled(params, h):
            return model.head_loss(params, h, labels) * scale
        sloss, vjp = jax.vjp(scaled, params, h)
        dparams, dh = vjp(jnp.ones((), sloss.dtype))
        return sloss / scale, dparams, dh

    def block_bwd(params, x, dy):
        _, vjp = jax.vjp(model.block_apply, params, x)
        return vjp(dy)

    _report("finetune head loss+grad", head_loss_and_grads, head,
            sds((b, s, d), BF16), sds((b, s), I32), sds((), F32))
    _report("finetune block backward", block_bwd, tree(block, BF16),
            sds((b, s, d), BF16), sds((b, s, d), BF16))
    sz = reference.Sizes(config)
    params = jax.eval_shape(lambda: reference._init(sz, 0))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    _report("reference training step", reference._loss_and_grads.__wrapped__,
            sz, "fp32", 512, params, sds((b, s), I32), sds((b, s), I32),
            static_argnums=(0, 1, 2))
    hyper = tuple(sds((), F32) for _ in range(4))
    _report("reference Adam update", reference._adam.__wrapped__, params,
            params, params, params, sds((), F32), hyper,
            donate_argnums=(0, 1, 2))

    # serving: qwen3-4b-l4 at batch 8, max_seq 1024
    config = harness.load_json(ROOT / "bench/configs/qwen3-4b-l4.json")
    cfg, model, block = _applies(config)
    bs, t, d, v = 8, 1024, cfg.d_model, cfg.vocab
    kv = (bs, t, cfg.n_kv_heads, cfg.head_dim)
    _report("serve block prefill", model.block_prefill, tree(block, BF16),
            sds((bs, t, d), BF16))
    _report("serve block decode step", model.block_step, tree(block, BF16),
            sds((bs, 1, d), BF16), sds(kv, BF16), sds(kv, BF16),
            sds((bs,), I32), static_argnames=("chunk",))
    _report("serve head logits", model.head_logits,
            {"final_norm": sds((d,), BF16), "head": sds((d, v), BF16)},
            sds((bs, 1, d), BF16))
    sz = reference.Sizes(config)
    params = jax.eval_shape(lambda: reference._init(sz, 0))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    _report("reference served logits", reference._logits_at.__wrapped__,
            sz, "fp32", params, sds((bs, t), I32), sds((bs, 16), I32),
            static_argnums=(0, 1))


if __name__ == "__main__":
    main()
