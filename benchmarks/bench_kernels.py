"""Kernel microbenchmarks (ours, beyond-paper): interpret-mode Pallas vs
pure-jnp oracle wall time is NOT meaningful on CPU; what we report instead
is correctness deltas + the jnp-oracle throughput as the reference the TPU
kernels are validated against."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import emit, time_us


def run() -> None:
    rng = np.random.default_rng(0)
    # fused overflow check, jnp formulation (the jitted on-device screen)
    g = jnp.asarray(rng.standard_normal(4 << 20), jnp.float32)
    from repro.core.overflow import (baseline_overflow_check_jnp,
                                     fused_overflow_check_jnp)
    f_fused = jax.jit(fused_overflow_check_jnp)
    f_base = jax.jit(baseline_overflow_check_jnp)
    us_f = time_us(lambda: jax.block_until_ready(f_fused(g)))
    us_b = time_us(lambda: jax.block_until_ready(f_base(g)))
    emit("kernel/overflow-jnp-4M", us_f,
         f"chained_us={us_b:.0f} fused_us={us_f:.0f} "
         f"speedup={us_b / us_f:.2f}x")

    # fused adam vs 4-op reference, jit'd oracle timing
    n = 1 << 20
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    gr = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n); v = jnp.zeros(n)
    f_ref = jax.jit(lambda *a: ref.ref_fused_adam(*a))
    us_ref = time_us(lambda: jax.block_until_ready(f_ref(p, gr, m, v, 1)))
    out_k = ops.fused_adam(p, gr, m, v, 1, interpret=True)
    out_r = f_ref(p, gr, m, v, 1)
    err = float(jnp.abs(out_k[0] - out_r[0]).max())
    emit("kernel/fused-adam-1M", us_ref,
         f"oracle_us={us_ref:.0f} kernel_maxerr={err:.1e}")

    # swa attention kernel vs oracle
    b, h, s, d = 1, 4, 1024, 64
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    f_oracle = jax.jit(lambda q, k, v: ref.ref_swa_attention(
        q, k, v, window=256))
    us_o = time_us(lambda: jax.block_until_ready(f_oracle(q, k, vv)))
    out = ops.swa_attention(q, k, vv, window=256, interpret=True)
    err = float(jnp.abs(out - f_oracle(q, k, vv)).max())
    emit("kernel/swa-1k", us_o,
         f"oracle_us={us_o:.0f} kernel_maxerr={err:.1e} window=256")
