"""Jitted public wrappers for the Pallas kernels.

Every wrapper lowers to Mosaic for the TPU unless the caller passes
``interpret=True``; none picks interpret mode by itself, so a kernel never
runs interpreted on a chip by accident.  The CPU tests and
``benchmarks/bench_kernels.py`` pass ``interpret=True`` explicitly: the
kernels then execute their Python bodies for validation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .fused_adam import fused_adam_pallas
from .overflow_check import overflow_check_pallas
from .swa_attention import swa_attention_pallas


@partial(jax.jit, static_argnames=("block_m", "interpret"))
def overflow_check(x, *, block_m: int = 512, interpret: bool = False):
    """Fused Inf/NaN flag over any tensor (the paper's Algorithm 1 on TPU)."""
    return overflow_check_pallas(x, block_m=block_m, interpret=interpret)


@partial(jax.jit, static_argnames=(
    "lr", "beta1", "beta2", "eps", "weight_decay", "out_dtype", "block_m",
    "interpret"))
def fused_adam(p, g, m, v, step, *, lr=1e-4, beta1=0.9, beta2=0.999,
               eps=1e-8, weight_decay=0.0, out_dtype=jnp.bfloat16,
               block_m: int = 256, interpret: bool = False):
    """Fused AdamW step emitting half-precision compute weights."""
    return fused_adam_pallas(p, g, m, v, step, lr=lr, beta1=beta1,
                             beta2=beta2, eps=eps, weight_decay=weight_decay,
                             out_dtype=out_dtype, block_m=block_m,
                             interpret=interpret)


@partial(jax.jit, static_argnames=("window", "causal", "block_q", "block_k",
                                   "interpret"))
def swa_attention(q, k, v, *, window: int = 0, causal: bool = True,
                  block_q: int = 256, block_k: int = 256,
                  interpret: bool = False):
    """Sliding-window flash attention (B, H, S, D) x (B, KH, S, D)."""
    return swa_attention_pallas(q, k, v, window=window, causal=causal,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)
