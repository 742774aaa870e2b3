"""Pallas TPU kernel: sliding-window flash attention (banded, online softmax).

The sub-quadratic attention variant backing ``long_500k`` on dense/MoE
architectures (DESIGN §4).  FlashAttention-style tiling adapted to the TPU
memory hierarchy: q/k/v stream HBM→VMEM in (block_q/block_k, head_dim)
tiles; softmax statistics (running max m, normalizer l) and the output
accumulator persist in VMEM scratch across the sequential k-block grid
dimension; the banded causal∧window mask is applied per tile.

GQA is handled in the index_map: query head h reads kv head h // n_rep —
no materialized head repetition (the pure-jnp path broadcasts).

Blocks entirely outside the band are skipped via ``pl.when`` predication
(a TPU grid cannot be data-dependently pruned; the HBM streaming for dead
blocks could be eliminated with a banded grid — a perf note, not a
correctness one).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                block_q, block_k, n_k, window, causal, scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # band check: block indices are traced values, so the any_live
    # predication below handles causal and window limits uniformly

    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    any_live = jnp.any(mask)

    @pl.when(any_live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_new = alpha * l_scr[:, :1] + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def swa_attention_pallas(q, k, v, *, window: int = 0, causal: bool = True,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False):
    """Banded attention.  q: (B, H, S, D); k, v: (B, KH, S, D); KH | H.

    ``window=0`` means no band limit (plain causal flash attention).
    Returns (B, H, S, D) in q's dtype.
    """
    b, h, s, d = q.shape
    kh = k.shape[1]
    if h % kh:
        raise ValueError(f"GQA requires KH | H, got H={h}, KH={kh}")
    n_rep = h // kh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"S={s} must be divisible by block sizes "
                         f"({block_q}, {block_k})")
    n_q, n_k = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)

    out = pl.pallas_call(
        functools.partial(_swa_kernel, block_q=block_q, block_k=block_k,
                          n_k=n_k, window=window, causal=causal, scale=scale),
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # normalizer
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out
