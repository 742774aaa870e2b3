"""Pallas TPU kernel: fused gradient-overflow check (paper Algorithm 1).

TPU-native adaptation of MemAscend's fused overflow check (DESIGN §2): the
flat gradient buffer streams HBM→VMEM in (block_m, 128) tiles of 32-bit
words; each tile is tested for the IEEE-754 all-ones exponent (Inf or
NaN); a single int32 flag in SMEM accumulates across the sequential TPU
grid.  No full-size temporaries are ever materialized — the kernel's extra
footprint is one VMEM tile, vs the baseline chain's 2.25× HBM spike.

16-bit inputs are bit-cast in pairs to uint32 words before the kernel and
both halves are tested: the kernel itself never holds a 16-bit vector.
Mosaic cannot load fp16 vectors on v5e, nor lay out a reduction over a
16-bit mask, so every dtype takes the same 32-bit path.

The paper's early exit (Algorithm 1 line 7) maps to predicated *skipping*:
once the flag is set, later tiles still stream but skip the test work
(`pl.when`).  A TPU grid cannot abort, so bandwidth is still paid — the
compute saving mirrors the OpenMP break semantics as closely as the
hardware allows (noted in DESIGN.md).

Exponent masks: fp32 0x7F80_0000; bf16 0x7F80; fp16 0x7C00 (each 16-bit
mask is tested in both halves of a word).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # TPU lane width
DEFAULT_BLOCK_M = 512   # (512, 128) uint32 tile = 256 KiB of VMEM

# exponent masks per 32-bit word: one for fp32, one per half for 16-bit
_MASKS = {
    jnp.dtype(jnp.float32): (0x7F80_0000,),
    jnp.dtype(jnp.bfloat16): (0x7F80, 0x7F80_0000),
    jnp.dtype(jnp.float16): (0x7C00, 0x7C00_0000),
}


def _overflow_kernel(x_ref, flag_ref, *, masks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        flag_ref[0] = jnp.int32(0)

    @pl.when(flag_ref[0] == 0)   # "early exit": skip work once flagged
    def _check():
        bits = x_ref[...]
        hit = None
        for m in masks:
            h = (bits & jnp.uint32(m)) == jnp.uint32(m)
            hit = h if hit is None else hit | h
        flag_ref[0] = jnp.max(hit.astype(jnp.int32))


def overflow_check_pallas(x, *, block_m: int = DEFAULT_BLOCK_M,
                          interpret: bool = False):
    """True iff any element of ``x`` is Inf or NaN.

    ``x`` may be any shape/size; it is padded (with zeros, which never
    trigger) to a (M, 128) layout of 32-bit words.
    """
    dtype = jnp.dtype(x.dtype)
    if dtype not in _MASKS:
        raise TypeError(f"overflow check: unsupported dtype {dtype}")
    per_word = 4 // dtype.itemsize

    flat = x.reshape(-1)
    n = flat.size
    rows = -(-n // (LANE * per_word))
    rows = -(-rows // block_m) * block_m          # multiple of block_m
    padded = jnp.zeros((rows * LANE * per_word,), dtype).at[:n].set(flat)
    if per_word == 1:
        words = jax.lax.bitcast_convert_type(padded, jnp.uint32)
    else:
        halves = jax.lax.bitcast_convert_type(padded, jnp.uint16)
        words = jax.lax.bitcast_convert_type(halves.reshape(-1, 2),
                                             jnp.uint32)
    tiled = words.reshape(rows, LANE)
    grid = rows // block_m

    flag = pl.pallas_call(
        functools.partial(_overflow_kernel, masks=_MASKS[dtype]),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_m, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
    )(tiled)
    return flag[0] > 0
