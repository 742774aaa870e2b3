"""Pallas TPU kernels for MemAscend's compute hot-spots.

* :mod:`overflow_check` — the paper's fused Inf/NaN scan (Algorithm 1),
* :mod:`fused_adam` — the host-optimizer analogue: fused AdamW + bf16 emit,
* :mod:`swa_attention` — banded flash attention for the long_500k shape.

``ops`` holds jitted wrappers; ``ref`` the pure-jnp oracles the tests sweep
against.  The wrappers lower to Mosaic unless the caller passes
``interpret=True``, as the CPU tests do; ``tests/test_tpu_compile.py``
compiles them for a described v5e chip.  BlockSpec tiling targets TPU
(8,128) 32-bit tiles and MXU-aligned matmul dims.
"""

from . import ops, ref
from .ops import fused_adam, overflow_check, swa_attention

__all__ = ["ops", "ref", "overflow_check", "fused_adam", "swa_attention"]
