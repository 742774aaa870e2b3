"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# overflow_check
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("n", [1, 127, 128, 129, 65_536, 100_001])
def test_overflow_shape_dtype_sweep(dtype, n, rng):
    x = jnp.asarray(rng.standard_normal(n), dtype)
    assert bool(ops.overflow_check(x, interpret=True)) == bool(ref.ref_overflow_check(x))
    x = x.at[n // 2].set(jnp.inf)
    assert bool(ops.overflow_check(x, interpret=True))
    x = x.at[n // 2].set(jnp.nan)
    assert bool(ops.overflow_check(x, interpret=True))


@pytest.mark.parametrize("shape", [(4, 4), (3, 5, 7), (2, 2, 2, 2)])
def test_overflow_nd_shapes(shape, rng):
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    assert not bool(ops.overflow_check(x, interpret=True))
    x = x.reshape(-1).at[0].set(-jnp.inf).reshape(shape)
    assert bool(ops.overflow_check(x, interpret=True))


@settings(max_examples=40, deadline=None)
@given(n=st.integers(min_value=1, max_value=20_000),
       pos=st.floats(min_value=0, max_value=1),
       kind=st.sampled_from(["none", "inf", "-inf", "nan", "max"]),
       block_m=st.sampled_from([8, 64, 512]))
def test_overflow_property(n, pos, kind, block_m):
    rng = np.random.default_rng(42)
    x = rng.standard_normal(n).astype(np.float32)
    if kind in ("inf", "-inf", "nan"):
        x[int(pos * (n - 1))] = {"inf": np.inf, "-inf": -np.inf,
                                 "nan": np.nan}[kind]
    elif kind == "max":
        x[int(pos * (n - 1))] = np.finfo(np.float32).max  # must NOT trigger
    expected = kind in ("inf", "-inf", "nan")
    got = bool(ops.overflow_check(jnp.asarray(x), block_m=block_m,
                                  interpret=True))
    assert got == expected


# ---------------------------------------------------------------------------
# fused_adam
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16,), (100, 3), (8, 8, 9), (2048,)])
@pytest.mark.parametrize("step", [1, 10, 1000])
def test_adam_shape_step_sweep(shape, step, rng):
    p = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.standard_normal(shape)) * 0.01, jnp.float32)
    kw = dict(lr=3e-3, weight_decay=0.05)
    out_k = ops.fused_adam(p, g, m, v, step, interpret=True, **kw)
    out_r = ref.ref_fused_adam(p, g, m, v, step, **kw)
    for a, b in zip(out_k[:3], out_r[:3], strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(out_k[3]).view(np.uint16),
        np.asarray(out_r[3]).view(np.uint16))   # bf16 bit-exact


def test_adam_multi_step_trajectory(rng):
    shape = (512,)
    p = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g0 = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.zeros(shape); v = jnp.zeros(shape)
    pr, mr, vr = p, m, v
    for t in range(1, 6):
        g = g0 * (0.9 ** t)
        p, m, v, _ = ops.fused_adam(p, g, m, v, t, lr=1e-2,
                                    interpret=True)
        pr, mr, vr, _ = ref.ref_fused_adam(pr, g, mr, vr, t, lr=1e-2)
    np.testing.assert_allclose(np.asarray(p), np.asarray(pr), rtol=1e-4,
                               atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(min_value=1, max_value=5000),
       lr=st.floats(min_value=1e-5, max_value=1e-1),
       step=st.integers(min_value=1, max_value=10_000),
       seed=st.integers(min_value=0, max_value=2**31))
def test_adam_property(n, lr, step, seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n); v = jnp.zeros(n)
    p2, m2, v2, w16 = ops.fused_adam(p, g, m, v, step, lr=lr,
                                     interpret=True)
    pr, mr, vr, _ = ref.ref_fused_adam(p, g, m, v, step, lr=lr)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pr), rtol=1e-4,
                               atol=1e-7)
    # v is a variance: always >= 0
    assert float(jnp.min(v2)) >= 0.0


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [0, 64, 128])
def test_swa_sweep(dtype, h, kh, window, rng):
    b, s, d = 2, 256, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, kh, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, kh, s, d)), dtype)
    out = ops.swa_attention(q, k, v, window=window, block_q=64, block_k=64,
                            interpret=True)
    expected = ref.ref_swa_attention(q, k, v, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), atol=tol)


def test_swa_non_causal(rng):
    b, h, s, d = 1, 2, 128, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    out = ops.swa_attention(q, k, v, window=0, causal=False, block_q=64,
                            block_k=64, interpret=True)
    expected = ref.ref_swa_attention(q, k, v, window=0, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


def test_swa_window_equals_full_when_window_ge_seq(rng):
    b, h, s, d = 1, 2, 128, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    full = ops.swa_attention(q, k, v, window=0, block_q=64, block_k=64,
                             interpret=True)
    wide = ops.swa_attention(q, k, v, window=s, block_q=64, block_k=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(wide), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([64, 128, 256]),
       window=st.sampled_from([0, 32, 64]),
       blocks=st.sampled_from([(32, 32), (64, 32), (64, 64)]),
       seed=st.integers(min_value=0, max_value=2**31))
def test_swa_block_shape_invariance(s, window, blocks, seed):
    """Kernel output must not depend on the BlockSpec tiling."""
    rng = np.random.default_rng(seed)
    b, h, d = 1, 2, 16
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    bq, bk = blocks
    out = ops.swa_attention(q, k, v, window=window, block_q=bq, block_k=bk,
                            interpret=True)
    expected = ref.ref_swa_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=3e-5)
