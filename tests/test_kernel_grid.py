"""Kernel parity grid: format x dtype policy x legal statics, interpret
mode vs the jnp refs.

This is the conformance gate ``repro.tune`` relies on: the autotuner is
free to pick ANY candidate from its search space, so every (format,
dtype policy, b_r, chunk_l) point the space can emit must compute the
same answer through the Pallas kernel as through the ref — at
tolerances set by the STORED value dtype, not by the statics — whether
x has exactly n_cols entries or is a padded carrier as the solvers pass
it.  The matrix is built once (deterministic seed, row count not a
multiple of any swept b_r, so every case exercises partial-block
padding).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import formats as F
from repro.kernels import ops

N = 160           # not a multiple of 64/128 -> padded tail blocks
_SEED = 0


def _build():
    rng = np.random.default_rng(_SEED)
    rl = np.clip(rng.zipf(1.8, size=N), 1, N // 4)     # skewed rows
    a = np.zeros((N, N), np.float32)
    for i in range(N):
        a[i, rng.integers(0, N, size=rl[i])] = rng.standard_normal(rl[i])
    return a, F.csr_from_dense(a)


_A, _M = _build()
_X = np.random.default_rng(_SEED + 1).standard_normal(N).astype(np.float32)
_TRUTH = _A.astype(np.float64) @ _X

# (value dtype, index_dtype, tolerance vs the f64 dense truth).  Kernel
# vs ref stays tight in BOTH policies: they read identical stored
# values and accumulate >= f32.
_DTYPES = [
    pytest.param(None, np.int32, 1e-4, id="f32+int32"),
    pytest.param(jnp.bfloat16, "auto", 3e-2, id="bf16+auto"),
]
_STATICS = [(32, 8), (64, 16), (128, 8)]        # (b_r, chunk_l)


def _parity(fmt, b_r, chunk_l, x_pad, dtype, index_dtype, tol):
    sd = ops.as_device(_M, fmt, b_r=b_r, diag_align=max(8, chunk_l),
                       chunk_l=chunk_l, dtype=dtype,
                       index_dtype=index_dtype)
    # Padded tail entries are never addressed: poison them.
    x = jnp.asarray(np.concatenate([_X, np.full(x_pad, np.nan, np.float32)]))
    y_ref = np.asarray(sd.matvec(x, backend="ref"), np.float64)
    y_ker = np.asarray(sd.matvec(x, backend="kernel"), np.float64)
    scale = max(np.abs(_TRUTH).max(), 1.0)
    np.testing.assert_allclose(y_ker / scale, y_ref / scale, atol=1e-5)
    np.testing.assert_allclose(y_ker / scale, _TRUTH / scale, atol=tol)


@pytest.mark.parametrize("dtype,index_dtype,tol", _DTYPES)
@pytest.mark.parametrize("b_r,chunk_l", _STATICS)
@pytest.mark.parametrize("x_pad", [0, 64])
@pytest.mark.parametrize("fmt", ["pjds", "sell", "cmrs"])
def test_blocked_kernel_grid(fmt, b_r, chunk_l, x_pad, dtype,
                             index_dtype, tol):
    _parity(fmt, b_r, chunk_l, x_pad, dtype, index_dtype, tol)


@pytest.mark.parametrize("dtype,index_dtype,tol", _DTYPES)
@pytest.mark.parametrize("b_r,chunk_l", _STATICS)
def test_ellr_kernel_grid(b_r, chunk_l, dtype, index_dtype, tol):
    _parity("ellpack_r", b_r, chunk_l, 0, dtype, index_dtype, tol)


@pytest.mark.parametrize("b_r,chunk_l", _STATICS[:2])
def test_sell_sigma_axis(b_r, chunk_l):
    # sigma sweeps reshuffle rows across windows; parity must hold at
    # every window size the tuner may choose, incl. the pJDS limit
    for sigma in (b_r, 4 * b_r, N + b_r):
        sd = ops.as_device(_M, "sell", b_r=b_r, diag_align=max(8, chunk_l),
                           chunk_l=chunk_l, sigma=sigma)
        x = jnp.asarray(_X)
        y_ref = np.asarray(sd.matvec(x, backend="ref"), np.float64)
        y_ker = np.asarray(sd.matvec(x, backend="kernel"), np.float64)
        scale = max(np.abs(_TRUTH).max(), 1.0)
        np.testing.assert_allclose(y_ker / scale, y_ref / scale, atol=1e-5)
        np.testing.assert_allclose(y_ref / scale, _TRUTH / scale, atol=1e-4)
