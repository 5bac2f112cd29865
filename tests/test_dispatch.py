"""Unified dispatch layer: operator(a, format="auto") @ x property tests.

Three structurally different sparsity patterns (banded, power-law,
uniform-random) must all produce the dense-reference answer through the
auto-dispatched path; the chosen format must be deterministic for a
fixed matrix; explicit formats must agree with each other; and the
conversion cache must hand back the same device representation.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import formats as F
from repro.core.operator import operator
from repro.kernels import ops

B_R = 32


def _banded(rng, n, bw=7):
    a = rng.standard_normal((n, n))
    d = np.abs(np.subtract.outer(np.arange(n), np.arange(n)))
    return np.where(d <= bw, a, 0.0).astype(np.float32)


def _power_law(rng, n):
    rl = np.clip(rng.zipf(1.7, size=n), 1, max(n // 4, 2))
    a = np.zeros((n, n), np.float32)
    for i in range(n):
        cols = rng.integers(0, n, size=rl[i])
        a[i, cols] = rng.standard_normal(len(cols))
    return a


def _uniform(rng, n, density=0.08):
    return (((rng.random((n, n)) < density)
             * rng.standard_normal((n, n))).astype(np.float32))


_PATTERNS = {"banded": _banded, "powerlaw": _power_law, "uniform": _uniform}


def _check_auto(a):
    m = F.csr_from_dense(a)
    rng = np.random.default_rng(1)
    x = rng.standard_normal(a.shape[1]).astype(np.float32)
    y = np.asarray(operator(m, format="auto", b_r=B_R) @ x)
    truth = a.astype(np.float64) @ x
    scale = max(np.abs(truth).max(), 1.0)
    np.testing.assert_allclose(y / scale, truth / scale, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       n=st.sampled_from([48, 96, 160, 224]),
       pattern=st.sampled_from(sorted(_PATTERNS)))
def test_auto_matches_dense_reference(seed, n, pattern):
    rng = np.random.default_rng(seed)
    _check_auto(_PATTERNS[pattern](rng, n))


@pytest.mark.parametrize("pattern", sorted(_PATTERNS))
def test_chosen_format_is_deterministic(rng, pattern):
    a = _PATTERNS[pattern](rng, 192)
    m = F.csr_from_dense(a)
    first = ops.select_format(m, b_r=B_R)
    assert all(ops.select_format(m, b_r=B_R) == first for _ in range(3))
    # the converted representation reports the same format
    assert ops.as_device(m, "auto", b_r=B_R).fmt == first
    # and an identical matrix built from the same dense array agrees
    assert ops.select_format(F.csr_from_dense(a), b_r=B_R) == first


@pytest.mark.parametrize("fmt", ["csr", "ellpack_r", "pjds", "sell"])
def test_explicit_formats_agree(rng, fmt):
    a = _uniform(rng, 160)
    m = F.csr_from_dense(a)
    x = rng.standard_normal(160).astype(np.float32)
    truth = a.astype(np.float64) @ x
    y = np.asarray(operator(m, format=fmt, b_r=B_R) @ x)
    scale = max(np.abs(truth).max(), 1.0)
    np.testing.assert_allclose(y / scale, truth / scale, atol=1e-5)


def test_kernel_backend_through_dispatch(rng):
    a = _uniform(rng, 128)
    m = F.csr_from_dense(a)
    x = rng.standard_normal(128).astype(np.float32)
    for fmt in ("ellpack_r", "pjds", "sell"):
        y_r = np.asarray(operator(m, format=fmt, b_r=B_R, backend="ref") @ x)
        y_k = np.asarray(operator(m, format=fmt, b_r=B_R,
                                   backend="kernel") @ x)
        np.testing.assert_allclose(y_k, y_r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("b_r", [32, 64, 200])
def test_ellpack_r_tile_is_whole_lane_widths(rng, b_r):
    # an ELLPACK-R tile is a lane slice, which the TPU compiler takes
    # only in whole 128-lane vregs: any b_r builds the rounded-up tile
    a = _uniform(rng, 300)
    m = F.csr_from_dense(a)
    x = rng.standard_normal(300).astype(np.float32)
    op = operator(m, format="ellpack_r", b_r=b_r, backend="kernel")
    assert op.dev.dev.tile_r == ops.ell_tile(b_r)
    assert op.dev.dev.tile_r % ops.ELL_LANES == 0
    truth = a.astype(np.float64) @ x
    scale = max(np.abs(truth).max(), 1.0)
    np.testing.assert_allclose(np.asarray(op @ x) / scale, truth / scale,
                               atol=1e-5)
    e = F.csr_to_ell(m, row_align=b_r)
    if b_r % ops.ELL_LANES:
        with pytest.raises(ValueError, match="multiple of 128"):
            ops.to_device_ell(e, tile_r=b_r)


def test_conversion_cache_reuses_device_rep(rng):
    m = F.csr_from_dense(_uniform(rng, 96))
    d1 = ops.as_device(m, "auto", b_r=B_R)
    d2 = ops.as_device(m, "auto", b_r=B_R)
    assert d1 is d2
    # different build params -> different entry (8 was the old default)
    d3 = ops.as_device(m, "auto", b_r=B_R, chunk_l=8)
    assert d3 is not d1
    # operator application goes through the same cache
    x = rng.standard_normal(96).astype(np.float32)
    operator(m, b_r=B_R) @ x
    assert ops.as_device(m, "auto", b_r=B_R) is d1


def test_dense_input_hits_conversion_cache(rng):
    """A dense ndarray is content-hashed: equal data (even a different
    array object) reuses one CSR conversion AND one device conversion —
    previously every dense call silently reconverted."""
    a = _uniform(rng, 96)
    d1 = ops.as_device(a, "auto", b_r=B_R)
    d2 = ops.as_device(a.copy(), "auto", b_r=B_R)   # equal bytes, new object
    assert d1 is d2
    # different content -> different entry
    b = a.copy()
    b[0, 0] += 1.0
    assert ops.as_device(b, "auto", b_r=B_R) is not d1
    # operator application over dense input rides the same cache
    x = rng.standard_normal(96).astype(np.float32)
    operator(a.copy(), b_r=B_R) @ x
    assert ops.as_device(a, "auto", b_r=B_R) is d1


def test_tiny_and_empty_fall_back_to_csr(rng):
    tiny = F.csr_from_dense(_uniform(rng, 16))
    assert ops.select_format(tiny, b_r=B_R) == "csr"
    empty = F.csr_from_dense(np.zeros((256, 256), np.float32))
    assert ops.select_format(empty, b_r=B_R) == "csr"
    x = np.ones(256, np.float32)
    assert np.all(np.asarray(operator(empty, b_r=B_R) @ x) == 0)


def test_non_square_dispatch(rng):
    a = (rng.random((96, 200)) < 0.1) * rng.standard_normal((96, 200))
    a = a.astype(np.float32)
    m = F.csr_from_dense(a)
    x = rng.standard_normal(200).astype(np.float32)
    truth = a.astype(np.float64) @ x
    for fmt in ("auto", "csr", "ellpack_r", "pjds", "sell"):
        y = np.asarray(operator(m, format=fmt, b_r=B_R) @ x)
        assert y.shape == (96,)
        np.testing.assert_allclose(y, truth, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       fmt=st.sampled_from(["ellpack_r", "pjds", "sell"]))
def test_storage_estimates_match_built_matrices(seed, fmt):
    """select_format prices formats from row lengths alone; the estimate
    must agree exactly with what the converters build."""
    rng = np.random.default_rng(seed)
    m = F.csr_from_dense(_uniform(rng, 160, density=0.1))
    rl = m.row_lengths()
    est = F.estimate_storage_elements(rl, fmt, b_r=B_R, diag_align=8,
                                      sigma=2 * B_R)
    if fmt == "ellpack_r":
        built = F.storage_elements(F.csr_to_ell(m, row_align=B_R,
                                                diag_align=8))
    elif fmt == "pjds":
        built = F.storage_elements(F.csr_to_pjds(m, b_r=B_R,
                                                 permuted_cols=False))
    else:
        built = F.storage_elements(F.csr_to_sell(m, c=B_R, sigma=2 * B_R,
                                                 permuted_cols=False))
    assert est == built


# --------------------------------------------------------------------------
# Deprecated pre-protocol shims
# --------------------------------------------------------------------------
def test_spmv_shim_warns_and_still_works(rng):
    """ops.spmv is a deprecated shim over the operator API: it must warn
    (pointing at operator / repro.solve) and keep computing correctly."""
    a = _uniform(rng, 120, density=0.08)
    m = F.csr_from_dense(a)
    x = rng.standard_normal(120).astype(np.float32)
    with pytest.warns(DeprecationWarning, match="operator"):
        y = np.asarray(ops.spmv(m, jnp.asarray(x)))
    np.testing.assert_allclose(y, a.astype(np.float64) @ x, atol=1e-4)


def test_operator_path_does_not_warn(rng):
    """The replacement API must be warning-free — otherwise every
    migrated caller would still see deprecation noise."""
    import warnings
    a = _uniform(rng, 96, density=0.1)
    m = F.csr_from_dense(a)
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        np.asarray(operator(m) @ x)
