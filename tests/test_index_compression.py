"""Compressed-stream edge cases: int16/int32 index selection at the
boundary, bf16 value storage vs the f32 reference, accumulator dtypes,
the padding-sentinel audit, and the gathered-RHS kernel path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import formats as F
from repro.core.operator import operator
from repro.kernels import ops


def _mk(rng, n, density=0.08, n_cols=None, dtype=np.float32):
    n_cols = n if n_cols is None else n_cols
    a = ((rng.random((n, n_cols)) < density)
         * rng.standard_normal((n, n_cols))).astype(dtype)
    return a, F.csr_from_dense(a)


# --------------------------------------------------------------- selection
def test_min_index_dtype_boundary():
    assert F.min_index_dtype(1) == np.int16
    assert F.min_index_dtype(2 ** 15) == np.int16       # max col 32767 fits
    assert F.min_index_dtype(2 ** 15 + 1) == np.int32   # col 32768 does not


def test_resolve_index_dtype_rejects_lossy_narrowing():
    assert F.resolve_index_dtype("auto", 100) == np.int16
    assert F.resolve_index_dtype(np.int32, 100) == np.int32  # explicit wide ok
    with pytest.raises(ValueError):
        F.resolve_index_dtype(np.int16, 2 ** 15 + 1)
    with pytest.raises(ValueError):
        F.resolve_index_dtype(np.uint16, 100)           # signed only


def test_builders_compress_at_boundary(rng):
    # wide-but-sparse matrices via COO keep the build cheap
    rows = np.arange(64, dtype=np.int64).repeat(3)
    vals = rng.standard_normal(len(rows))
    for span, want in ((2 ** 15, np.int16), (2 ** 15 + 1, np.int32)):
        cols = rng.integers(0, span, len(rows))
        m = F.csr_from_coo(rows, cols, vals, (64, span))
        e = F.csr_to_ell(m, row_align=32)
        p = F.csr_to_pjds(m, b_r=32, permuted_cols=False)
        assert e.col_idx.dtype == want
        assert p.col_idx.dtype == want
    # the permuted-cols build addresses the PADDED ROW span, not n_cols
    sq = F.csr_from_coo(rows, rng.integers(0, 64, len(rows)), vals, (64, 64))
    assert F.csr_to_pjds(sq, b_r=32, permuted_cols=True).col_idx.dtype \
        == np.int16


# ----------------------------------------------------- end-to-end numerics
@pytest.mark.parametrize("n", [96, 130, 161])   # incl. non-divisible rows
@pytest.mark.parametrize("fmt", ["ellpack_r", "pjds", "sell"])
def test_int16_matches_int32_and_dense(rng, n, fmt):
    a, m = _mk(rng, n)
    x = rng.standard_normal(n).astype(np.float32)
    truth = a.astype(np.float64) @ x
    y16 = np.asarray(operator(m, format=fmt, b_r=32,
                              backend="kernel") @ x)
    y32 = np.asarray(operator(m, format=fmt, b_r=32, backend="kernel",
                              index_dtype=np.int32) @ x)
    d16 = ops.as_device(m, fmt, b_r=32)
    assert d16.index_dtype == np.int16        # n << 2**15: auto compresses
    assert ops.as_device(m, fmt, b_r=32,
                         index_dtype=np.int32).index_dtype == np.int32
    np.testing.assert_allclose(y16, y32, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(y16, truth, atol=1e-3)


@pytest.mark.parametrize("fmt", ["pjds", "sell"])
def test_bf16_storage_numerics_and_dtype(rng, fmt):
    a, m = _mk(rng, 160)
    x = rng.standard_normal(160).astype(np.float32)
    truth = a.astype(np.float64) @ x
    dev = ops.as_device(m, fmt, b_r=32, dtype=jnp.bfloat16)
    assert dev.value_dtype == jnp.bfloat16
    assert dev.index_dtype == np.int16
    for backend in ("ref", "kernel"):
        y = dev.matvec(jnp.asarray(x), backend=backend)
        # bf16 storage, f32 accumulation — and an f32 result
        assert y.dtype == jnp.float32
        scale = max(np.abs(truth).max(), 1.0)
        err = np.abs(np.asarray(y, np.float64) - truth) / scale
        assert err.max() < 1e-2               # the acceptance tolerance


def test_bf16_matmat_accumulates_f32(rng):
    a, m = _mk(rng, 128)
    dev = ops.as_device(m, "sell", b_r=32, dtype=jnp.bfloat16)
    xs = rng.standard_normal((128, 8)).astype(np.float32)
    ys = dev.matmat(jnp.asarray(xs), backend="kernel")
    assert ys.dtype == jnp.float32
    truth = a.astype(np.float64) @ xs
    scale = max(np.abs(truth).max(), 1.0)
    assert (np.abs(np.asarray(ys, np.float64) - truth) / scale).max() < 1e-2


# --------------------------------------------------------- padding sentinel
def test_padding_audit_passes_on_built_formats(rng):
    _, m = _mk(rng, 130, density=0.15)
    F.assert_padding_invariant(F.csr_to_ell(m, row_align=32))
    F.assert_padding_invariant(F.csr_to_pjds(m, b_r=32, permuted_cols=False))
    F.assert_padding_invariant(F.csr_to_sell(m, c=32, permuted_cols=False))


def test_padding_audit_catches_corruption(rng):
    _, m = _mk(rng, 130, density=0.05)
    p = F.csr_to_pjds(m, b_r=32, permuted_cols=False)
    # the very last storage slot of the last block belongs to the padded
    # (shortest, possibly empty) row of the sorted order
    assert p.rowlen[-1] < p.block_len[-1]
    bad_val = p.val.copy()
    bad_val[-1, -1] = 7.0
    with pytest.raises(AssertionError):
        F.assert_padding_invariant(
            F.PJDSMatrix(**{**p.__dict__, "val": bad_val}))
    bad_col = p.col_idx.copy()
    bad_col[-1, -1] = 3
    with pytest.raises(AssertionError):
        F.assert_padding_invariant(
            F.PJDSMatrix(**{**p.__dict__, "col_idx": bad_col}))


# ------------------------------------------------- gathered RHS, any length
@pytest.mark.parametrize("n", [128, 130])      # incl. non-divisible rows
@pytest.mark.parametrize("fmt", ["ellpack_r", "pjds", "sell", "cmrs"])
def test_kernel_gathers_rhs_of_any_length(rng, fmt, n):
    # the RHS gather runs ahead of the kernel for every format: x of a
    # length no tile divides must still meet every stored column
    a, m = _mk(rng, n, density=0.1)
    x = rng.standard_normal(n).astype(np.float32)
    y = np.asarray(operator(m, format=fmt, b_r=32, backend="kernel") @ x)
    np.testing.assert_allclose(y, a.astype(np.float64) @ x, atol=1e-3)


def test_cache_key_normalizes_index_dtype(rng):
    _, m = _mk(rng, 96)
    d1 = ops.as_device(m, "pjds", b_r=32, index_dtype=np.int32)
    d2 = ops.as_device(m, "pjds", b_r=32, index_dtype="int32")
    d3 = ops.as_device(m, "pjds", b_r=32, index_dtype=np.dtype("int32"))
    assert d1 is d2 is d3


# ------------------------------------------------------- interpret default
def test_resolve_interpret_default_tracks_backend():
    on_tpu = jax.default_backend() == "tpu"
    assert ops.resolve_interpret(None) == (not on_tpu)
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False


# ------------------------------------------------------------- distributed
def test_partition_compresses_per_device_slices(rng):
    # A 512-row global matrix split 4 ways: each slice spans n_loc = 128
    # local columns and a (2w+1)*n_loc ext buffer — both int16 territory
    # regardless of the global size.
    from repro.core import dist_spmv as D
    a, m = _mk(rng, 512, density=0.02)
    dist = D.partition_csr(m, 4, b_r=32)
    assert dist.loc_col.dtype == jnp.int16
    assert dist.rem_col.dtype == jnp.int16
    assert dist.loc_max_chunks >= 1 and dist.rem_max_chunks >= 1
    d32 = D.partition_csr(m, 4, b_r=32, index_dtype=np.int32)
    assert d32.loc_col.dtype == jnp.int32
