"""``formats.csr_transpose`` against the stable COO build it replaced:
the same rows, columns and value order, duplicates kept."""
import numpy as np
import pytest

from repro.core import formats as F


def _coo_transpose(m):
    rows = np.repeat(np.arange(m.n_rows, dtype=np.int64), m.row_lengths())
    return F.csr_from_coo(m.indices.astype(np.int64), rows, m.data,
                          (m.n_cols, m.n_rows), sum_duplicates=False)


def _with_duplicates(rng, n_rows, n_cols, nnz):
    rows = np.sort(rng.integers(0, n_rows, nnz))
    cols = rng.integers(0, n_cols, nnz)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return F.CSRMatrix(indptr, cols.astype(np.int32),
                       rng.standard_normal(nnz).astype(np.float32),
                       (n_rows, n_cols))


@pytest.mark.parametrize("n_rows,n_cols,nnz", [
    (1, 1, 0), (40, 7, 300), (7, 40, 300), (500, 500, 5000),
    (3, 1 << 20, 50)])
def test_csr_transpose_matches_the_coo_build(n_rows, n_cols, nnz):
    m = _with_duplicates(np.random.default_rng(nnz + n_cols), n_rows,
                         n_cols, nnz)
    got, want = F.csr_transpose(m), _coo_transpose(m)
    assert got.shape == want.shape == (n_cols, n_rows)
    np.testing.assert_array_equal(got.indptr, want.indptr)
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_array_equal(got.data, want.data)
    assert got.indices.dtype == np.int32
