"""CMRS format: converter exactness, device refs, dispatch membership,
and the tuner search-space entries (DESIGN.md §13)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import formats as F, matrices as M
from repro.kernels import ops
from repro.tune.space import Candidate, enumerate_candidates, price_candidate


def _hub_matrix(rng, n=300):
    """A few huge rows over a sparse background: the padding-hostile
    shape where CMRS's dense packing wins every blocked format."""
    a = np.zeros((n, n), np.float32)
    for i in range(n):
        a[i, rng.integers(0, n, size=3)] = rng.standard_normal(3)
    for i in rng.integers(0, n, size=4):
        a[i, :] = rng.standard_normal(n)
    np.fill_diagonal(a, np.arange(1, n + 1, dtype=np.float32))
    return a, F.csr_from_dense(a)


def test_cmrs_dense_roundtrip(rng):
    a, m = _hub_matrix(rng)
    for b_r, da in ((32, 8), (128, 16)):
        c = F.csr_to_cmrs(m, b_r=b_r, diag_align=da)
        np.testing.assert_array_equal(F.cmrs_to_dense(c), a)


def test_cmrs_estimate_matches_storage(rng):
    _, m = _hub_matrix(rng)
    rl = m.row_lengths()
    for b_r, da in ((32, 8), (64, 8), (128, 16)):
        c = F.csr_to_cmrs(m, b_r=b_r, diag_align=da)
        assert F.storage_elements(c) == \
            F.estimate_storage_elements(rl, "cmrs", b_r, da)


def test_cmrs_padding_invariant(rng):
    _, m = _hub_matrix(rng)
    c = F.csr_to_cmrs(m, b_r=32, diag_align=8)
    F.assert_padding_invariant(c)     # raises on violation
    bad = F.CMRSMatrix(
        val=c.val, col_idx=c.col_idx,
        row_in_strip=np.where(c.val == 0, 2, c.row_in_strip).astype(np.int8),
        strip_start=c.strip_start, strip_len=c.strip_len,
        strip_nnz=c.strip_nnz, shape=c.shape, b_r=c.b_r,
        n_rows_pad=c.n_rows_pad)
    if np.any(bad.row_in_strip != c.row_in_strip):
        with pytest.raises(AssertionError):
            F.assert_padding_invariant(bad)


def test_cmrs_matvec_matches_dense(rng):
    a, m = _hub_matrix(rng)
    sd = ops.as_device(m, "cmrs")
    x = rng.standard_normal(m.shape[1]).astype(np.float32)
    truth = a.astype(np.float64) @ x
    y = np.asarray(sd.matvec(jnp.asarray(x), backend="ref"), np.float64)
    np.testing.assert_allclose(y, truth, atol=1e-3 * np.abs(truth).max())


def test_cmrs_rmatvec_and_matmat(rng):
    a, m = _hub_matrix(rng)
    sd = ops.as_device(m, "cmrs")
    k = 3
    xs = rng.standard_normal((m.shape[1], k)).astype(np.float32)
    ym = np.asarray(sd.matmat(jnp.asarray(xs)), np.float64)
    np.testing.assert_allclose(ym, a.astype(np.float64) @ xs,
                               atol=1e-3 * np.abs(a).max() * np.sqrt(a.shape[0]))
    y = rng.standard_normal(m.shape[0]).astype(np.float32)
    zt = np.asarray(sd.rmatvec(jnp.asarray(y)), np.float64)
    truth_t = a.T.astype(np.float64) @ y
    np.testing.assert_allclose(zt, truth_t,
                               atol=1e-3 * max(np.abs(truth_t).max(), 1.0))


def test_cmrs_diagonal(rng):
    a, m = _hub_matrix(rng)
    from repro.core.operator import operator
    op = operator(m, format="cmrs")
    np.testing.assert_allclose(np.asarray(op.diagonal()), np.diag(a),
                               rtol=1e-6)


def test_select_format_offers_cmrs(rng):
    _, m = _hub_matrix(rng)
    # x fits one window: the windowed SELL gathers nothing in XLA
    assert ops.select_format(m) == "wsell"
    # without windows (a sigma of part blocks builds none), CMRS wins
    # every format that gathers x in XLA
    assert ops.select_format(m, sigma=1000) == "cmrs"


def test_select_format_still_prefers_ell_for_uniform():
    m = M.poisson_2d(24, 24)
    assert ops.select_format(m) == "wsell"
    assert ops.select_format(m, sigma=1000) == "ellpack_r"


def test_cmrs_in_tuner_space(rng):
    _, m = _hub_matrix(rng)
    cands = enumerate_candidates(m)
    cm = [c for c in cands if c.fmt == "cmrs"]
    assert cm, "cmrs missing from the tuner search space"
    for c in cm[:3]:
        assert price_candidate(m, c) > 0


def test_cmrs_candidate_builds_through_as_device(rng):
    _, m = _hub_matrix(rng)
    c = Candidate(fmt="cmrs", b_r=32, chunk_l=8)
    sd = ops.as_device(m, **c.build_kwargs())
    assert sd.fmt == "cmrs"
    x = jnp.asarray(rng.standard_normal(m.shape[1]).astype(np.float32))
    y = sd.matvec(x, backend="ref")
    assert y.shape == (m.shape[0],)


def test_cmrs_empty_rows_and_tiny(rng):
    # all-empty strips, strip count 1, n not a multiple of b_r
    a = np.zeros((70, 70), np.float32)
    a[0, 3] = 2.0
    a[69, 0] = -1.0
    m = F.csr_from_dense(a)
    c = F.csr_to_cmrs(m, b_r=32, diag_align=8)
    np.testing.assert_array_equal(F.cmrs_to_dense(c), a)
    sd = ops.as_device(m, "cmrs", b_r=32)
    x = rng.standard_normal(70).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(sd.matvec(jnp.asarray(x), backend="ref")),
        a @ x, atol=1e-5)
