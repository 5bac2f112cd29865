"""Windowed SELL-C-sigma: the RHS gathered inside the kernel from a
window of x, the out-of-window non-zeros as an XLA remainder.

Parity of the kernel (interpret mode on the CPU) and the ref path with
the CSR product on the patterns the format has to get right, under both
dtype policies (float32 values with int32 global remainder columns;
bfloat16 values with int16 ones; the window offsets are int16 in both),
then the operator's other applies on a windowed operand, and when the
dispatch engages the format: where rows are local, and never where the
columns scatter.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import formats as F
from repro.core import matrices as M
from repro.core.operator import operator
from repro.kernels import ops

UNIT = F.WINDOW_UNIT


def _coo(n, rows, cols, seed, n_cols=None):
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.5, 1.5, len(rows)) * rng.choice([-1.0, 1.0],
                                                          len(rows))
    return F.csr_from_coo(rows, cols, vals.astype(np.float32),
                          (n, n_cols or n))


def _banded(n, band, far_share=0.0, wrap=False, seed=0, diag=True):
    """Rows of 3 to 12 entries within +-band of the diagonal (wrapped at
    the edges, or clipped), a share of them moved to uniform columns."""
    rng = np.random.default_rng(seed)
    rl = rng.integers(3, 13, n)
    rows = np.repeat(np.arange(n), rl)
    cols = rows + rng.integers(-band, band + 1, len(rows))
    cols = cols % n if wrap else np.clip(cols, 0, n - 1)
    far = rng.random(len(rows)) < far_share
    cols[far] = rng.integers(0, n, int(far.sum()))
    if diag:
        rows = np.concatenate([rows, np.arange(n)])
        cols = np.concatenate([cols, np.arange(n)])
    return _coo(n, rows, cols, seed + 1)


def _block_outside(n=9000):
    """Banded rows, but the first 128 rows couple only to the far end of
    x: their whole block falls outside the window its sigma-window
    takes."""
    m = _banded(n, 30, seed=3, diag=False)
    rng = np.random.default_rng(4)
    rows = np.repeat(np.arange(n), m.row_lengths())
    cols = m.indices.astype(np.int64).copy()
    first = rows < 128
    cols[first] = rng.integers(n - 200, n, int(first.sum()))
    return _coo(n, rows, cols, 5)


CASES = {
    # band and 5% uniform couplings, as sAMG; rows not a multiple of 128
    "banded_far": lambda: _banded(9000, 50, far_share=0.05, seed=0),
    # a wide band wrapped at the edges, as DLR1: edge rows land far away
    "wrapped": lambda: _banded(6000, 300, wrap=True, seed=1),
    "block_outside": _block_outside,
    # x fits one window: no remainder
    "no_remainder": lambda: _banded(2000, 40, seed=2),
    # n_rows % 128 != 0 and the last windows clamped at x's end
    "ragged_clamped": lambda: _banded(5000, 20, seed=6),
}

POLICIES = [pytest.param(None, np.int32, id="f32+int32"),
            pytest.param(jnp.bfloat16, np.int16, id="bf16+int16")]


def _truth(m, dtype, x):
    """The CSR product in float64 on the values as stored."""
    vals = m.data if dtype is None else np.asarray(
        jnp.asarray(m.data).astype(dtype).astype(jnp.float32))
    rows = np.repeat(np.arange(m.n_rows), m.row_lengths())
    y = np.zeros((m.n_rows,) + x.shape[1:])
    xs = x[m.indices].astype(np.float64)
    np.add.at(y, rows, (vals[:, None] * xs) if x.ndim == 2 else vals * xs)
    return y


def _close(y, truth):
    scale = np.abs(truth).max()
    np.testing.assert_allclose(np.asarray(y) / scale, truth / scale,
                               atol=2e-6)


@pytest.fixture(scope="module")
def mats():
    return {k: f() for k, f in CASES.items()}


def test_cases_hold_what_they_name(mats):
    w = {k: F.csr_to_wsell(m, diag_align=16) for k, m in mats.items()}
    assert all(len(w[k].rem_row)
               for k in ("banded_far", "wrapped", "block_outside"))
    assert len(w["no_remainder"].rem_row) == 0
    b = w["block_outside"]
    # rows 0-127 serve nothing from their window: sorted last in their
    # sigma-window, they fill one block of padding slots alone
    empty = np.flatnonzero(b.rowlen.reshape(-1, 128).sum(axis=1) == 0)
    assert len(empty) == 1 and b.block_len[empty[0]] == 16
    pos = np.arange(empty[0] * 128, (empty[0] + 1) * 128)
    np.testing.assert_array_equal(np.sort(b.perm[pos]), np.arange(128))
    assert set(pos) <= set(b.rem_row.tolist())
    for k, v in w.items():
        assert v.x_len % UNIT == 0 and v.window % UNIT == 0
        assert int(v.wbase.max()) * UNIT + v.window <= v.x_len
    c = w["ragged_clamped"]
    assert c.shape[0] % 128
    assert int(c.wbase.max()) * UNIT + c.window == c.x_len   # clamped
    assert int(c.wbase[-1]) < (c.shape[0] - 1) // UNIT       # below anchor


@pytest.mark.parametrize("case", sorted(CASES))
def test_converter_round_trips(mats, case):
    m = mats[case]
    w = F.csr_to_wsell(m, diag_align=16)
    F.assert_padding_invariant(w)
    assert w.col_off.dtype == np.int16
    assert np.all(np.diff(w.rem_row) >= 0)
    np.testing.assert_array_equal(F.wsell_to_dense(w), F.csr_to_dense(m))
    assert F.storage_elements(w) == w.val.size + len(w.rem_val)
    assert w.window_share == pytest.approx(
        1 - len(w.rem_row) / m.nnz)


@pytest.mark.parametrize("dtype,idt", POLICIES)
@pytest.mark.parametrize("backend", ["kernel", "ref"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_windowed_matvec_matches_csr(mats, case, backend, dtype, idt):
    m = mats[case]
    op = operator(m, format="wsell", backend=backend, dtype=dtype,
                  index_dtype=idt)
    d = op.dev.dev
    assert op.fmt == "wsell" and d.col_off.dtype == jnp.int16
    assert d.rem_col.dtype == idt
    x = np.random.default_rng(7).standard_normal(m.shape[1]).astype(
        np.float32)
    _close(op @ jnp.asarray(x), _truth(m, dtype, x))


@pytest.mark.parametrize("dtype,idt", POLICIES)
@pytest.mark.parametrize("backend", ["kernel", "ref"])
def test_windowed_matmat_rmatvec_diagonal(mats, backend, dtype, idt):
    m = mats["wrapped"]
    op = operator(m, format="wsell", backend=backend, dtype=dtype,
                  index_dtype=idt)
    rng = np.random.default_rng(8)
    x = rng.standard_normal((m.shape[1], 3)).astype(np.float32)
    _close(op @ jnp.asarray(x), _truth(m, dtype, x))
    y = rng.standard_normal(m.shape[0]).astype(np.float32)
    _close(op.rmatvec(jnp.asarray(y)),
           _truth(F.csr_transpose(m), dtype, y))
    _close(op.T @ jnp.asarray(y), _truth(F.csr_transpose(m), dtype, y))
    a = F.csr_to_dense(m)
    np.testing.assert_allclose(np.asarray(op.diagonal()),
                               np.diag(a).astype(np.float32), rtol=1e-2)


def test_values_carry_the_remainder(mats):
    """One value leaf holds the slots' and the remainder's values, so
    ``with_values`` and gradients reach the out-of-window non-zeros."""
    m = mats["banded_far"]
    op = operator(m, format="wsell")
    d = op.dev.dev
    assert d.rem_row.shape[0] and op.values is d.val
    x = jnp.asarray(np.random.default_rng(9).standard_normal(m.shape[1]),
                    jnp.float32)
    scaled = op.with_values(2 * op.values)
    np.testing.assert_allclose(np.asarray(scaled @ x),
                               2 * np.asarray(op @ x), rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda v: jnp.sum(op.with_values(v) @ x))(op.values)
    # d(sum A x)/d(a_ij) = x_j: the remainder's gradient is x at its columns
    np.testing.assert_allclose(
        np.asarray(dataclasses.replace(d, val=g).rem_val),
        np.asarray(x)[np.asarray(d.rem_col)], rtol=1e-6)


# ------------------------------------------------------ in-kernel unpermute
# The kernel restores the row order inside each output group of
# OUT_BLOCKS * 128 = 1024 rows where sigma divides it, and leaves it to
# XLA otherwise; the remainder is each group's starting value.
UNPERMUTE_CASES = {
    # 4 full groups and a partial fifth, 5% of the entries far away
    "partial_group": lambda: _banded(5000, 40, far_share=0.05, seed=12),
    # whole groups only
    "whole_groups": lambda: _banded(5120, 30, far_share=0.05, seed=13),
    # x fits one window: an empty remainder
    "no_remainder": lambda: _banded(1500, 20, seed=14),
}


@pytest.fixture(scope="module")
def unpermute_mats():
    return {k: f() for k, f in UNPERMUTE_CASES.items()}


def _stored(dense, dtype):
    """``dense`` with its values rounded as the operand stores them."""
    if dtype is None:
        return dense.astype(np.float64)
    return np.asarray(jnp.asarray(dense).astype(dtype).astype(jnp.float32),
                      np.float64)


@pytest.mark.parametrize("dtype,idt", POLICIES)
@pytest.mark.parametrize("case", sorted(UNPERMUTE_CASES))
@pytest.mark.parametrize("sigma", [128, 512, 1024, 2048, 4096])
def test_kernel_unpermute_matches_ref_and_dense(unpermute_mats, sigma, case,
                                                dtype, idt):
    m = unpermute_mats[case]
    kw = dict(format="wsell", sigma=sigma, dtype=dtype, index_dtype=idt)
    op = operator(m, backend="kernel", **kw)
    d = op.dev.dev
    assert d.sigma == sigma and d.fuses_unpermute == (sigma <= 1024)
    assert d.inv_perm.shape[0] % 1024 == 0
    assert (d.rem_row.shape[0] == 0) == (case == "no_remainder")
    x = jnp.asarray(np.random.default_rng(15).standard_normal(m.shape[1]),
                    jnp.float32)
    y = op @ x
    _close(y, np.asarray(operator(m, backend="ref", **kw) @ x, np.float64))
    w = F.csr_to_wsell(m, sigma=sigma, diag_align=16)
    _close(y, _stored(F.wsell_to_dense(w), dtype) @ np.asarray(x, np.float64))
    # the fused apply runs no XLA unpermute; the others keep theirs
    hlo = jax.jit(lambda v: op.matvec(v)).lower(x).as_text(debug_info=True)
    assert ("repro.unpermute" in hlo) == (sigma > 1024)


@pytest.mark.parametrize("sigma", [512, 4096])
def test_fused_operator_keeps_its_other_applies(unpermute_mats, sigma):
    """matmat, rmatvec, the diagonal, a value swap and the gradient read
    the group-padded inverse permutation as they read the plain one."""
    m = unpermute_mats["partial_group"]
    op = operator(m, format="wsell", backend="kernel", sigma=sigma)
    a = F.csr_to_dense(m).astype(np.float64)
    rng = np.random.default_rng(16)
    xs = rng.standard_normal((m.shape[1], 2)).astype(np.float32)
    _close(op @ jnp.asarray(xs), a @ xs)
    y = rng.standard_normal(m.shape[0]).astype(np.float32)
    _close(op.rmatvec(jnp.asarray(y)), a.T @ y)
    np.testing.assert_allclose(np.asarray(op.diagonal()), np.diag(a),
                               rtol=1e-6)
    x = jnp.asarray(xs[:, 0])
    _close(op.with_values(2 * op.values) @ x, 2 * a @ xs[:, 0])
    g = jax.grad(lambda v: jnp.sum(op.with_values(v) @ x))(op.values)
    # d(sum A x)/d(a_ij) = x_j: the slots' and the remainder's alike
    d = op.dev.dev
    cols = np.asarray(d.columns())
    slot_g = np.asarray(g[: d.col_off.shape[0]])
    live = np.asarray(d.slot_val) != 0
    np.testing.assert_allclose(slot_g[live], np.asarray(x)[cols[live]],
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(dataclasses.replace(d, val=g).rem_val),
        np.asarray(x)[np.asarray(d.rem_col)], rtol=1e-6)


@pytest.mark.parametrize("fmt,kw,share", [
    ("wsell", dict(sigma=128), 1.0),
    ("wsell", dict(sigma=1024), 1.0),
    ("wsell", dict(sigma=4096), 0.0),
    ("sell", {}, 0.0),
    ("pjds", {}, 0.0),
    ("cmrs", {}, 0.0),
])
def test_fused_unpermute_gauge(unpermute_mats, fresh_obs, fmt, kw, share):
    op = operator(unpermute_mats["whole_groups"], format=fmt, **kw)
    assert op.fused_unpermute == share
    assert fresh_obs.gauges()["repro.fused_unpermute"] == share


# --------------------------------------------------------------- engagement
ENGAGED = {
    "samg_like": lambda: M.samg(0.006),        # band 50, 5% far couplings
    "dlr1_like": lambda: _banded(20000, 400, wrap=True, seed=10),
}


def _uniform_random(n, seed):
    """2 to 19 entries a row at uniform columns."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n), rng.integers(2, 20, n))
    return _coo(n, rows, rng.integers(0, n, len(rows)), seed + 1)


# (matrix, the format the dispatch picked before windows existed)
GATHERED = {
    "power_law": (lambda: M.power_law(40000), "cmrs"),
    "uniform_random": (lambda: _uniform_random(40000, 11), "cmrs"),
}


@pytest.fixture
def fresh_obs():
    obs.reset()
    yield obs
    obs.reset()


@pytest.mark.parametrize("name", sorted(ENGAGED))
def test_auto_picks_windows_for_local_rows(name, fresh_obs):
    m = ENGAGED[name]()
    assert ops.select_format(m, diag_align=16) == "wsell"
    op = operator(m)
    assert op.fmt == "wsell"
    share = F.window_plan(m, 1024).share
    assert share > 0.9
    assert fresh_obs.gauges()["repro.window_share"] == pytest.approx(share)
    assert op.window_share == pytest.approx(share)


@pytest.mark.parametrize("name", sorted(GATHERED))
def test_scattered_columns_keep_the_gathered_path(name, fresh_obs):
    make, before = GATHERED[name]
    m = make()
    assert F.window_plan(m, 1024).share < 0.2
    assert ops.select_format(m, diag_align=16) == before
    op = operator(m)
    assert op.fmt == before and op.window_share == 0.0
    assert fresh_obs.gauges()["repro.window_share"] == 0.0
    # its apply gathers x in XLA ahead of the kernel
    assert not isinstance(op.dev.dev, ops.WSELLDevice)
    hlo = jax.jit(lambda v: op.matvec(v, backend="kernel")).lower(
        jax.ShapeDtypeStruct((m.shape[1],), jnp.float32)).as_text(
        debug_info=True)
    assert "repro.gather_rhs" in hlo
