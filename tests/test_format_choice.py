"""The dispatch's format choice and window plan on the benchmark's
matrices: the banded ones (the sAMG and DLR1 configurations) keep the
windowed SELL with 3-unit windows, a Graph500 PageRank operator keeps a
gathered format, and ``formats.window_plan`` gives what its plain
counting below gives, non-zero by non-zero."""
import json
import pathlib

import numpy as np
import pytest

from repro.core import formats as F
from repro.core import graph as G
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _bench_matrix(name, scale):
    import sys
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench import matrices as CM
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    m = CM.build(cfg, value_seed=1, scale=scale, use_cache=False)
    return F.CSRMatrix(m.indptr, m.indices, m.data, (m.n_rows, m.n_rows))


def _graph500(scale):
    src, dst = G.kronecker_edges(scale, seed=1)
    return G.transition(G.undirected_csr(src, dst))[0]


MATRICES = {
    "samg": lambda: _bench_matrix("samg", 0.05),
    "dlr1": lambda: _bench_matrix("dlr1", 0.1),
    "graph500": lambda: _graph500(15),
}


@pytest.fixture(scope="module")
def mats():
    return {k: f() for k, f in MATRICES.items()}


def _plain_plan(m, sigma):
    """Every non-zero counted on its own: the histogram of its unit's
    offset from its sigma-window's anchor prices each width and start
    as ``window_plan`` does; a row's in-window non-zeros are those whose
    unit its window covers."""
    u_max = F.WINDOW_UNITS_MAX
    n_rows, n_cols = m.shape
    n_win = max(-(-n_rows // sigma), 1)
    x_units = max(-(-n_cols // F.WINDOW_UNIT), 1)
    row = np.repeat(np.arange(n_rows), np.diff(m.indptr))
    win = row // sigma
    anchor = np.minimum((np.arange(n_win) * sigma + sigma // 2)
                        // F.WINDOW_UNIT, x_units - 1)
    unit = m.indices.astype(np.int64) // F.WINDOW_UNIT
    best = []
    for u in range(1, u_max + 1):
        top = max(x_units, u) - u
        served = np.full(n_win, -1)
        start = np.zeros(n_win, np.int64)
        for d in range(1 - u, 1):
            s = np.clip(anchor + d, 0, top)
            got = np.bincount(win, (unit >= s[win]) & (unit < s[win] + u),
                              n_win).astype(np.int64)
            better = got > served
            served = np.where(better, got, served)
            start = np.where(better, s, start)
        best.append((u, start, int(served.sum())))
    pick = best[-1]
    for (u, start, got), (_, _, wider) in zip(best, best[1:]):
        if (wider - got) / m.nnz < F.WINDOW_SHARE_TOL:
            pick = (u, start, got)
            break
    u, start, _ = pick
    inside = (unit >= start[win]) & (unit < start[win] + u)
    rowlen = np.bincount(row, inside, n_rows).astype(np.int64)
    first = np.full(n_rows, -1)
    pos = np.flatnonzero(inside)
    first[row[pos[::-1]]] = pos[::-1]
    row_lo = np.where(rowlen > 0, first, m.indptr[:-1])
    return u, start, rowlen, row_lo


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_window_plan_counts_every_non_zero(mats, name):
    m = mats[name]
    plan = F.window_plan(m, 1024)
    u, start, rowlen, row_lo = _plain_plan(m, 1024)
    assert plan.units == u
    assert np.array_equal(plan.start, start)
    assert np.array_equal(plan.rowlen, rowlen)
    assert np.array_equal(plan.row_lo, row_lo)


@pytest.mark.parametrize("name", ["samg", "dlr1"])
def test_banded_matrices_keep_three_unit_windows(mats, name):
    m = mats[name]
    assert ops.select_format(m, diag_align=16) == "wsell"
    assert F.window_plan(m, 1024).units == 3


def test_graph500_keeps_a_gathered_format(mats):
    m = mats["graph500"]
    assert F.window_plan(m, 1024).share < 0.2
    assert ops.select_format(m, diag_align=16) == "cmrs"


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_window_reach_bounds_every_plan(mats, name):
    m = mats[name]
    served = int(F.window_plan(m, 1024).rowlen.sum())
    assert served <= F.window_reach(m, 1024) <= m.nnz


@pytest.mark.parametrize("name", sorted(MATRICES) + ["power_law",
                                                     "wide_band"])
def test_skipping_the_plan_changes_no_choice(mats, name):
    """Where the windowed SELL cannot win the plan is not made; pricing
    it from a given plan picks the same format."""
    from repro.core import matrices as M
    m = (mats.get(name) or {"power_law": lambda: M.power_law(20000),
                            "wide_band": lambda: M.poisson_2d(150, 150)}
         [name]())
    fmt, plan = ops._select(m, diag_align=16)
    assert fmt == ops.select_format(m, diag_align=16,
                                    plan=F.window_plan(m, 1024))
    assert (plan is None) == (name in ("graph500", "power_law"))
    if plan is not None:
        ref = F.window_plan(m, 1024)
        assert plan.units == ref.units
        assert np.array_equal(plan.rowlen, ref.rowlen)


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_window_reach_counts_every_non_zero(mats, name):
    m = mats[name]
    n_rows, n_cols = m.shape
    x_units = max(-(-n_cols // F.WINDOW_UNIT), 1)
    row = np.repeat(np.arange(n_rows), np.diff(m.indptr))
    anchor = np.minimum(((row // 1024) * 1024 + 512) // F.WINDOW_UNIT,
                        x_units - 1)
    off = np.abs(m.indices.astype(np.int64) // F.WINDOW_UNIT - anchor)
    assert F.window_reach(m, 1024) == np.count_nonzero(
        off < F.WINDOW_UNITS_MAX)
