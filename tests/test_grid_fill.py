"""``grid_fill``: the share of a kernel grid's steps that compute a
chunk, per format, and the gauge the operator build notes."""
import numpy as np
import pytest

from repro import obs
from repro.core import dist_spmv as D
from repro.core import graph as G
from repro.core import matrices as M
from repro.core.operator import DistOperator, operator
from repro.kernels._backend import OUT_BLOCKS


@pytest.fixture(scope="module")
def hubs():
    """A Graph500 PageRank operator's matrix: a few rows of thousands of
    entries over rows of a handful."""
    src, dst = G.kronecker_edges(14, seed=2)
    return G.transition(G.undirected_csr(src, dst))[0]


def _grouped_fill(dev, n_blocks):
    """Chunks over the grouped grid's steps, counted from the chunk map."""
    cm = np.asarray(dev.chunk_map)
    groups = np.bincount(cm // OUT_BLOCKS)
    n_groups = -(-n_blocks // OUT_BLOCKS)
    return len(cm) / (n_groups * groups.max())


@pytest.mark.parametrize("fmt", ["pjds", "sell", "cmrs", "wsell"])
def test_grouped_formats(hubs, fmt):
    op = operator(hubs, format=fmt)
    d = op.dev.dev
    n_blocks = d.n_strips if fmt == "cmrs" else d.n_blocks
    assert op.grid_fill == pytest.approx(_grouped_fill(d, n_blocks))
    assert 0 < op.grid_fill <= 1


def test_ellpack_r_and_csr(hubs):
    m = M.power_law(3000)
    ell = operator(m, format="ellpack_r")
    tc = np.asarray(ell.dev.dev.tile_chunks)
    n_chunks = ell.dev.dev.val.shape[0] // ell.dev.dev.chunk_l
    steps = -(-len(tc) // OUT_BLOCKS) * OUT_BLOCKS * n_chunks
    assert ell.grid_fill == pytest.approx(tc.sum() / steps)
    assert operator(m, format="csr").grid_fill == 1.0


def test_hub_rows_empty_the_pjds_grid(hubs):
    """pJDS sorts the hubs into its first group, whose chunk count sets
    every group's extent; CMRS's ceiling is one strip's non-zeros."""
    pjds = operator(hubs, format="pjds").grid_fill
    cmrs = operator(hubs, format="cmrs").grid_fill
    assert pjds < 0.2 and cmrs > 0.5


def test_distributed_operator():
    m = M.poisson_2d(30, 30)
    dist = D.partition_csr(m, 4, b_r=32)
    op = DistOperator(dist, mesh=None)
    n_groups = -(-dist.n_blocks // OUT_BLOCKS)
    n_loc = dist.loc_chunk_map.shape[1]
    n_rem = dist.rem_chunk_map.shape[1]
    want = (n_loc + n_rem) / (n_groups * (dist.loc_max_chunks
                                          + dist.rem_max_chunks))
    assert op.grid_fill == pytest.approx(want)
    assert 0 < op.grid_fill <= 1


def test_build_notes_the_operator_built_last(hubs):
    obs.reset()
    try:
        a = operator(hubs, format="pjds")
        assert obs.gauges()["repro.grid_fill"] == a.grid_fill
        b = operator(hubs, format="cmrs")
        assert obs.gauges()["repro.grid_fill"] == b.grid_fill
    finally:
        obs.reset()
