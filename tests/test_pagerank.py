"""PageRank on Graph500 graphs (``repro.core.graph``, ``repro.pagerank``)
against the plain float64 reference of the Graphalytics equations
(``repro.testing.pagerank``), and the generator against its
specification.

Tolerance: every rank is compared on its own, ``|x - ref| / ref``.  The
program stores ``1/outdeg`` in float32 (relative error <= 2**-24) and
sums each row's products and the update in float32: a few ulps of
float32 per iteration, so 2e-6 per vertex over one iteration and 1e-5
over ten.  Storing the values in bfloat16 (2**-9) misses both by two
orders of magnitude, which the last tests check.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import formats as F
from repro.core import graph as G
from repro.testing import pagerank as PR

STEP_RTOL = 2e-6
RUN_RTOL = 1e-5
FORMATS = ["pjds", "sell", "cmrs", "wsell", "ellpack_r"]


def _rel_err(x, ref):
    return float(np.max(np.abs(np.asarray(x, np.float64) - ref) / ref))


def _directed(n=3000, seed=0):
    """A directed graph with power-law out-degrees, a tenth of the
    vertices without out-edges (dangling), and a few vertices with
    neither in- nor out-edges."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(rng.zipf(1.8, n), 400)
    deg[rng.random(n) < 0.1] = 0
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n - 5, len(src))        # the last 5: no in-edges
    keep = src != dst
    m = F.csr_from_coo(src[keep], dst[keep], np.ones(int(keep.sum()),
                                                     np.float32), (n, n))
    m.data[:] = 1.0                               # duplicates merged
    return m


@pytest.fixture(scope="module")
def directed():
    return _directed()


@pytest.fixture(scope="module")
def kron():
    src, dst = G.kronecker_edges(11, seed=7)
    return G.undirected_csr(src, dst)


def _reference(adj, iterations, x0=None, damping=0.85):
    src, dst = PR.csr_edges(adj.indptr, adj.indices)
    return PR.pagerank(src, dst, adj.n_rows, iterations, damping, x0)


def test_directed_graph_with_dangling_vertices(directed):
    assert np.diff(directed.indptr).min() == 0       # dangling present
    ranks = repro.pagerank(directed, iterations=10)
    ref = _reference(directed, 10)
    assert _rel_err(ranks, ref) < RUN_RTOL
    assert float(jnp.sum(ranks)) == pytest.approx(1.0, abs=1e-5)
    assert ref.sum() == pytest.approx(1.0, abs=1e-12)


def test_dropping_the_dangling_mass_is_caught(directed):
    """The reference's dangling term matters on this graph: without it
    the ranks no longer sum to 1 and miss the tolerance."""
    src, dst = PR.csr_edges(directed.indptr, directed.indices)
    n = directed.n_rows
    x = np.full(n, 1.0 / n)
    ref = PR.pagerank_step(src, dst, n, x)
    outdeg = np.bincount(src, minlength=n)
    lost = 0.85 * x[outdeg == 0].sum() / n
    assert _rel_err(ref - lost, ref) > 100 * STEP_RTOL


def test_undirected_kronecker_graph(kron):
    ranks = repro.pagerank(kron, iterations=10)
    assert _rel_err(ranks, _reference(kron, 10)) < RUN_RTOL


def test_x0_is_taken_and_checked(kron):
    rng = np.random.default_rng(3)
    x0 = rng.uniform(0.5, 1.5, kron.n_rows)
    x0 /= x0.sum()
    ranks = repro.pagerank(kron, iterations=3, x0=x0.astype(np.float32))
    assert _rel_err(ranks, _reference(kron, 3, x0=x0)) < RUN_RTOL
    with pytest.raises(ValueError, match="vertices"):
        repro.pagerank(kron, x0=x0[:-1])


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("graph", ["directed", "kron"])
def test_every_format_matches_the_reference(fmt, graph, request):
    adj = request.getfixturevalue(graph)
    pr = G.pagerank_operator(adj, format=fmt)
    assert pr.op.fmt == fmt
    rng = np.random.default_rng(11)
    x0 = rng.uniform(0.5, 1.5, adj.n_rows)
    x0 /= x0.sum()
    x = jnp.asarray(x0, jnp.float32)
    step = jax.jit(G.pagerank_steps, static_argnums=(2,))
    one = step(pr, x, 1, 0.85)
    assert _rel_err(one, _reference(adj, 1, x0=np.asarray(x))) < STEP_RTOL
    ten = step(pr, x, 10, 0.85)
    assert _rel_err(ten, _reference(adj, 10, x0=np.asarray(x))) < RUN_RTOL


@pytest.mark.parametrize("fmt", ["pjds", "sell", "cmrs", "wsell"])
def test_kernels_match_the_reference(fmt):
    """The Pallas kernels (interpret mode here) on a small Kronecker
    graph, one iteration."""
    src, dst = G.kronecker_edges(8, seed=2)
    adj = G.undirected_csr(src, dst)
    pr = G.pagerank_operator(adj, format=fmt, backend="kernel")
    x = jnp.full(adj.n_rows, 1.0 / adj.n_rows, jnp.float32)
    y = G.pagerank_steps(pr, x, 1)
    assert _rel_err(y, _reference(adj, 1)) < STEP_RTOL


@pytest.mark.parametrize("graph", ["directed", "kron"])
def test_bfloat16_values_fail_the_tolerance(graph, request):
    adj = request.getfixturevalue(graph)
    pr = G.pagerank_operator(adj, dtype=jnp.bfloat16)
    x = jnp.full(adj.n_rows, 1.0 / adj.n_rows, jnp.float32)
    y = G.pagerank_steps(pr, x, 1)
    assert _rel_err(y, _reference(adj, 1)) > 10 * STEP_RTOL


def test_transition_columns_sum_to_one(directed):
    p, dangling = G.transition(directed)
    outdeg = np.diff(directed.indptr)
    assert np.array_equal(dangling, outdeg == 0)
    colsum = np.bincount(p.indices, weights=p.data, minlength=p.n_cols)
    np.testing.assert_allclose(colsum[~dangling], 1.0, rtol=1e-6)
    assert np.all(colsum[dangling] == 0)
    assert F.validate_csr(p)[1].ok


def test_undirected_graph_is_simple_and_symmetric(kron):
    rows, cols = PR.csr_edges(kron.indptr, kron.indices)
    assert np.all(rows != cols)                               # no loops
    key = rows * kron.n_rows + cols
    assert np.all(np.diff(key) > 0)                   # sorted, no dups
    back = np.sort(cols * kron.n_rows + rows)
    assert np.array_equal(key, back)                          # symmetric
    assert np.diff(kron.indptr).min() >= 1                # no isolated


def test_undirected_csr_drops_loops_duplicates_and_isolated():
    src = np.array([0, 1, 1, 2, 5, 5, 3])
    dst = np.array([1, 0, 1, 5, 2, 5, 3])
    m = G.undirected_csr(src, dst, n=7)
    # live vertices 0, 1, 2, 5 renumbered 0..3; edges 0-1 and 2-5
    assert m.shape == (4, 4)
    assert m.indptr.tolist() == [0, 1, 2, 3, 4]
    assert m.indices.tolist() == [1, 0, 3, 2]


def test_generator_draws_the_initiator():
    """Quadrant frequencies per level, before the relabelling: A, B, C,
    D within five standard deviations of the binomial count."""
    scale, ef = 12, 16
    a, b, c = G.GRAPH500_INITIATOR
    expect = np.array([a, b, c, 1 - a - b - c])
    src, dst = G.kronecker_ids(scale, ef, np.random.default_rng(5),
                               G.GRAPH500_INITIATOR)
    n_e = ef << scale
    for level in range(scale):
        q = ((src >> level) & 1) * 2 + ((dst >> level) & 1)
        got = np.bincount(q, minlength=4)
        sd = np.sqrt(n_e * expect * (1 - expect))
        assert np.all(np.abs(got - n_e * expect) < 5 * sd), (level, got)


def test_generator_follows_its_seed():
    a = G.kronecker_edges(10, seed=1)
    b = G.kronecker_edges(10, seed=1)
    c = G.kronecker_edges(10, seed=2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert len(a[0]) == 16 << 10 and a[0].max() < 1 << 10


def test_update_runs_under_its_scope(kron):
    pr = G.pagerank_operator(kron, format="cmrs")
    hlo = jax.jit(G.pagerank_steps, static_argnums=(2,)).lower(
        pr, jax.ShapeDtypeStruct((kron.n_rows,), jnp.float32), 1,
        0.85).as_text(debug_info=True)
    assert "repro.pagerank" in hlo


def test_build_notes_the_grid_fill():
    obs.reset()
    try:
        src, dst = G.kronecker_edges(12, seed=3)
        pr = G.pagerank_operator(G.undirected_csr(src, dst), format="pjds")
        fill = obs.gauges()["repro.grid_fill"]
        assert fill == pr.op.grid_fill
        assert "repro.graph.transition" in obs.totals()
    finally:
        obs.reset()
