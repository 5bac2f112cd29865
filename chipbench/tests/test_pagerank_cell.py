"""The ``graph500.pagerank`` cell on the CPU at a small size: the
benchmark's generator against the program's, its reference against a
dense computation, sound runs correct and each fault the cell can have,
planted under the timed path, caught; and its two new readers."""
import json
import pathlib
import sys
import time

import numpy as np
import pytest

from chipbench import matrices as CM
from chipbench import pagerank_reference as R
from chipbench import run as RUN
from chipbench.matrices import graph500 as GEN

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "graph500.pagerank"
SCALE = 0.002          # 8,388 rows asked: scale 13
SEED = 2**31 + 977


@pytest.fixture(autouse=True)
def fresh_programs():
    import jax
    jax.clear_caches()


def _cfg():
    return json.loads((ROOT / "chipbench" / "configs" / "graph500.json")
                      .read_text())


def _run(**kw):
    return RUN.run_cell(CELL, SEED, 1.0, False, t_start=time.perf_counter(),
                        require_chip=False, scale=SCALE, **kw)


@pytest.mark.parametrize("scale", [9, 12])
def test_generator_is_the_programs_draw_for_draw(scale):
    from repro.core import graph as G
    cfg = _cfg()
    indptr, indices = GEN.structure(cfg, 1 << scale)
    src, dst = G.kronecker_edges(scale, cfg["edge_factor"],
                                 cfg["structure_seed"], cfg["initiator"])
    theirs = G.undirected_csr(src, dst, 1 << scale)
    assert np.array_equal(indptr, theirs.indptr)
    assert np.array_equal(indices, theirs.indices)


def test_scale_is_log2_of_the_rows():
    cfg = _cfg()
    m = CM.build(cfg, value_seed=3, scale=SCALE, use_cache=False)
    indptr, _ = GEN.structure(cfg, 1 << 13)
    assert np.array_equal(m.indptr, indptr)
    assert m.data.dtype == np.float32 and np.all(m.data == 1.0)
    assert np.diff(m.indptr).min() >= 1           # no isolated vertices


def test_reference_matches_a_dense_step():
    cfg = _cfg()
    m = CM.build(cfg, value_seed=3, scale=SCALE, use_cache=False)
    n = m.n_rows
    a = np.zeros((n, n))
    a[np.repeat(np.arange(n), np.diff(m.indptr)), m.indices] = 1.0
    a[5] = 0.0                                    # one dangling vertex
    keep = np.repeat(np.arange(n), np.diff(m.indptr)) != 5
    lens = np.diff(m.indptr).copy()
    lens[5] = 0
    m5 = CM.HostMatrix(np.concatenate(([0], np.cumsum(lens))),
                       m.indices[keep], m.data[keep])
    x = np.random.default_rng(0).uniform(0.5, 1.5, n)
    x /= x.sum()
    deg = a.sum(1)
    p = (a / np.where(deg > 0, deg, 1)[:, None]).T
    dense = 0.15 / n + 0.85 * (p @ x + x[deg == 0].sum() / n)
    ref = R.HostPageRank(m5).step(x, 0.85)
    np.testing.assert_allclose(ref, dense, rtol=1e-12)
    assert ref.sum() == pytest.approx(1.0, abs=1e-12)


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "hbm_gb", "spmvm_gflops"} <= set(line["metrics"])
    assert line["checks"]["pagerank_max_rel_err"]["value"] > 0


@pytest.mark.parametrize("kind", ["unchanged", "altered"])
def test_matvec_fault_is_caught(monkeypatch, kind):
    from repro.core.operator import DeviceOperator
    import jax.numpy as jnp
    sound = DeviceOperator.matvec

    def broken(self, x, backend=None):
        if kind == "unchanged":
            return x[: self.shape[0]]
        y = sound(self, x, backend)
        return y.at[0].add(0.01 * jnp.abs(y).max())
    monkeypatch.setattr(DeviceOperator, "matvec", broken)
    assert _run()["correct"] is False


@pytest.mark.parametrize("fault", ["damping", "teleport"])
def test_update_fault_is_caught(monkeypatch, fault):
    """The cell's graph has no dangling vertex, so a dropped dangling
    term cannot show there (``tests/test_pagerank.py`` catches it on a
    graph that has them); a changed damping or a lost teleport can."""
    from repro.core import graph as G
    sound = G.pagerank_steps

    def broken(pr, x, k, damping=0.85):
        if fault == "damping":
            return sound(pr, x, k, 0.86)
        return sound(pr, x, k, damping) - (1.0 - damping) / pr.n
    monkeypatch.setattr(G, "pagerank_steps", broken)
    assert _run()["correct"] is False


def _ctx():
    return {"counters": {}, "trace": None, "peaks": None, "n_rows": 10,
            "nnz": 20}


@pytest.fixture
def fresh_obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


def test_grid_fill_reads_the_gauge(fresh_obs):
    read = RUN._reader("grid_fill")
    assert read(_ctx()) is None
    fresh_obs.gauge("repro.grid_fill", 0.5)
    assert read(_ctx()) == 0.5


def test_grid_fill_reads_nothing_without_the_program(monkeypatch, fresh_obs):
    fresh_obs.gauge("repro.grid_fill", 0.5)
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert RUN._reader("grid_fill")(_ctx()) is None


def test_update_ms_reads_nothing_without_a_trace():
    assert RUN._reader("update_ms.pagerank")(
        {**_ctx(), "counters": {"applies": 3}}) is None
