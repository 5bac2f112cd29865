"""The benchmark's matrices and byte count against the program's
generators and a hand count."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import matrices as CM
from chipbench import reference as R

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _lower(indptr, indices):
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    low = indices < rows
    return rows[low], indices[low]


@pytest.mark.parametrize("scale", [0.002, 0.01])
def test_samg_lower_triangle_is_the_programs(scale):
    from repro.core import matrices as M
    cfg = _cfg("samg")
    mine = CM.build(cfg, value_seed=12345, scale=scale, use_cache=False)
    theirs = M.samg(scale=scale, seed=cfg["structure_seed"])
    for a, b in zip(_lower(mine.indptr, mine.indices),
                    _lower(theirs.indptr, theirs.indices)):
        assert np.array_equal(a, b)


def _dense(m):
    a = np.zeros((m.n_rows, m.n_rows))
    rows = np.repeat(np.arange(m.n_rows), np.diff(m.indptr))
    a[rows, m.indices] = m.data
    return a


def test_samg_is_a_shifted_laplacian():
    cfg = _cfg("samg")
    m = CM.build(cfg, value_seed=2**31 + 7, scale=0.001, use_cache=False)
    a = _dense(m).astype(np.float64)
    assert m.data.dtype == np.float32
    assert np.array_equal(a, a.T)
    off = a - np.diag(np.diag(a))
    assert np.all(off <= 0)
    w = -off[off < 0]
    assert w.min() >= 0.5 and w.max() <= 1.5
    degree = -off.sum(1)
    sigma = np.diag(a) - degree
    np.testing.assert_allclose(sigma, degree.max() / cfg["time_step"],
                               rtol=1e-5)
    eig = np.linalg.eigvalsh(a)
    assert eig.min() > 0
    assert eig.max() / eig.min() <= 1 + 2 * cfg["time_step"]
    rl = np.diff(m.indptr)
    assert rl.min() >= 1 and rl.max() > 4 * rl.min()
    assert 6.4 <= rl.mean() <= 6.9


def test_samg_values_follow_the_seed():
    cfg = _cfg("samg")
    a = CM.build(cfg, value_seed=1, scale=0.002, use_cache=False)
    b = CM.build(cfg, value_seed=1, scale=0.002, use_cache=False)
    c = CM.build(cfg, value_seed=2**31 + 7, scale=0.002, use_cache=False)
    assert np.array_equal(a.data, b.data) and not np.array_equal(a.data, c.data)
    assert np.array_equal(a.indices, c.indices)


def test_dlr1_row_lengths_are_the_published():
    m = CM.build(_cfg("dlr1"), value_seed=3, scale=0.1, use_cache=False)
    rl = np.diff(m.indptr)
    assert abs(rl.mean() - 144) <= 2
    assert 1.8 <= rl.max() / rl.min() <= 2.5
    assert m.n_rows == 28_000


def test_structure_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(CM, "CACHE", tmp_path)
    cfg = _cfg("samg")
    a = CM.build(cfg, value_seed=5, scale=0.002)
    assert len(list(tmp_path.glob("*.npz"))) == 1
    b = CM.build(cfg, value_seed=5, scale=0.002)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.data, b.data)
    # any changed key of the configuration makes a structure of its own
    CM.build({**cfg, "band": 40}, value_seed=5, scale=0.002)
    CM.build({**cfg, "assumed": {}}, value_seed=5, scale=0.002)
    assert len(list(tmp_path.glob("*.npz"))) == 3


def test_min_bytes_hand_count():
    # 3 rows, 5 non-zeros: 5 x (4 B value + 4 B index) + 3 x 3 x 4 B
    # (x read, y read and written) = 40 + 36.
    assert R.spmvm_min_bytes(3, 5) == 76
    # sAMG at its published size: 22,627,647 nnz and 3.4M rows.
    assert R.spmvm_min_bytes(3_400_000, 22_627_647) == 221_821_176


def test_reference_matches_dense():
    m = CM.build(_cfg("samg"), value_seed=9, scale=0.002, use_cache=False)
    dense = _dense(m)
    x = np.random.default_rng(0).standard_normal(m.n_rows)
    ref = R.HostCSR(m)
    np.testing.assert_allclose(ref.matvec(x), dense @ x, rtol=1e-12)
    y = ref.power_steps(x, 2)
    z = dense @ (dense @ x)
    np.testing.assert_allclose(y, z / np.linalg.norm(z), rtol=1e-9)
