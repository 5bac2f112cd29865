"""Distributed multi-RHS spMM + gathered-halo partition tests.

Host-side partition/accounting tests run in-process (they build arrays
but never launch collectives); the end-to-end spMM and block-CG checks
run in a subprocess with 8 virtual host devices, like test_dist_spmv.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import dist_spmv as D, formats as F, matrices as M

pytestmark = pytest.mark.dist


# --------------------------------------------------------------------------
# Host-side: gather sets and communication accounting
# --------------------------------------------------------------------------
def _block_diag_csr(rng, n_dev=8, n_loc=64, n_rows=300):
    """Block-diagonal w.r.t. the n_loc partition of the padded size."""
    n_pad = n_dev * n_loc
    a = np.zeros((n_rows, n_rows), np.float32)
    for p in range(n_dev):
        lo, hi = p * n_loc, min((p + 1) * n_loc, n_rows)
        if hi <= lo:
            break
        blk = rng.standard_normal((hi - lo, hi - lo))
        a[lo:hi, lo:hi] = blk * (rng.random(blk.shape) < 0.3)
    assert n_pad >= n_rows
    return F.csr_from_dense(a)


def _boundary_coupled_csr(rng, n=512, n_loc=64, reach=96, stride=8):
    """Tridiagonal + sparse long-range coupling at ring distance <= 2:
    every ``stride``-th row references column i +/- ``reach``
    (n_loc < reach < 2*n_loc), so only a few columns cross each
    boundary — the regime where the gathered halo wins big."""
    a = np.zeros((n, n), np.float32)
    i = np.arange(n)
    a[i, i] = 4.0
    a[i[:-1], i[:-1] + 1] = -1.0
    a[i[1:], i[1:] - 1] = -1.0
    far = i[::stride]
    for sgn in (+1, -1):
        tgt = far + sgn * reach
        ok = (tgt >= 0) & (tgt < n)
        a[far[ok], tgt[ok]] = -0.5
    return F.csr_from_dense(a)


def test_remote_columns_by_distance():
    """The gather sets are exactly the referenced neighbor columns."""
    # device 1 of 4 (n_loc=4): rows reference cols 0, 2 (dist -1),
    # own slice, and col 9 (dist +1)
    dense = np.zeros((4, 16), np.float32)
    dense[0, [0, 4]] = 1.0
    dense[1, [2, 5, 9]] = 1.0
    dense[3, [0, 7]] = 1.0
    sl = F.csr_from_dense(dense)
    need = F.csr_remote_columns_by_distance(sl, p=1, n_loc=4, n_dev=4)
    assert set(need) == {-1, +1}
    np.testing.assert_array_equal(need[-1], [0, 2])
    np.testing.assert_array_equal(need[+1], [1])   # col 9 -> slice 2, local 1


def test_block_diagonal_measures_zero_halo(rng):
    dist = D.partition_csr(_block_diag_csr(rng), 8, b_r=32)
    assert dist.halo_w == 0
    assert dist.halo_lens == ()
    assert dist.comm_bytes_per_device() == 0
    assert dist.comm_bytes_per_device(halo="full") == 0


def test_comm_bytes_reports_measured_gathered_halo(rng):
    """Satellite: comm_bytes_per_device must report what the wire
    carries, not 2*halo_w*n_loc."""
    m = _boundary_coupled_csr(rng)
    dist = D.partition_csr(m, 8, b_r=32)
    assert dist.halo_w == 2
    gathered = dist.comm_bytes_per_device(value_bytes=4)
    full = dist.comm_bytes_per_device(value_bytes=4, halo="full")
    assert gathered == sum(dist.halo_lens) * 4
    assert full == 2 * 2 * dist.n_loc * 4
    # sparse coupling: the compressed exchange ships far less
    assert gathered * 5 <= full
    # multi-RHS scales both linearly
    assert dist.comm_bytes_per_device(value_bytes=4, k=4) == 4 * gathered


def test_halo_lens_match_gather_sets(rng):
    m = _boundary_coupled_csr(rng)
    n_loc = D.padded_global_size(m.n_rows, 8, 32) // 8
    needs = [
        F.csr_remote_columns_by_distance(
            D._csr_row_slice(m, p * n_loc, (p + 1) * n_loc, n_loc),
            p, n_loc, 8)
        for p in range(8)
    ]
    dist = D.partition_csr(m, 8, b_r=32)
    for i, d in enumerate(D.halo_distances(dist.halo_w)):
        expect = max(len(nd.get(d, ())) for nd in needs)
        assert dist.halo_lens[i] == expect


def test_explicit_halo_w_too_small_raises(rng):
    m = _boundary_coupled_csr(rng)
    with pytest.raises(ValueError, match="halo_w"):
        D.partition_csr(m, 8, b_r=32, halo_w=1)


def test_poisson_partition_matches_tridiag_structure():
    m = M.poisson_2d(40, 40)
    dist = D.partition_csr(m, 8, b_r=32)
    assert dist.halo_w == 1
    # the 5-point stencil couples one grid line (40 cols) per boundary
    assert dist.halo_lens == (40, 40)


# --------------------------------------------------------------------------
# Subprocess: distributed spMM vs dense, and block-CG end-to-end
# --------------------------------------------------------------------------
_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import repro
    from repro.core import formats as F, matrices as M, dist_spmv as D
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh

    out = {}
    n_dev = 8
    mesh = make_host_mesh(n_dev)
    rng = np.random.default_rng(0)

    def block_diag(n_rows=300, n_loc=64):
        a = np.zeros((n_rows, n_rows), np.float32)
        for p in range(n_dev):
            lo, hi = p * n_loc, min((p + 1) * n_loc, n_rows)
            if hi <= lo:
                break
            blk = rng.standard_normal((hi - lo, hi - lo))
            a[lo:hi, lo:hi] = blk * (rng.random(blk.shape) < 0.3)
        return F.csr_from_dense(a)

    def boundary_coupled(n=512, reach=96, stride=8):
        a = np.zeros((n, n), np.float32)
        i = np.arange(n)
        a[i, i] = 4.0
        a[i[:-1], i[:-1] + 1] = -1.0
        a[i[1:], i[1:] - 1] = -1.0
        far = i[::stride]
        for sgn in (+1, -1):
            tgt = far + sgn * reach
            ok = (tgt >= 0) & (tgt < n)
            a[far[ok], tgt[ok]] = -0.5
        return F.csr_from_dense(a)

    # halo_w 0 / 1 / 2; 300 and 320 are NOT divisible by n_dev*b_r = 256
    cases = [("w0", block_diag(), 0), ("w1", M.poisson_2d(20, 16), 1),
             ("w2", boundary_coupled(), 2)]
    for name, m, w_expect in cases:
        dist = D.partition_csr(m, n_dev, b_r=32)
        out[f"halo_{name}"] = dist.halo_w
        assert dist.halo_w == w_expect, (name, dist.halo_w)
        dense = F.csr_to_dense(m).astype(np.float64)
        for k in (1, 4):
            X = np.zeros((dist.n_global_pad, k), np.float32)
            X[:m.n_rows] = rng.standard_normal((m.n_rows, k))
            Xj = jax.device_put(jnp.asarray(X),
                                jax.NamedSharding(mesh, P("data", None)))
            T = dense @ X[:m.n_rows]
            scale = np.abs(T).max()
            for mode in ("vector", "naive", "overlap"):
                mm = jax.jit(dist_operator(dist, mesh, mode=mode).matmat)
                Y = np.asarray(mm(Xj))[:m.n_rows]
                out[f"err_{name}_k{k}_{mode}"] = float(
                    np.abs(Y - T).max() / scale)
            # gathered and full-slice halos agree
            mm_full = jax.jit(dist_operator(dist, mesh, mode="overlap",
                                            halo="full").matmat)
            Yf = np.asarray(mm_full(Xj))[:m.n_rows]
            out[f"err_{name}_k{k}_full"] = float(np.abs(Yf - T).max() / scale)

    # block-CG on the SPD Poisson system, distributed operator in
    # overlap mode, vs k independent CG solves
    m = M.poisson_2d(20, 16)
    dist = D.partition_csr(m, n_dev, b_r=32)
    k = 4
    B = np.zeros((dist.n_global_pad, k), np.float32)
    B[:m.n_rows] = rng.standard_normal((m.n_rows, k))
    Bj = jax.device_put(jnp.asarray(B),
                        jax.NamedSharding(mesh, P("data", None)))
    op = dist_operator(dist, mesh, mode="overlap")
    res = repro.solve(op, Bj, method="block_cg", maxiter=1500, tol=1e-6)
    out["blk_cg_res"] = float(np.max(np.asarray(res.residual)))
    out["blk_cg_iters"] = int(res.iters)
    Xblk = np.asarray(res.x)[:m.n_rows]

    cg_res, Xcols = [], []
    for j in range(k):
        bj = jax.device_put(jnp.asarray(B[:, j]),
                            jax.NamedSharding(mesh, P("data")))
        r = repro.solve(op, bj, method="cg", maxiter=1500, tol=1e-6)
        cg_res.append(float(r.residual))
        Xcols.append(np.asarray(r.x)[:m.n_rows])
    out["cg_res_max"] = max(cg_res)
    Xind = np.stack(Xcols, axis=1)
    out["x_diff"] = float(np.abs(Xblk - Xind).max() /
                          max(np.abs(Xind).max(), 1e-30))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def spmm_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_spmm_all_modes_all_widths(spmm_results):
    for name in ("w0", "w1", "w2"):
        for k in (1, 4):
            for mode in ("vector", "naive", "overlap"):
                assert spmm_results[f"err_{name}_k{k}_{mode}"] < 1e-5, (
                    name, k, mode)


def test_spmm_gathered_matches_full_slice(spmm_results):
    for name in ("w0", "w1", "w2"):
        for k in (1, 4):
            assert spmm_results[f"err_{name}_k{k}_full"] < 1e-5


def test_measured_halo_widths(spmm_results):
    assert spmm_results["halo_w0"] == 0
    assert spmm_results["halo_w1"] == 1
    assert spmm_results["halo_w2"] == 2


def test_distributed_block_cg_matches_independent_cg(spmm_results):
    """Acceptance: block-CG over the distributed overlap-mode operator
    reaches the same residual as k independent CG solves."""
    assert spmm_results["blk_cg_res"] < 1e-5
    assert spmm_results["cg_res_max"] < 1e-5
    assert spmm_results["x_diff"] < 1e-3
    assert 0 < spmm_results["blk_cg_iters"] < 1500
