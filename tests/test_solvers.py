"""Krylov solvers on single-device pJDS operators."""
import numpy as np
import jax.numpy as jnp

from repro.core import formats as F, matrices as M, solvers as S
from repro.kernels import ops


def _op(m, b_r=32):
    p = F.csr_to_pjds(m, b_r=b_r)
    dev = ops.to_device_pjds(p)
    return p, (lambda x: ops.pjds_matvec(dev, x))


def _block_op(m, b_r=32):
    p = F.csr_to_pjds(m, b_r=b_r)
    dev = ops.to_device_pjds(p)
    return p, (lambda x: ops.pjds_matmat(dev, x))


def _permute_cols(p, a):
    return np.stack([p.permute(a[:, j]) for j in range(a.shape[1])], axis=1)


def test_cg_poisson(rng):
    m = M.poisson_2d(20, 20)
    p, mv = _op(m)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    res = S.cg(mv, jnp.asarray(p.permute(b)), maxiter=1500, tol=1e-7)
    x = p.unpermute(np.asarray(res.x))
    r = np.linalg.norm(F.csr_to_dense(m) @ x - b) / np.linalg.norm(b)
    assert r < 1e-4


def test_cg_on_samg_matrix(rng):
    m = M.samg(scale=0.0005)            # small SPD-shifted AMG analogue
    p, mv = _op(m)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    res = S.cg(mv, jnp.asarray(p.permute(b)), maxiter=3000, tol=1e-6)
    assert float(res.residual) < 1e-4


def test_lanczos_extremal_eigenvalue(rng):
    m = M.poisson_2d(16, 16)
    p, mv = _op(m)
    v0 = jnp.asarray(p.permute(rng.standard_normal(m.n_rows).astype(np.float32)))
    al, be = S.lanczos(mv, v0, m=60)
    ev = S.tridiag_eigvals(al, be)
    dense_ev = np.linalg.eigvalsh(F.csr_to_dense(m))
    assert abs(ev.max() - dense_ev.max()) < 1e-3 * abs(dense_ev.max())


def test_power_iteration(rng):
    m = M.poisson_2d(12, 12)
    p, mv = _op(m)
    v0 = jnp.asarray(p.permute(np.ones(m.n_rows, np.float32)))
    _, lam = S.power_iteration(mv, v0, iters=500)
    dense_ev = np.linalg.eigvalsh(F.csr_to_dense(m))
    assert abs(float(lam) - dense_ev.max()) < 1e-2 * abs(dense_ev.max())


def test_block_cg_matches_dense_solve(rng):
    """Block-CG over the multi-RHS pJDS operator solves all k systems."""
    m = M.poisson_2d(20, 20)
    p, mm = _block_op(m)
    k = 4
    b = rng.standard_normal((m.n_rows, k)).astype(np.float32)
    res = S.block_cg(mm, jnp.asarray(_permute_cols(p, b)),
                     maxiter=1500, tol=1e-7)
    assert float(np.max(np.asarray(res.residual))) < 1e-5
    x = np.stack([p.unpermute(np.asarray(res.x)[:, j]) for j in range(k)],
                 axis=1)
    r = np.linalg.norm(F.csr_to_dense(m) @ x - b) / np.linalg.norm(b)
    assert r < 1e-4


def test_block_cg_fewer_iters_than_scalar_cg(rng):
    """The block Krylov space is richer: block-CG needs fewer iterations
    (i.e. fewer matrix streams) than any of the k scalar solves."""
    m = M.poisson_2d(16, 16)
    p, mm = _block_op(m)
    _, mv = _op(m)
    b = rng.standard_normal((m.n_rows, 4)).astype(np.float32)
    res_blk = S.block_cg(mm, jnp.asarray(_permute_cols(p, b)),
                         maxiter=800, tol=1e-6)
    res_0 = S.cg(mv, jnp.asarray(p.permute(b[:, 0])), maxiter=800, tol=1e-6)
    assert int(res_blk.iters) < int(res_0.iters)


def test_block_lanczos_extremal_eigenvalue(rng):
    m = M.poisson_2d(16, 16)
    p, mm = _block_op(m)
    v0 = rng.standard_normal((m.n_rows, 4)).astype(np.float32)
    al, be = S.block_lanczos(mm, jnp.asarray(_permute_cols(p, v0)), m=20)
    assert al.shape == (20, 4, 4) and be.shape == (20, 4, 4)
    ev = S.block_tridiag_eigvals(al, be)
    dense_ev = np.linalg.eigvalsh(F.csr_to_dense(m))
    assert abs(ev.max() - dense_ev.max()) < 1e-3 * abs(dense_ev.max())


def test_hmep_hamiltonian_lanczos(rng):
    """The paper's HMEp use case: extremal eigenvalue of a (symmetrised)
    Holstein-Hubbard-like Hamiltonian via Lanczos over pJDS spMVM."""
    m = M.hmep(scale=0.0002)
    # symmetrise: (A + A^T)/2 so Lanczos applies
    d = F.csr_to_dense(m)
    d = (d + d.T) / 2
    m = F.csr_from_dense(d)
    p, mv = _op(m)
    v0 = jnp.asarray(p.permute(rng.standard_normal(m.n_rows).astype(np.float32)))
    al, be = S.lanczos(mv, v0, m=80)
    ev = S.tridiag_eigvals(al, be)
    dense_ev = np.linalg.eigvalsh(d)
    assert abs(ev.max() - dense_ev.max()) < 5e-3 * max(abs(dense_ev).max(), 1)

# --------------------------------------------------------------------------
# repro.solve front door: fused/composed parity, refinement, the result
# contract, and the distributed leg of the parity grid
# --------------------------------------------------------------------------
import json
import os
import subprocess
import sys
import textwrap

import pytest

import repro
from repro import api
from repro.core.operator import operator

# 17x19 grids: 323 rows, not divisible by any tile height — both the
# fused kernel's slab epilogue and the composed path must mask the ragged
# tail identically
_PARITY_CASES = [
    ("cg", lambda: M.poisson_2d(17, 19)),
    ("bicgstab", lambda: M.convection_poisson(17, 19, beta=0.4)),
]


def _true_residual(m, x, b):
    d = F.csr_to_dense(m).astype(np.float64)
    return float(np.linalg.norm(d @ np.asarray(x, np.float64) - b)
                 / np.linalg.norm(b))


@pytest.mark.parametrize("method,mk", _PARITY_CASES,
                         ids=[c[0] for c in _PARITY_CASES])
def test_fused_composed_parity_device(method, mk, rng):
    """The fused spMV+dots iteration and the composed operator body are
    the same algorithm: same convergence, same solution."""
    m = mk()
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    bj = jnp.asarray(b)
    op = operator(m, format="sell")
    fused = api._one_solve(op, bj, method=method, strategy="fused",
                           maxiter=3000, tol=1e-7, precond=None)
    comp = api._one_solve(op, bj, method=method, strategy="composed",
                          maxiter=3000, tol=1e-7, precond=None)
    assert fused.info["strategy"] == "fused"
    assert comp.info["strategy"] == "composed"
    assert _true_residual(m, fused.x, b) < 1e-5
    assert _true_residual(m, comp.x, b) < 1e-5
    scale = max(np.abs(np.asarray(comp.x)).max(), 1e-30)
    assert np.abs(np.asarray(fused.x) - np.asarray(comp.x)).max() \
        / scale < 1e-4


@pytest.mark.parametrize("method,mk", _PARITY_CASES,
                         ids=[c[0] for c in _PARITY_CASES])
def test_refined_bf16_matches_f32_device(method, mk, rng):
    """bf16 inner iterations + f32 residual correction land on the same
    answer as the all-f32 solve, at the same tolerance."""
    m = mk()
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    bj = jnp.asarray(b)
    r32 = repro.solve(m, bj, method=method, tol=1e-6, maxiter=3000,
                      tune="off", refine=False)
    rref = repro.solve(m, bj, method=method, tol=1e-6, maxiter=3000,
                       tune="off", dtype=jnp.bfloat16, refine="auto")
    assert bool(r32.converged) and bool(rref.converged)
    assert _true_residual(m, r32.x, b) < 1e-5
    assert _true_residual(m, rref.x, b) < 1e-5
    rounds = rref.info["refine"]["rounds"]
    assert len(rounds) >= 1
    assert rref.info["refine"]["inner_dtype"] == "bfloat16"


def test_solve_result_contract(rng):
    """Every method returns the SAME result type with the same fields
    populated — the point of collapsing the per-solver NamedTuples."""
    m = M.poisson_2d(12, 14)                 # 168 rows, also non-divisible
    b1 = jnp.asarray(rng.standard_normal(m.n_rows).astype(np.float32))
    bk = jnp.asarray(rng.standard_normal((m.n_rows, 3)).astype(np.float32))
    for method, rhs in (("cg", b1), ("bicgstab", b1), ("block_cg", bk)):
        res = repro.solve(m, rhs, method=method, tol=1e-6, maxiter=2000,
                          tune="off", refine=False)
        assert isinstance(res, S.SolveResult)
        assert res.method == method
        assert res.x.shape == rhs.shape
        # residual: scalar for 1-D solves (possibly a certified host
        # float from the fused driver), per-column (k,) for block_cg
        assert np.shape(res.residual) == (() if rhs.ndim == 1 else (3,))
        assert bool(res.converged)
        assert 0 < int(res.iters) <= 2000
        assert res.info["strategy"] in ("fused", "composed")
        assert {"tune", "build", "solve"} <= set(res.info["phase_s"])


def test_solve_rejects_bad_arguments(rng):
    m = M.poisson_2d(8, 8)
    b = jnp.asarray(rng.standard_normal(m.n_rows).astype(np.float32))
    with pytest.raises(ValueError, match="method"):
        repro.solve(m, b, method="gmres")
    with pytest.raises(ValueError, match="shape"):
        repro.solve(m, b, method="block_cg")
    with pytest.raises(ValueError, match="refine"):
        repro.solve(m, jnp.stack([b, b], axis=1), method="block_cg",
                    refine=True)
    with pytest.raises(ValueError, match="closure"):
        op = operator(m)
        repro.solve(op.matvec, b, refine=True)


_DIST_SCRIPT = textwrap.dedent("""
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
    mesh = make_host_mesh(8)
    rng = np.random.default_rng(0)
    cases = [("cg", M.poisson_2d(17, 19)),
             ("bicgstab", M.convection_poisson(17, 19, beta=0.4))]
    for method, m in cases:
        dist = D.partition_csr(m, 8, b_r=32)
        b = np.zeros(dist.n_global_pad, np.float32)
        b[:m.n_rows] = rng.standard_normal(m.n_rows)
        bj = jax.device_put(jnp.asarray(b),
                            jax.NamedSharding(mesh, P("data")))
        op = dist_operator(dist, mesh, mode="overlap")
        dense = F.csr_to_dense(m).astype(np.float64)
        bn = np.linalg.norm(b[:m.n_rows])
        res = repro.solve(op, bj, method=method, maxiter=4000, tol=1e-6)
        x = np.asarray(res.x, np.float64)[:m.n_rows]
        out[f"{method}_true"] = float(
            np.linalg.norm(dense @ x - b[:m.n_rows]) / bn)
        out[f"{method}_strategy"] = res.info["strategy"]
        resr = repro.solve(op, bj, method=method, maxiter=4000, tol=1e-6,
                           refine=True)
        xr = np.asarray(resr.x, np.float64)[:m.n_rows]
        out[f"{method}_true_refined"] = float(
            np.linalg.norm(dense @ xr - b[:m.n_rows]) / bn)
        out[f"{method}_rounds"] = len(resr.info["refine"]["rounds"])
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def dist_solve_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _DIST_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.dist
@pytest.mark.parametrize("method", ["cg", "bicgstab"])
def test_solve_distributed_parity(dist_solve_results, method):
    """The Dist column of the parity grid: repro.solve over the mesh
    operator (composed strategy — fused is single-device) reaches the
    f32 tolerance, plain and bf16-refined."""
    out = dist_solve_results
    assert out[f"{method}_strategy"] == "composed"
    assert out[f"{method}_true"] < 1e-5
    assert out[f"{method}_true_refined"] < 1e-5
    assert out[f"{method}_rounds"] >= 1


# --------------------------------------------------------------------------
# Failure taxonomy: breakdown detection and the NaN-masking regression
# --------------------------------------------------------------------------
def _csr_op(a):
    return operator(F.csr_from_dense(np.asarray(a, np.float32)), b_r=32)


def _singular(rng, n=24):
    """Rank-deficient PSD: B B^T with a thin B — random b is outside the
    range, so the Krylov recurrence must break down, not converge."""
    bm = rng.standard_normal((n, n // 2))
    return bm @ bm.T


def _indefinite(rng, n=24):
    a = rng.standard_normal((n, n))
    a = (a + a.T) / 2
    np.fill_diagonal(a, np.linspace(-2.0, 2.0, n))   # eigenvalues both signs
    return a


def _skew(rng, n=24):
    a = rng.standard_normal((n, n))
    return a - a.T                                    # x^T A x == 0 for all x


def test_nan_residual_is_not_converged_composed(rng):
    """Regression: a NaN residual must flag non_finite, never satisfy
    the convergence predicate (NaN > tol*tol is False — the old
    ``_not_done`` read that as done)."""
    n = 24
    a = np.eye(n)
    a[3, 3] = np.nan
    op = _csr_op(a)
    b = rng.standard_normal(n).astype(np.float32)
    res = S.cg(op, b, maxiter=50, tol=1e-6)
    assert res.status == "non_finite"
    assert not bool(res.converged)
    res = S.bicgstab(op, b, maxiter=50, tol=1e-6)
    assert res.status == "non_finite"
    assert not bool(res.converged)


def test_nan_residual_is_not_converged_fused(rng):
    m = M.poisson_2d(6, 6)
    data = np.asarray(m.data)
    saved = data[0]
    data[0] = np.nan
    try:
        ops.clear_device_cache()
        res = api.solve(m, rng.standard_normal(m.n_rows).astype(np.float32),
                        tune="off", fallback="off")
    finally:
        data[0] = saved
        ops.clear_device_cache()
    assert res.info["strategy"] == "fused"
    assert res.status == "non_finite"
    assert not bool(res.converged)


def test_probe_contract_ignores_failure_detection(rng):
    """tol <= 0 is the tuner/bench fixed-length probe: it must run to
    exactly maxiter with no breakdown/divergence exits."""
    op = _csr_op(_indefinite(rng))
    b = rng.standard_normal(24).astype(np.float32)
    res = S.cg(op, b, maxiter=37, tol=0.0)
    assert int(res.iters) == 37
    assert res.status == "maxiter"


@pytest.mark.parametrize("mk,expected", [
    (_singular, ("breakdown", "diverged")),
    (_indefinite, ("breakdown", "diverged")),
    (_skew, ("breakdown",)),
])
def test_cg_breakdown_taxonomy(mk, expected, rng):
    a = mk(rng)
    op = _csr_op(a)
    b = rng.standard_normal(a.shape[0]).astype(np.float32)
    res = S.cg(op, b, maxiter=500, tol=1e-8)
    assert res.status in expected, res.status
    assert not bool(res.converged)
    assert np.all(np.isfinite(np.asarray(res.x)))


@pytest.mark.parametrize("mk", [_singular, _skew])
def test_bicgstab_breakdown_taxonomy(mk, rng):
    a = mk(rng)
    op = _csr_op(a)
    b = rng.standard_normal(a.shape[0]).astype(np.float32)
    res = S.bicgstab(op, b, maxiter=500, tol=1e-8)
    # typed, never a false converged claim
    assert res.status in ("breakdown", "diverged", "non_finite", "maxiter")
    if res.status == "maxiter":
        assert float(res.residual) > 1e-8


@pytest.mark.parametrize("mk", [_singular, _indefinite, _skew])
def test_block_cg_breakdown_taxonomy(mk, rng):
    a = mk(rng)
    op = _csr_op(a)
    b = rng.standard_normal((a.shape[0], 3)).astype(np.float32)
    res = S.block_cg(op, b, maxiter=500, tol=1e-8)
    assert res.status in ("breakdown", "diverged", "non_finite")
    assert not bool(res.converged)
    assert np.all(np.isfinite(np.asarray(res.x)))


def test_breakdown_statuses_survive_solve_front_door(rng):
    """repro.solve with the ladder: an indefinite system fails every
    rung and surfaces a typed SolveFailure whose ladder names them."""
    a = _indefinite(rng)
    m = F.csr_from_dense(np.asarray(a, np.float32))
    b = rng.standard_normal(a.shape[0]).astype(np.float32)
    with pytest.raises(repro.SolveFailure) as ei:
        repro.solve(m, b, tune="off", maxiter=500)
    assert all(e.get("status") in ("breakdown", "diverged", "non_finite")
               for e in ei.value.ladder)


_DIST_BREAKDOWN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import repro
    from repro.core import formats as F
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(8)
    rng = np.random.default_rng(0)
    n = 96
    bm = rng.standard_normal((n, n // 2))
    m = F.csr_from_dense((bm @ bm.T).astype(np.float32))
    op = dist_operator(m, mesh, b_r=8)
    b = np.zeros(op.dist.n_global_pad, np.float32)
    b[:n] = rng.standard_normal(n)
    bj = jax.device_put(jnp.asarray(b), jax.NamedSharding(mesh, P("data")))
    res = repro.solve(op, bj, maxiter=500, tol=1e-8, tune="off",
                      fallback="off")
    print(json.dumps({"status": res.status,
                      "converged": bool(res.converged)}))
""")


@pytest.mark.dist
def test_breakdown_detected_on_dist_operator():
    """The same breakdown taxonomy holds through the mesh-distributed
    operator (singular PSD system, rows padded and sharded)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _DIST_BREAKDOWN_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["status"] in ("breakdown", "diverged")
    assert not out["converged"]


# ---------------------------------------------------------------------------
# Refinement divergence guard: a stalled or poisoned refinement is a
# typed failure the ladder escalates off, not max_rounds of nothing
# ---------------------------------------------------------------------------
def test_refinement_guard_reason_codes():
    b = jnp.ones(8, jnp.float32)
    residual_of = lambda x: b - x           # A = I

    x, rn, rounds, reason = S.iterative_refinement(
        residual_of, lambda r: (r, 1, 0.0), b)
    assert reason == "converged" and rn <= 1e-6

    # a zero correction leaves the residual exactly where it was: one
    # wasted round, then the guard calls it, not max_rounds of them
    x, rn, rounds, reason = S.iterative_refinement(
        residual_of, lambda r: (jnp.zeros_like(r), 1, 1.0), b)
    assert reason == "stalled" and len(rounds) == 1

    x, rn, rounds, reason = S.iterative_refinement(
        residual_of, lambda r: (jnp.full_like(r, jnp.nan), 1, 1.0), b)
    assert reason == "non_finite"


def test_refined_stall_is_typed_and_escalates_to_f32(rng, monkeypatch):
    import repro
    m = M.poisson_2d(8, 8)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    orig = S.iterative_refinement

    def stalling(residual_of, inner, b_, **kw):
        # the inner solve never improves anything — the way a matrix
        # too ill-conditioned for bf16 values surfaces
        return orig(residual_of,
                    lambda r: (jnp.zeros_like(r), 1, 1.0), b_, **kw)

    monkeypatch.setattr(S, "iterative_refinement", stalling)

    res = repro.solve(m, b, dtype="bfloat16", refine="auto", tune="off",
                      fallback="off")
    assert res.status == "diverged"
    assert res.diagnostics["refine_reason"] == "stalled"

    res = repro.solve(m, b, dtype="bfloat16", refine="auto", tune="off",
                      fallback="auto")
    assert res.status == "converged"
    assert res.diagnostics["certified"]
    entries = {e["rung"]: e.get("status") for e in res.info["ladder"]}
    assert entries.get("bf16->f32") == "converged"
    assert all(s == "diverged" for r, s in entries.items()
               if r != "bf16->f32")


def test_kernel_to_ref_rung_warns_with_the_kernel_error(rng):
    # the ref path is a different program: a solve that falls back to it
    # must say so, carrying the error the kernel path raised
    import repro
    from repro.testing import faults
    m = M.poisson_2d(8, 8)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    with faults.fail_kernel_backend():
        with pytest.warns(RuntimeWarning, match="injected kernel-launch"):
            res = repro.solve(m, b, tune="off", backend="kernel",
                              fallback="auto")
    assert res.status == "converged"
    assert "kernel->ref" in [e["rung"] for e in res.info["ladder"]]
