"""SparseOperator protocol: conformance, transpose, autodiff, solvers.

The acceptance surface of the operator redesign (DESIGN.md §8):

* a conformance suite every implementation must pass — shapes, matvec /
  matmat / rmatvec / rmatmat against the dense reference, lazy ``.T``,
  pytree round-trips, jit and ``lax.while_loop`` carriers;
* property tests ``A.T @ x == dense.T @ x`` and ``jax.grad`` (through
  stored values AND x) vs the dense gradient, across every format;
* ONE solver source running unmodified on a single-device operator and
  on a distributed mesh operator (the mesh half runs in a subprocess
  with 8 host devices, like the other distributed tests);
* the new solvers: Jacobi-preconditioned CG and BiCGStab (whose dual
  ``A^T y = c`` solve exercises ``rmatvec`` through ``op.T``).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import formats as F, matrices as M, solvers as S
from repro.core.operator import (DeviceOperator, TransposeOperator, operator)
from repro.kernels import ops

B_R = 32
FORMATS = ["csr", "ellpack_r", "pjds", "sell", "wsell", "cmrs"]


def _b_r(fmt):
    """The row-block width each format is built at: the windowed SELL
    takes only whole 128-lane blocks."""
    return 128 if fmt == "wsell" else B_R


def _random_sparse(rng, n_rows, n_cols, density=0.1):
    a = ((rng.random((n_rows, n_cols)) < density)
         * rng.standard_normal((n_rows, n_cols))).astype(np.float32)
    return a


def _scaled_close(got, want, atol=1e-5):
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, atol=atol)


# --------------------------------------------------------------------------
# Conformance suite (single-device; the Dist half runs in the subprocess)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("transpose", ["ref", "device"])
def test_conformance_device_operator(rng, fmt, transpose):
    a = _random_sparse(rng, 96, 160)
    m = F.csr_from_dense(a)
    op = operator(m, format=fmt, b_r=_b_r(fmt), transpose=transpose)

    assert op.shape == (96, 160)
    assert op.dtype == np.float32
    assert op.fmt == fmt
    assert isinstance(op.T, TransposeOperator)
    assert op.T.shape == (160, 96)
    assert op.T.T is op                      # lazy view collapses

    x = rng.standard_normal(160).astype(np.float32)
    y = rng.standard_normal(96).astype(np.float32)
    xs = rng.standard_normal((160, 4)).astype(np.float32)
    ys = rng.standard_normal((96, 3)).astype(np.float32)

    _scaled_close(np.asarray(op @ x), a @ x)
    _scaled_close(np.asarray(op.matvec(x)), a @ x)
    _scaled_close(np.asarray(op @ xs), a @ xs)
    _scaled_close(np.asarray(op.T @ y), a.T @ y)
    _scaled_close(np.asarray(op.rmatvec(y)), a.T @ y)
    _scaled_close(np.asarray(op.T @ ys), a.T @ ys)
    _scaled_close(np.asarray(op.rmatmat(ys)), a.T @ ys)


@pytest.mark.parametrize("fmt", FORMATS)
def test_operator_is_pytree_and_jit_carrier(rng, fmt):
    a = _random_sparse(rng, 96, 96)
    m = F.csr_from_dense(a)
    op = operator(m, format=fmt, b_r=_b_r(fmt))
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))

    # flatten/unflatten round-trip preserves behaviour
    leaves, treedef = jax.tree_util.tree_flatten(op)
    assert all(isinstance(l, (jax.Array, np.ndarray)) for l in leaves)
    op2 = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(np.asarray(op @ x), np.asarray(op2 @ x))

    # operators pass through jit as arguments...
    y_jit = jax.jit(lambda o, v: o @ v)(op, x)
    _scaled_close(np.asarray(y_jit), a @ np.asarray(x))

    # ...and ride lax.while_loop carriers (the solver use case)
    def body(state):
        o, v, k = state
        return o, o @ v, k + 1

    _, y3, _ = jax.lax.while_loop(lambda s: s[2] < 3, body,
                                  (op, x, jnp.int32(0)))
    want = a @ (a @ (a @ np.asarray(x)))
    _scaled_close(np.asarray(y3), want, atol=1e-4)

    # the transpose view is a pytree too
    yt = jax.jit(lambda o, v: o @ v)(op.T, x)
    _scaled_close(np.asarray(yt), a.T @ np.asarray(x))


def test_operator_factory_idempotent_and_shares_cache(rng):
    m = F.csr_from_dense(_random_sparse(rng, 96, 96))
    op = operator(m, b_r=B_R)
    assert operator(op) is op
    # the device representation comes from the as_device cache
    assert op.dev is ops.as_device(m, "auto", b_r=B_R)
    # wrapping an existing SparseDevice
    op2 = operator(op.dev)
    assert isinstance(op2, DeviceOperator) and op2.dev is op.dev
    with pytest.raises(ValueError):
        operator(op.dev, format="csr" if op.dev.fmt != "csr" else "pjds")


# --------------------------------------------------------------------------
# Transpose + autodiff property tests
# --------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2 ** 16), fmt=st.sampled_from(FORMATS))
def test_transpose_matches_dense(seed, fmt):
    rng = np.random.default_rng(seed)
    n, c = rng.integers(40, 200), rng.integers(40, 200)
    a = _random_sparse(rng, int(n), int(c))
    op = operator(F.csr_from_dense(a), format=fmt, b_r=_b_r(fmt))
    y = rng.standard_normal(int(n)).astype(np.float32)
    _scaled_close(np.asarray(op.T @ y), a.T @ y)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_grad_wrt_x_matches_dense(rng, fmt, backend):
    """Acceptance: jax.grad through operator.matvec == dense grad @1e-5.
    The kernel backend differentiates through the custom_vjp (the Pallas
    kernels themselves have no transpose rule)."""
    if fmt == "csr" and backend == "kernel":
        pytest.skip("csr has no kernel")
    a = _random_sparse(rng, 96, 96)
    op = operator(F.csr_from_dense(a), format=fmt, b_r=_b_r(fmt),
                  backend=backend)
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    w = rng.standard_normal(96).astype(np.float32)
    gx = jax.grad(lambda v: jnp.vdot(jnp.asarray(w), op @ v))(x)
    _scaled_close(np.asarray(gx), a.T @ w, atol=1e-5)


@pytest.mark.parametrize("fmt", FORMATS)
def test_jvp_through_operator(rng, fmt):
    """Forward mode works too (the derivative rule is a custom_jvp, so
    spmv keeps the jvp support the plain ref path had)."""
    a = _random_sparse(rng, 96, 96)
    op = operator(F.csr_from_dense(a), format=fmt, b_r=_b_r(fmt))
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    dx = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    y, y_dot = jax.jvp(lambda v: op @ v, (x,), (dx,))
    _scaled_close(np.asarray(y), a @ np.asarray(x))
    _scaled_close(np.asarray(y_dot), a @ np.asarray(dx))


@pytest.mark.parametrize("fmt", FORMATS)
def test_grad_wrt_values_linearity(rng, fmt):
    """y is LINEAR in the stored values, so the value-gradient satisfies
    <grad, u> == loss(op.with_values(u) @ x) exactly — an independent
    check that d(Ax)/d(val) reuses the forward gather structure."""
    a = _random_sparse(rng, 96, 96)
    op = operator(F.csr_from_dense(a), format=fmt, b_r=_b_r(fmt))
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(96).astype(np.float32))

    def loss(v):
        return jnp.vdot(w, op.with_values(v) @ x)

    gv = jax.grad(loss)(op.values)
    assert gv.shape == op.values.shape and gv.dtype == op.values.dtype
    u = jnp.asarray(rng.standard_normal(op.values.shape).astype(np.float32))
    got = float(jnp.vdot(gv, u))
    want = float(loss(u))
    assert abs(got - want) <= 1e-3 * max(abs(want), 1.0)


def test_grad_wrt_values_matches_dense_pattern(rng):
    """For CSR the value stream maps 1:1 to (row, col) pairs, so the
    value-gradient must equal the dense gradient g x^T sampled at the
    sparsity pattern."""
    a = _random_sparse(rng, 64, 64)
    m = F.csr_from_dense(a)
    op = operator(m, format="csr", b_r=B_R)
    x = rng.standard_normal(64).astype(np.float32)
    w = rng.standard_normal(64).astype(np.float32)
    gv = jax.grad(lambda v: jnp.vdot(jnp.asarray(w),
                                     op.with_values(v) @ jnp.asarray(x)))(
        op.values)
    rows = np.repeat(np.arange(m.n_rows), m.row_lengths())
    want = w[rows] * x[m.indices]            # (g x^T)[row, col] per nnz
    _scaled_close(np.asarray(gv), want.astype(np.float32), atol=1e-5)


def test_sparse_ffn_trainable_end_to_end(rng):
    """jax.grad flows through a SparseLinear (operator-backed) layer:
    grad wrt the input matches the pruned-dense reference."""
    from repro.sparse.sparse_ffn import SparseLinear
    w = rng.standard_normal((64, 96)).astype(np.float32)
    sl = SparseLinear.from_dense(w, density=0.2, b_r=B_R)
    x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
    wp = np.asarray(jax.device_get(
        sl.with_values(sl.values)(jnp.eye(64, dtype=jnp.float32))))

    def loss(xx):
        return jnp.sum(sl(xx) ** 2)

    gx = jax.grad(loss)(x)
    y = np.asarray(sl(x))
    want = 2 * y @ wp.T                      # d sum(y^2) / dx = 2 y W_p^T
    _scaled_close(np.asarray(gx), want, atol=1e-4)

    # and wrt the stored values (the fine-tuning handle): linearity of y
    gv = jax.grad(lambda v: jnp.sum(sl.with_values(v)(x)))(sl.values)
    u = jnp.asarray(rng.standard_normal(gv.shape).astype(np.float32))
    got = float(jnp.vdot(gv, u))
    want_dir = float(jnp.sum(sl.with_values(u)(x)))
    assert abs(got - want_dir) <= 1e-3 * max(abs(want_dir), 1.0)


# --------------------------------------------------------------------------
# Diagonal + preconditioned / non-symmetric solvers on the protocol
# --------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FORMATS)
def test_diagonal_matches_dense(rng, fmt):
    a = _random_sparse(rng, 96, 96, density=0.15)
    np.fill_diagonal(a, rng.standard_normal(96).astype(np.float32))
    op = operator(F.csr_from_dense(a), format=fmt, b_r=_b_r(fmt))
    np.testing.assert_allclose(np.asarray(op.diagonal()), np.diag(a),
                               atol=1e-6)


@pytest.mark.parametrize("backend", ["ref", "kernel"])
def test_pjds_is_sell_with_sigma_all_rows(rng, backend):
    """pJDS is SELL-C-sigma with sigma equal to the padded rows: built
    from one matrix, the two operators apply bit for bit alike."""
    a = _random_sparse(rng, 96, 96, density=0.15)
    m = F.csr_from_dense(a)
    pjds = operator(m, format="pjds", b_r=B_R, backend=backend)
    sell = operator(m, format="sell", b_r=B_R, backend=backend,
                    sigma=-(-96 // B_R) * B_R)
    x = jnp.asarray(rng.standard_normal(96).astype(np.float32))
    xs = jnp.asarray(rng.standard_normal((96, 4)).astype(np.float32))
    for apply in (lambda o: o.matvec(x), lambda o: o.matmat(xs),
                  lambda o: o.rmatvec(x), lambda o: o.diagonal()):
        np.testing.assert_array_equal(np.asarray(apply(pjds)),
                                      np.asarray(apply(sell)))


def test_jacobi_pcg_beats_plain_cg(rng):
    """On an SPD system with a wildly varying diagonal the Jacobi
    preconditioner collapses the condition number — same cg() source."""
    m = M.poisson_2d(24, 24)
    s = (10.0 ** rng.uniform(-1.5, 1.5, m.n_rows)).astype(np.float32)
    d = F.csr_to_dense(m)
    a = (s[:, None] * d * s[None, :]).astype(np.float32)
    op = operator(F.csr_from_dense(a), b_r=B_R)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    plain = S.cg(op, jnp.asarray(b), maxiter=20000, tol=1e-6)
    pre = S.cg(op, jnp.asarray(b), maxiter=20000, tol=1e-6, M="jacobi")
    assert float(pre.residual) < 1e-5
    assert int(pre.iters) * 10 < int(plain.iters)
    x = np.asarray(pre.x)
    err = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
    assert err < 1e-4


def test_bicgstab_nonsymmetric(rng):
    mn = M.convection_poisson(32, 32)
    a = F.csr_to_dense(mn).astype(np.float64)
    op = operator(mn, b_r=B_R)
    b = rng.standard_normal(mn.n_rows).astype(np.float32)
    res = S.bicgstab(op, jnp.asarray(b), maxiter=2000, tol=1e-8)
    x = np.asarray(res.x, np.float64)
    err = np.linalg.norm(a @ x - b) / np.linalg.norm(b)
    assert err < 1e-4
    # CG has no business converging here; BiCGStab is the first solver
    # in the repo that does.
    assert int(res.iters) < 2000


def test_bicgstab_dual_system_via_transpose_view(rng):
    """The dual residual check: solve A^T y = c by passing op.T — the
    rmatvec path — and verify against the dense transpose solve."""
    mn = M.convection_poisson(32, 32)
    a = F.csr_to_dense(mn).astype(np.float64)
    op = operator(mn, b_r=B_R)
    c = rng.standard_normal(mn.n_rows).astype(np.float32)
    res = S.bicgstab(op.T, jnp.asarray(c), maxiter=2000, tol=1e-8)
    y = np.asarray(res.x, np.float64)
    err = np.linalg.norm(a.T @ y - c) / np.linalg.norm(c)
    assert err < 1e-4
    # dual residual of the primal solve: r_dual = c - A^T y ~ 0 links the
    # two systems; recompute it through rmatvec to cross-check op.T
    r_dual = np.asarray(op.rmatvec(jnp.asarray(y.astype(np.float32))))
    _scaled_close(r_dual, (a.T @ y).astype(np.float32), atol=1e-4)


def test_solver_source_runs_on_device_operator(rng):
    """The single-device half of the acceptance criterion: the SAME
    S.cg / S.block_cg / S.bicgstab sources also run on DistOperator in
    the subprocess suite below."""
    m = M.poisson_2d(20, 20)
    op = operator(m, b_r=B_R)
    b = rng.standard_normal(m.n_rows).astype(np.float32)
    res = S.cg(op, jnp.asarray(b), maxiter=1500, tol=1e-7)
    a = F.csr_to_dense(m)
    err = np.linalg.norm(a @ np.asarray(res.x) - b) / np.linalg.norm(b)
    assert err < 1e-4
    bk = rng.standard_normal((m.n_rows, 4)).astype(np.float32)
    bres = S.block_cg(op, jnp.asarray(bk), maxiter=1500, tol=1e-7)
    assert float(np.max(np.asarray(bres.residual))) < 1e-5


# --------------------------------------------------------------------------
# Distributed conformance + solver parity (subprocess, 8 host devices)
# --------------------------------------------------------------------------
_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import formats as F, matrices as M, solvers as S
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh

    out = {}
    mesh = make_host_mesh(8)
    rng = np.random.default_rng(0)

    # non-symmetric convection-diffusion system (BiCGStab + transpose)
    m = M.poisson_2d(40, 40)
    mn = M.convection_poisson(40, 40, beta=0.5)
    dense = F.csr_to_dense(mn).astype(np.float64)

    op = dist_operator(mn, mesh, b_r=32)
    n_pad = op.shape[0]
    sh = jax.NamedSharding(mesh, P("data"))

    x = np.zeros(n_pad, np.float32); x[:m.n_rows] = rng.standard_normal(m.n_rows)
    xj = jax.device_put(jnp.asarray(x), sh)
    scale = float(np.abs(dense @ x[:m.n_rows]).max())
    out["err_mv"] = float(np.abs(np.asarray(op @ xj)[:m.n_rows]
                                 - dense @ x[:m.n_rows]).max() / scale)
    out["err_rmv"] = float(np.abs(np.asarray(op.T @ xj)[:m.n_rows]
                                  - dense.T @ x[:m.n_rows]).max() / scale)
    g = jax.grad(lambda v: jnp.vdot(xj, op.matvec(v)))(xj)
    out["err_grad_x"] = float(np.abs(np.asarray(g)[:m.n_rows]
                                     - dense.T @ x[:m.n_rows]).max() / scale)
    X = np.zeros((n_pad, 4), np.float32)
    X[:m.n_rows] = rng.standard_normal((m.n_rows, 4))
    Xj = jax.device_put(jnp.asarray(X), jax.NamedSharding(mesh, P("data", None)))
    out["err_mm"] = float(np.abs(np.asarray(op @ Xj)[:m.n_rows]
                                 - dense @ X[:m.n_rows]).max() / scale)
    out["err_diag"] = float(np.abs(np.asarray(op.diagonal())[:m.n_rows]
                                   - np.diag(dense)).max())

    # ONE solver source on the mesh operator: cg (on the SPD system),
    # jacobi-pcg, block-cg, bicgstab (non-symmetric), bicgstab on op.T
    sym = dist_operator(m, mesh, b_r=32)
    b = np.zeros(n_pad, np.float32); b[:m.n_rows] = rng.standard_normal(m.n_rows)
    bj = jax.device_put(jnp.asarray(b), sh)
    dsym = F.csr_to_dense(m).astype(np.float64)
    res = S.cg(sym, bj, maxiter=2000, tol=1e-6)
    out["cg_err"] = float(np.linalg.norm(
        dsym @ np.asarray(res.x, np.float64)[:m.n_rows] - b[:m.n_rows])
        / np.linalg.norm(b[:m.n_rows]))
    res_j = S.cg(sym, bj, maxiter=2000, tol=1e-6, M="jacobi")
    out["pcg_err"] = float(np.linalg.norm(
        dsym @ np.asarray(res_j.x, np.float64)[:m.n_rows] - b[:m.n_rows])
        / np.linalg.norm(b[:m.n_rows]))
    Bj = jax.device_put(jnp.asarray(X), jax.NamedSharding(mesh, P("data", None)))
    bres = S.block_cg(sym, Bj, maxiter=2000, tol=1e-6)
    out["block_cg_res"] = float(np.max(np.asarray(bres.residual)))
    nres = S.bicgstab(op, bj, maxiter=2000, tol=1e-8)
    out["bicgstab_err"] = float(np.linalg.norm(
        dense @ np.asarray(nres.x, np.float64)[:m.n_rows] - b[:m.n_rows])
        / np.linalg.norm(b[:m.n_rows]))
    tres = S.bicgstab(op.T, bj, maxiter=2000, tol=1e-8)
    out["bicgstab_T_err"] = float(np.linalg.norm(
        dense.T @ np.asarray(tres.x, np.float64)[:m.n_rows] - b[:m.n_rows])
        / np.linalg.norm(b[:m.n_rows]))

    # serve-layer consumer: SolveEngine batches RHS against the mesh op
    from repro.serve.engine import SolveEngine, SolveRequest
    eng = SolveEngine(sym, slots=4, maxiter=2000, tol=1e-6)
    reqs = [SolveRequest(rid=i, b=np.asarray(X[:, i % 4])) for i in range(6)]
    eng.run(reqs)
    out["serve_done"] = int(sum(r.done for r in reqs))
    out["serve_res"] = float(max(r.residual for r in reqs))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def dist_op_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _DIST_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.dist
def test_dist_conformance(dist_op_results):
    for k in ("err_mv", "err_rmv", "err_grad_x", "err_mm"):
        assert dist_op_results[k] < 1e-5, (k, dist_op_results[k])
    assert dist_op_results["err_diag"] < 1e-6


@pytest.mark.dist
def test_solver_source_runs_on_dist_operator(dist_op_results):
    """Acceptance: the same cg/block_cg/bicgstab sources that ran on the
    DeviceOperator above converge on the mesh operator."""
    assert dist_op_results["cg_err"] < 1e-4
    assert dist_op_results["pcg_err"] < 1e-4
    assert dist_op_results["block_cg_res"] < 1e-5
    assert dist_op_results["bicgstab_err"] < 1e-4
    assert dist_op_results["bicgstab_T_err"] < 1e-4


def test_solve_engine_serves_dist_operator(dist_op_results):
    assert dist_op_results["serve_done"] == 6
    assert dist_op_results["serve_res"] < 1e-5
