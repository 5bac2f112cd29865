"""Lanczos eigensolver on a Holstein-Hubbard-like Hamiltonian (HMEp).

The paper's motivating workload (§1.3, and 'application of our results
to a production-grade eigensolver' in the outlook): extremal eigenvalues
of a sparse quantum Hamiltonian, where spMVM dominates the runtime.
Since PR 3 the Krylov iteration runs against the SparseOperator
protocol — ``operator(h)`` picks the storage format and keeps every
permutation internal, so the solver sees the original basis end-to-end.
The Ritz estimate is then polished with shift-inverted inverse
iteration, whose inner SPD solves go through ``repro.solve``.

    PYTHONPATH=src python examples/eigensolver.py
"""
import numpy as np
import jax.numpy as jnp

import repro
from repro.core import formats as F, matrices as M, solvers as S
from repro.core.operator import operator


def main():
    raw = M.hmep(scale=0.001)
    # symmetrise (physical Hamiltonians are Hermitian)
    d = F.csr_to_dense(raw)
    h = F.csr_from_dense(((d + d.T) / 2).astype(np.float32))
    print(f"Hamiltonian: {h.shape}, nnz={h.nnz}, N_nzr={h.n_nzr:.1f}")

    print(f"pJDS vs ELLPACK reduction: "
          f"{100 * F.data_reduction_vs_ellpack(h):.1f}%")
    op = operator(h, b_r=128)
    print(f"operator chose format={op.fmt!r}")

    rng = np.random.default_rng(0)
    v0 = jnp.asarray(rng.standard_normal(h.n_rows).astype(np.float32))
    # the operator hides the permuted basis — no permute/unpermute dance
    al, be = S.lanczos(op, v0, m=100)
    ritz = S.tridiag_eigvals(al, be)
    print(f"Lanczos Ritz extremes: lam_min~{ritz.min():.4f} "
          f"lam_max~{ritz.max():.4f}")

    # polish the extremal Ritz value with inverse iteration: for a shift
    # sigma just above lam_max, (sigma*I - H) is SPD, so each inverse-
    # iteration step is a CG solve through the repro.solve front door
    sigma = float(ritz.max()) + 0.02
    dh = F.csr_to_dense(h)
    shifted = operator(
        F.csr_from_dense((sigma * np.eye(h.n_rows, dtype=np.float32) - dh)))
    # warm start: shifted power steps bias v toward the lam_max eigenvector
    v = v0 / jnp.linalg.norm(v0)
    for _ in range(20):
        v = op @ v + 7.0 * v
        v = v / jnp.linalg.norm(v)
    # 1e-4: (sigma*I - H) is near-singular BY DESIGN, so its f32
    # residual floor sits around 1e-5 — far above what the recurrence
    # claims.  Certification (DESIGN.md §11) would demote a 1e-8
    # request to a typed failure; inverse iteration only needs the
    # direction anyway.
    for _ in range(3):
        sol = repro.solve(shifted, v, method="cg", tol=1e-4, maxiter=4000)
        v = sol.x / jnp.linalg.norm(sol.x)
    lam = float(v @ (op @ v))            # Rayleigh quotient, original basis
    print(f"inverse-iteration polish:  lam_max~{lam:.6f} "
          f"(cg iters/step ~{int(sol.iters)})")

    ref = np.linalg.eigvalsh(dh)
    print(f"dense reference:       lam_min={ref.min():.4f} "
          f"lam_max={ref.max():.4f}")
    print(f"extremal eigenvalue error: Lanczos "
          f"{abs(ritz.max() - ref.max()):.2e}, polished "
          f"{abs(lam - ref.max()):.2e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
