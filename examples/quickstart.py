"""Quickstart: wrap a sparse matrix as a SparseOperator, run y = A x.

The operator protocol (DESIGN.md §8) hides storage format, permutation
and padding: ``operator(m) @ x`` picks a format from row-length
statistics, converts once, and computes in the original basis.  The
same object gives the transpose (``op.T``) and gradients for free.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import formats as F, matrices as M, perf_model as PM
from repro.core.operator import operator


def main():
    # 1. A sparse matrix with strongly varying row lengths (sAMG analogue)
    m = M.samg(scale=0.002)
    print(f"matrix: {m.shape}, nnz={m.nnz}, N_nzr={m.n_nzr:.1f}")

    # 2. Storage: ELLPACK pads to the global max row length; pJDS sorts
    #    rows and pads per 128-row block (paper Fig. 1)
    ell = F.csr_to_ell(m, row_align=128)
    pjds = F.csr_to_pjds(m, b_r=128)
    print(f"ELLPACK stored elements: {F.storage_elements(ell):>10,}")
    print(f"pJDS    stored elements: {F.storage_elements(pjds):>10,}")
    print(f"data reduction: {100 * F.data_reduction_vs_ellpack(m):.1f}% "
          "(paper Table 1 measured 19-71% on its matrices)")

    # 3. The one-line API: a SparseOperator.  format="auto" prices the
    #    candidates (DESIGN.md §5) and backend="auto" picks kernel/ref.
    op = operator(m)
    print(f"operator(m) chose format={op.fmt!r}, shape={op.shape}")

    rng = np.random.default_rng(0)
    x = rng.standard_normal(m.shape[0]).astype(np.float32)
    y = np.asarray(op @ x)                       # original basis, y = A x
    y_ref = np.array([x[m.indices[m.indptr[i]:m.indptr[i + 1]]]
                      @ m.data[m.indptr[i]:m.indptr[i + 1]]
                      for i in range(m.n_rows)])
    print(f"max |op @ x - y_ref| = {np.abs(y - y_ref).max():.2e}")

    # 4. The transpose view costs nothing to build: blocked formats run
    #    A^T x as a scatter-accumulate over the same stored indices
    yt = np.asarray(op.T @ y_ref)
    yt_ref = F.csr_to_dense(m).T @ y_ref
    scale = max(np.abs(yt_ref).max(), 1.0)
    print(f"rel max |op.T @ y - ref| = "
          f"{np.abs(yt - yt_ref).max() / scale:.2e}")

    # 5. And it is differentiable: jax.grad flows through the stored
    #    values (op.with_values) and through x — d(w.Ax)/dx = A^T w
    w = rng.standard_normal(m.shape[0]).astype(np.float32)
    gx = jax.grad(lambda v: jnp.vdot(jnp.asarray(w), op @ v))(jnp.asarray(x))
    print(f"grad wrt x == A^T w: max err = "
          f"{np.abs(np.asarray(gx) - F.csr_to_dense(m).T @ w).max():.2e}")

    # 6. What the paper's model says about this matrix on an accelerator
    lo, hi = PM.alpha_range(m.n_nzr)
    thresh = PM.n_nzr_upper_for_link_penalty(
        PM.TPU_V5E.hbm_bw, PM.TPU_V5E.ici_bw, alpha=lo)
    print(f"Eq.3 threshold N_nzr <= {thresh:.0f}: this matrix "
          f"(N_nzr={m.n_nzr:.0f}) is "
          + ("LINK-DOMINATED -> keep it resident, avoid host traffic"
             if m.n_nzr < thresh else "compute-worthy"))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
