"""The plain reference: float64 CSR products on the host, and the
paper's minimum bytes of one spMVM.

Imports nothing of the program under test and uses none of its arrays:
it reads the benchmark's own ``HostMatrix``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse


class HostCSR:
    """y = A x in float64, straight off the benchmark's CSR arrays."""

    def __init__(self, m):
        n = m.n_rows
        self.n = n
        self.a = scipy.sparse.csr_matrix(
            (m.data.astype(np.float64), m.indices, m.indptr), shape=(n, n))

    def matvec(self, x) -> np.ndarray:
        return self.a @ np.asarray(x, np.float64)[: self.n]

    def power_steps(self, x, k: int) -> np.ndarray:
        """k steps of x <- A x / ||A x||."""
        x = np.asarray(x, np.float64)[: self.n]
        for _ in range(k):
            y = self.matvec(x)
            x = y / max(np.linalg.norm(y), 1e-300)
        return x

    def true_residual(self, b, x) -> float:
        """||b - A x|| / ||b||."""
        b = np.asarray(b, np.float64)[: self.n]
        r = b - self.matvec(x)
        return float(np.linalg.norm(r) / max(np.linalg.norm(b), 1e-300))


def max_rel_err(y, y_ref) -> float:
    """max |y - y_ref| / max |y_ref|."""
    y = np.asarray(y, np.float64)[: len(y_ref)]
    if not np.isfinite(y).all():
        return float("inf")
    return float(np.abs(y - y_ref).max() / max(np.abs(y_ref).max(), 1e-300))


def spmvm_min_bytes(n_rows: int, nnz: int, value_bytes: int = 4,
                    index_bytes: int = 4, vector_bytes: int = 4) -> int:
    """The paper's least traffic of one y = A x: every value and column
    index once, x read once, y read and written (arXiv:1112.5588 §2)."""
    return nnz * (value_bytes + index_bytes) + 3 * n_rows * vector_bytes
