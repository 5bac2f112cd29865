"""The plain PageRank reference: one LDBC Graphalytics PageRank
iteration (arXiv:2011.15028) in float64 on the host,

``x'(v) = (1 - d)/|V| + d * sum_{u -> v} x(u)/outdeg(u)
+ (d/|V|) * sum_{w : outdeg(w) = 0} x(w)``,

straight off the benchmark's adjacency (a stored ``(u, v)`` is the
edge ``u -> v``; values are not weights).  Imports nothing of the
program under test and uses none of its arrays.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse


class HostPageRank:
    def __init__(self, m):
        n = m.n_rows
        self.n = n
        outdeg = np.diff(m.indptr)
        adj = scipy.sparse.csr_matrix(
            (np.ones(m.nnz), m.indices, m.indptr), shape=(n, n))
        self.adj_t = adj.T                 # CSC: A^T z without a copy
        self.inv_deg = np.zeros(n)
        np.divide(1.0, outdeg, out=self.inv_deg, where=outdeg > 0)
        self.dangling = outdeg == 0

    def step(self, x, damping: float) -> np.ndarray:
        x = np.asarray(x, np.float64)[: self.n]
        incoming = self.adj_t @ (x * self.inv_deg)
        lost = x[self.dangling].sum()
        return (1.0 - damping) / self.n + damping * (incoming + lost / self.n)

    def steps(self, x, k: int, damping: float) -> np.ndarray:
        for _ in range(k):
            x = self.step(x, damping)
        return x


def max_rel_err(x, ref) -> float:
    """max over vertices of |x - ref| / ref: every rank is at least the
    teleport (1 - d)/|V| > 0, so each vertex is judged on its own."""
    x = np.asarray(x, np.float64)[: len(ref)]
    if not np.isfinite(x).all():
        return float("inf")
    return float((np.abs(x - ref) / ref).max())
