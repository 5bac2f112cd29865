"""The plain PageRank reference: LDBC Graphalytics' equations
(arXiv:2011.15028, PageRank) in float64 numpy over an edge list, with
no kernels and no operator.

One iteration maps ranks ``x`` to

``x'(v) = (1 - d)/|V| + d * sum_{u -> v} x(u)/outdeg(u)
+ (d/|V|) * sum_{w : outdeg(w) = 0} x(w)``.

Departures from the specification: none in the equations.  The
specification starts from ``1/|V|``; :func:`pagerank` takes any start
so that a test can compare single iterations from the same ranks as the
program under test.  An edge listed twice counts twice, in ``outdeg``
and in the sum, as it does in the program's transition matrix.
"""
from __future__ import annotations

import numpy as np

__all__ = ["csr_edges", "pagerank_step", "pagerank"]


def csr_edges(indptr, indices):
    """``(src, dst)`` of the graph whose adjacency CSR has a stored
    entry ``(u, v)`` for every edge ``u -> v``."""
    indptr = np.asarray(indptr)
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return src, np.asarray(indices, np.int64)


def pagerank_step(src, dst, n: int, x, damping: float = 0.85) -> np.ndarray:
    """One Graphalytics PageRank iteration from ``x``, in float64."""
    x = np.asarray(x, np.float64)
    outdeg = np.bincount(src, minlength=n)
    share = x[src] / outdeg[src]
    incoming = np.bincount(dst, weights=share, minlength=n)
    dangling = x[outdeg == 0].sum()
    return (1.0 - damping) / n + damping * incoming + damping * dangling / n


def pagerank(src, dst, n: int, iterations: int, damping: float = 0.85,
             x0=None) -> np.ndarray:
    """``iterations`` PageRank iterations from ``x0`` (``1/|V|`` each by
    default), in float64."""
    x = np.full(n, 1.0 / n) if x0 is None else np.asarray(x0, np.float64)
    for _ in range(iterations):
        x = pagerank_step(src, dst, n, x, damping)
    return x
