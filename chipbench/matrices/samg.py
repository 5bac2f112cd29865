"""sAMG analogue (arXiv:1112.5588 §1.3, Fig. 3): the Poisson operator of
an adaptive-multigrid code, ~7 non-zeros per row, mostly short rows and
a tail, the longest row more than 4x the shortest.

The structure draws the neighbours of ``repro.core.matrices.samg`` draw
for draw (a band around the diagonal and a share of long-range
couplings), keeps those below the diagonal and mirrors them, so the
pattern is symmetric: at one structure seed its strict lower triangle
is the program generator's.  Every row has its diagonal.

The values are a weighted graph Laplacian ``L = D - W`` on that pattern
(weights uniform from the run's seed, symmetric) plus ``sigma * I``:
the implicit-Euler heat step ``(I/dt + L)`` at ``dt`` = ``time_step`` x
``1/max_i d_i``, the forward-Euler stability bound that Gershgorin
gives.  The matrix is symmetric positive definite with condition number
at most ``1 + 2 * time_step``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse

from chipbench.matrices import dedup_csr


def structure(cfg: dict, n: int):
    rng = np.random.default_rng(cfg["structure_seed"])
    p = cfg["row_length"]
    rl = np.clip(rng.geometric(p["geometric_p"], size=n) + p["offset"],
                 p["min"], p["max"])
    tot = int(rl.sum())
    rows = np.repeat(np.arange(n), rl)
    # unstructured mesh neighbours: local band + occasional long-range
    band = cfg["band"]
    cols = np.clip(rows + rng.integers(-band, band + 1, size=tot), 0, n - 1)
    far = rng.random(tot) < cfg["far_share"]
    cols[far] = rng.integers(0, n, size=int(far.sum()))
    low = cols < rows
    r, c, d = rows[low], cols[low], np.arange(n)
    return dedup_csr(np.concatenate([r, c, d]), np.concatenate([c, r, d]), n)


def values(cfg: dict, indptr, indices, rng) -> np.ndarray:
    """``sigma * I + L``: each coupling below the diagonal draws a weight
    ``w`` uniform in ``weights``, its mirror takes the same ``w``, both
    store ``-w``; the diagonal is the row's weight sum plus ``sigma``."""
    n = len(indptr) - 1
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    low = indices < rows
    w = cfg["weights"]
    lower = scipy.sparse.csr_matrix(
        (rng.uniform(w["low"], w["high"], size=int(low.sum())),
         (rows[low], indices[low])), shape=(n, n))
    weights = lower + lower.T.tocsr()
    degree = np.asarray(weights.sum(axis=1)).ravel()
    sigma = degree.max() / cfg["time_step"]
    a = (scipy.sparse.diags(degree + sigma) - weights).tocsr()
    a.sort_indices()
    assert np.array_equal(a.indptr, indptr) and np.array_equal(a.indices, indices)
    return a.data
