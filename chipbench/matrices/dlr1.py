"""DLR1 analogue (arXiv:1112.5588 §1.3, Fig. 3): an adjoint CFD (TAU)
matrix with ~144 non-zeros per row and a narrow spread (max/min ~2).

Follows ``repro.core.matrices.dlr1`` (80% of rows draw between 0.8 and
1 of the longest length, the rest between a half and 0.8, columns
uniform in a band around the diagonal, duplicates merged), with two
parameters changed so the published row length holds: the longest
drawn length is raised until the mean after merging is 144, and the band
wraps around at the matrix's edge, so edge rows are not cut short.
"""
from __future__ import annotations

import numpy as np

from chipbench.matrices import dedup_csr


def structure(cfg: dict, n: int):
    rng = np.random.default_rng(cfg["structure_seed"])
    p = cfg["row_length"]
    top = p["max"]
    long_row = rng.random(n) < p["long_share"]
    rl = np.where(long_row,
                  rng.integers(int(p["long_from"] * top), top + 1, size=n),
                  rng.integers(int(p["short_from"] * top),
                               int(p["long_from"] * top), size=n))
    rows = np.repeat(np.arange(n), rl)
    band = cfg["band"]
    cols = (rows + rng.integers(-band, band + 1, size=rows.size)) % n
    return dedup_csr(rows, cols, n)


def values(cfg: dict, indptr, indices, rng) -> np.ndarray:
    return rng.standard_normal(int(indptr[-1]))
