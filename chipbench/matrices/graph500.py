"""Graph500 Kronecker graph as LDBC Graphalytics ships it
(arXiv:2011.15028, datasets ``graph500-<scale>``): the adjacency of the
undirected simple graph, live vertices only, stored value 1.0.

The benchmark's own copy of the program's ``repro.core.graph``
generator, draw for draw: ``edge_factor * 2**scale`` edges, one 64-bit
draw per level and edge from ``default_rng(structure_seed)`` (low half
against A + B for the row bit, high half against A/(A+B) or C/(C+D)
for the column bit), then one random permutation of the vertex labels;
then both directions of every edge, no self-loops, no duplicates, no
isolated vertices, the rest numbered densely.  The scale is log2 of the
rows the harness asks for, rounded down (2**22 rows give scale 22).
"""
from __future__ import annotations

import math

import numpy as np

EDGE_CHUNK = 1 << 22


def edges(scale: int, edge_factor: int, seed: int, initiator):
    a, b, c = initiator
    d = 1.0 - a - b - c
    to_u32 = lambda p: np.uint32(min(round(p * 2.0 ** 32), 2 ** 32 - 1))  # noqa: E731
    row_t = to_u32(a + b)
    col_t = (to_u32(a / (a + b)), to_u32(c / (c + d)))
    n_edges = edge_factor << scale
    rng = np.random.default_rng(seed)
    src = np.empty(n_edges, np.int64)
    dst = np.empty(n_edges, np.int64)
    for lo in range(0, n_edges, EDGE_CHUNK):
        k = min(EDGE_CHUNK, n_edges - lo)
        s = np.zeros(k, np.uint32)
        t = np.zeros(k, np.uint32)
        for _ in range(scale):
            u = rng.bit_generator.random_raw(k).view(np.uint32).reshape(k, 2)
            row = u[:, 0] >= row_t
            col = u[:, 1] >= np.where(row, col_t[1], col_t[0])
            s <<= 1
            s |= row
            t <<= 1
            t |= col
        src[lo:lo + k] = s
        dst[lo:lo + k] = t
    perm = rng.permutation(1 << scale)
    return perm[src], perm[dst]


def undirected(src, dst, n: int):
    """(indptr, indices) of the undirected simple graph on the live
    vertices, columns ascending within each row."""
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.concatenate([lo * n + hi, hi * n + lo])
    del lo, hi, keep
    key.sort()
    key = key[np.concatenate(([True], key[1:] != key[:-1]))]
    row = key // n
    deg = np.bincount(row, minlength=n)
    new_id = np.cumsum(deg > 0) - 1
    indptr = np.zeros(int(new_id[-1]) + 2, np.int64)
    np.cumsum(deg[deg > 0], out=indptr[1:])
    return indptr, new_id[key - row * n].astype(np.int32)


def structure(cfg: dict, n_rows: int):
    scale = int(math.log2(n_rows))
    src, dst = edges(scale, cfg["edge_factor"], cfg["structure_seed"],
                     cfg["initiator"])
    return undirected(src, dst, 1 << scale)


def values(cfg: dict, indptr, indices, rng) -> np.ndarray:
    return np.ones(int(indptr[-1]))
