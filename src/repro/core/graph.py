"""PageRank on Graph500 Kronecker graphs, through the operator protocol.

The deployment is LDBC Graphalytics' PageRank (arXiv:2011.15028) on its
``graph500-<scale>`` datasets:

* :func:`kronecker_edges` is the Graph500 specification's edge generator
  (graph500.org): ``edge_factor * 2**scale`` edges, each drawn one bit
  of its source and destination per level from the 2x2 initiator
  ``A, B, C`` (``D = 1 - A - B - C``), then every vertex label put
  through one random permutation.
* :func:`undirected_csr` is the graph Graphalytics ships: symmetrised,
  self-loops and duplicate edges dropped, isolated vertices dropped and
  the rest numbered densely, in the permuted order.
* :func:`transition` is ``P = A^T D_out^-1`` (``P[v, u] = 1/outdeg(u)``
  for every edge ``u -> v``) with its dangling mask (``outdeg == 0``).
* :func:`pagerank_steps` iterates Graphalytics' equation

  ``PR(v) = (1 - d)/|V| + d * sum_{u -> v} PR(u)/outdeg(u)
  + (d/|V|) * sum_{w dangling} PR(w)``

  with one ``op @ x`` per iteration; the update around it runs under
  the device scope ``repro.pagerank``.

``repro.pagerank`` (``api.pagerank``) is the front door.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import formats as F

__all__ = ["kronecker_ids", "kronecker_edges", "undirected_csr",
           "transition", "PageRankOperator", "pagerank_operator",
           "pagerank_steps"]

# The Graph500 initiator (A, B, C); D = 1 - A - B - C.
GRAPH500_INITIATOR = (0.57, 0.19, 0.19)

# Edges drawn per pass: bounds the generator's temporaries (~100 MB).
_EDGE_CHUNK = 1 << 22


def kronecker_ids(scale: int, edge_factor: int, rng: np.random.Generator,
                  initiator=GRAPH500_INITIATOR):
    """The Graph500 Kronecker draw before relabelling: ``(src, dst)``,
    int64 arrays of ``edge_factor * 2**scale`` ids in ``[0, 2**scale)``.

    Per level, the row bit is 1 with probability ``C + D`` (drawn
    against ``A + B``) and the column bit then with probability
    ``B/(A+B)`` after a 0 row bit or ``D/(C+D)`` after a 1 (drawn
    against ``A/(A+B)`` or ``C/(C+D)``), as the specification's
    reference code draws them.  Each level takes one 64-bit draw per
    edge from ``rng``: its low 32 bits decide the row bit and its high
    32 bits the column bit."""
    a, b, c = initiator
    d = 1.0 - a - b - c
    if min(a, b, c, d) < 0:
        raise ValueError(f"initiator {initiator} leaves D = {d} < 0")
    to_u32 = lambda p: np.uint32(min(round(p * 2.0 ** 32), 2 ** 32 - 1))  # noqa: E731
    row_t = to_u32(a + b)
    col_t = (to_u32(a / (a + b)), to_u32(c / (c + d)))
    n_edges = edge_factor << scale
    src = np.empty(n_edges, np.int64)
    dst = np.empty(n_edges, np.int64)
    for lo in range(0, n_edges, _EDGE_CHUNK):
        k = min(_EDGE_CHUNK, n_edges - lo)
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
    return src, dst


def kronecker_edges(scale: int, edge_factor: int = 16, seed: int = 0,
                    initiator=GRAPH500_INITIATOR):
    """The Graph500 Kronecker edge list: :func:`kronecker_ids` drawn from
    ``default_rng(seed)``, then every label put through one random
    permutation of the ``2**scale`` ids, drawn from the same stream."""
    rng = np.random.default_rng(seed)
    src, dst = kronecker_ids(scale, edge_factor, rng, initiator)
    perm = rng.permutation(1 << scale)
    return perm[src], perm[dst]


def undirected_csr(src, dst, n: int | None = None) -> F.CSRMatrix:
    """The undirected simple graph of an edge list, as Graphalytics
    ships it: both directions of every edge, no self-loops, no
    duplicates, no isolated vertices, the live vertices numbered
    densely in their order.  Values are 1.0 (float32); columns ascend
    within each row."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if n is None:
        n = int(max(src.max(initial=-1), dst.max(initial=-1))) + 1
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
    n_live = int(new_id[-1]) + 1 if n else 0
    indptr = np.zeros(n_live + 1, np.int64)
    np.cumsum(deg[deg > 0], out=indptr[1:])
    indices = new_id[key - row * n].astype(np.int32)
    return F.CSRMatrix(indptr, indices, np.ones(len(indices), np.float32),
                       (n_live, n_live))


def transition(adj: F.CSRMatrix):
    """``(P, dangling)``: the host CSR of ``P = A^T D_out^-1`` for the
    graph whose edge ``u -> v`` is ``adj[u, v] != 0`` stored (rows are
    sources; stored values are not weights), and the boolean mask of
    vertices without out-edges.  ``P[v, u] = 1/outdeg(u)``; a stored
    duplicate counts as one more edge, in ``outdeg`` and in ``P``.  For
    an undirected graph ``P = A D^-1``.  ``P`` is ``(D^-1 A)^T`` by
    ``formats.csr_transpose``.  Runs under the host span
    ``repro.graph.transition``."""
    n, n_cols = adj.shape
    if n != n_cols:
        raise ValueError(f"a graph's adjacency is square; got {adj.shape}")
    with obs.span("repro.graph.transition"):
        outdeg = np.diff(adj.indptr)
        inv = np.zeros(n, np.float64)
        np.divide(1.0, outdeg, out=inv, where=outdeg > 0)
        dt = adj.data.dtype if adj.data.dtype.kind == "f" else np.float32
        scaled = F.CSRMatrix(adj.indptr, adj.indices,          # D^-1 A
                             np.repeat(inv, outdeg).astype(dt), adj.shape)
        return F.csr_transpose(scaled), outdeg == 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PageRankOperator:
    """The transition operator ``op`` (any ``SparseOperator``) and the
    dangling mask on the device; ``|V|`` is the mask's length."""

    op: object
    dangling: jax.Array              # (|V|,) bool

    @property
    def n(self) -> int:
        return self.dangling.shape[0]


def pagerank_operator(adj: F.CSRMatrix, **operator_kw) -> PageRankOperator:
    """``P`` of the graph ``adj`` (:func:`transition`) built through
    ``core.operator.operator`` (``format="auto"`` unless given;
    ``dtype``, ``index_dtype`` and the rest pass through), with its
    dangling mask on the device."""
    from repro.core.operator import operator
    p, dangling = transition(adj)
    op = operator(p, **operator_kw)
    return PageRankOperator(op=op, dangling=jnp.asarray(dangling))


def pagerank_steps(pr: PageRankOperator, x: jax.Array, k: int,
                   damping: float = 0.85) -> jax.Array:
    """``k`` PageRank iterations from ranks ``x`` (length ``|V|``), one
    ``pr.op @ x`` each; jittable with ``k`` static.  The dangling sum,
    the teleport and the damping run under the device scope
    ``repro.pagerank``; the apply keeps its own scopes."""
    n = pr.n

    def body(v, _):
        y = pr.op @ v
        with jax.named_scope("repro.pagerank"):
            lost = jnp.sum(jnp.where(pr.dangling, v, 0))
            v = (1.0 - damping) / n + damping * (y[:n] + lost / n)
        return v.astype(x.dtype), None

    return jax.lax.scan(body, x, None, length=k)[0]
