"""repro: SELL-C-sigma / pJDS spMVM and Krylov solvers in JAX+Pallas.

Lazy top-level API (PEP 562) — importing ``repro`` stays cheap; the
heavy submodules load on first attribute access::

    import repro
    res = repro.solve(m, b, method="cg")         # the solver front door
    ranks = repro.pagerank(adj)                  # Graphalytics PageRank
    op = repro.operator(m)                       # y = op @ x
    dop = repro.dist_operator(m, mesh)           # mesh-distributed

Everything else lives in the submodules: ``repro.core`` (formats,
matrices, solvers, perf model), ``repro.kernels`` (device kernels and
dispatch), ``repro.tune`` (autotuner), ``repro.serve`` (engines).
"""
from __future__ import annotations

__all__ = ["solve", "SolveResult", "SolveFailure", "pagerank", "operator",
           "dist_operator", "load_mm", "save_mm", "preprocess"]

_LAZY = {
    "solve": "repro.api",
    "SolveResult": "repro.core.solvers",
    "SolveFailure": "repro.api",
    "pagerank": "repro.api",
    "operator": "repro.core.operator",
    "dist_operator": "repro.core.operator",
    "load_mm": "repro.core.io_mm",
    "save_mm": "repro.core.io_mm",
    "preprocess": "repro.core.reorder",
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module 'repro' has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(target), name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
