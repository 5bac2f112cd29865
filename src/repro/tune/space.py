"""Search-space enumeration + model-based pruning for the autotuner.

The kernel-static space after the PR-4 bandwidth overhaul is
``format x b_r x chunk_l x sigma`` (times the dtype policy,
which is an INPUT here, not a search axis: the caller's storage
precision is a contract, the tuner only picks layout statics for it).
Measuring the full cross product would take seconds per matrix, so the
space is pruned with the same ``perf_model`` pricing the static
dispatch heuristic uses — candidates whose predicted memory-bound time
is hopeless never get measured — with one guarantee the tuner's
correctness story rests on: :func:`prune_candidates` NEVER drops the
heuristic default (``kernels.ops.as_device``'s no-tuning build), so the
measured winner can only tie or beat what dispatch would have picked.

All legality constraints live in one place (:func:`enumerate_candidates`)
and mirror the converters': ``diag_align`` is raised to ``chunk_l``
exactly as ``as_device`` does, ``sigma`` is a SELL-only axis capped at
the padded row count (where it degenerates to the pJDS global sort),
and ELLPACK-R row tiles are whole multiples of the 128-lane vreg width.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from repro.core import formats as F
from repro.core import perf_model as PM
from repro.kernels import ops

__all__ = [
    "Candidate",
    "heuristic_candidate",
    "enumerate_candidates",
    "price_candidate",
    "prune_candidates",
    "solver_candidates",
    "dist_candidates",
]

# Default search axes.  Deliberately small: the point of the model-based
# prune is that ENUMERATION can stay generous while MEASUREMENT stays
# top-k; these are the values the converters are known to like on the
# (8, 128) register tile (DESIGN.md §2).
B_R_OPTIONS = (32, 64, 128)
CHUNK_L_OPTIONS = (8, 16, 32)
SIGMA_FACTORS = (1, 4, 8, 32)      # sigma = factor * b_r, capped at n_pad

_DEFAULT_B_R = 128                 # as_device defaults — the heuristic build
_DEFAULT_CHUNK_L = 16
_DEFAULT_DIAG_ALIGN = 8


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the kernel-static search space: everything
    ``kernels.ops.as_device`` needs beyond the matrix and the dtype
    policy.  ``sigma`` is meaningful for sell only (None elsewhere);
    hashable/frozen so candidate sets dedupe, JSON-roundtrippable so
    the persistent cache can store the winning point."""

    fmt: str
    b_r: int = _DEFAULT_B_R
    chunk_l: int = _DEFAULT_CHUNK_L
    sigma: Optional[int] = None

    def build_kwargs(self) -> dict:
        """Keyword arguments for ``ops.as_device`` (minus the dtype
        policy, which the caller owns)."""
        return dict(
            format=self.fmt,
            b_r=self.b_r,
            diag_align=max(_DEFAULT_DIAG_ALIGN, self.chunk_l),
            sigma=self.sigma,
            chunk_l=self.chunk_l,
        )

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Candidate":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def label(self) -> str:
        sig = f" sigma={self.sigma}" if self.sigma is not None else ""
        return f"{self.fmt} b_r={self.b_r} chunk_l={self.chunk_l}{sig}"


def heuristic_candidate(
    m: F.CSRMatrix,
    format: str = "auto",
    dtype=None,
    index_dtype="auto",
) -> Candidate:
    """The exact build ``as_device`` produces with default statics and
    ``tune="off"`` — the baseline every tuned decision is benchmarked
    against, and the candidate :func:`prune_candidates` may never drop."""
    da = max(_DEFAULT_DIAG_ALIGN, _DEFAULT_CHUNK_L)
    fmt = format
    if fmt == "auto":
        fmt = ops.select_format(m, b_r=_DEFAULT_B_R, diag_align=da,
                                sigma=None, value_dtype=dtype,
                                index_dtype=index_dtype)
    sigma = None
    if fmt == "sell":
        sigma = min(8 * _DEFAULT_B_R,
                    _pad_to(max(m.n_rows, 1), _DEFAULT_B_R))
    return Candidate(
        fmt=fmt,
        b_r=_DEFAULT_B_R,
        chunk_l=_DEFAULT_CHUNK_L,
        sigma=sigma,
    )


def _pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def enumerate_candidates(
    m: F.CSRMatrix,
    format: str = "auto",
    dtype=None,
    index_dtype="auto",
    b_r_options: Sequence[int] = B_R_OPTIONS,
    chunk_l_options: Sequence[int] = CHUNK_L_OPTIONS,
    sigma_factors: Sequence[int] = SIGMA_FACTORS,
) -> list[Candidate]:
    """All legal kernel-static points for ``m`` under the given format
    restriction (``format != "auto"`` collapses the format axis).  The
    heuristic default is always a member.  Degenerate matrices (empty,
    or too few rows to fill one block at the smallest b_r) collapse to
    the CSR baseline."""
    heur = heuristic_candidate(m, format, dtype, index_dtype)
    n = m.n_rows
    if m.nnz == 0 or n < ops._CSR_MIN_ROWS_FACTOR * min(b_r_options):
        return list(dict.fromkeys([Candidate(fmt="csr"), heur]))

    fmts = (["csr", "ellpack_r", "pjds", "sell", "cmrs"] if format == "auto"
            else [format])
    out = [heur]
    for fmt in fmts:
        if fmt == "csr":
            out.append(Candidate(fmt="csr"))
            continue
        for b_r in b_r_options:
            if n < ops._CSR_MIN_ROWS_FACTOR * b_r:
                continue       # block padding dominates; csr covers this
            if fmt == "ellpack_r" and b_r != ops.ell_tile(b_r):
                continue       # as_device would build the 128-lane tile
            sigmas = [None]
            if fmt == "sell":
                n_pad = _pad_to(n, b_r)
                sigmas = sorted({min(f * b_r, n_pad)
                                 for f in sigma_factors})
            for chunk_l in chunk_l_options:
                for sigma in sigmas:
                    out.append(Candidate(fmt=fmt, b_r=b_r, chunk_l=chunk_l,
                                         sigma=sigma))
    return list(dict.fromkeys(out))


def solver_candidates(
    m: F.CSRMatrix,
    *,
    method: str = "cg",
    dtype=None,
    index_dtype="auto",
) -> list[tuple[str, Candidate]]:
    """The SOLVER-level probe set: (strategy, layout) pairs for
    ``tune_solver``, where strategy is ``"fused"`` (the fused
    spMV+dots iteration over a SELL build) or ``"composed"`` (separate
    matvec + reduction HLOs over whatever layout wins per matvec).

    Deliberately tiny — a handful of probes, each a fixed-iteration
    solve, because the per-matvec tuner (:func:`enumerate_candidates` +
    prune) already explored the layout space; here only the decisions
    that CHANGE at the solver level are measured: fused vs composed,
    and the fused path's tile height (the epilogue's dot reductions
    shift the best chunk_l relative to a bare matvec).
    """
    h_sell = heuristic_candidate(m, "sell", dtype, index_dtype)
    alt_cl = 8 if h_sell.chunk_l != 8 else 16
    h_auto = heuristic_candidate(m, "auto", dtype, index_dtype)
    out: list[tuple[str, Candidate]] = [
        ("fused", h_sell),
        ("fused", dataclasses.replace(h_sell, chunk_l=alt_cl)),
        ("composed", h_auto),
    ]
    if h_auto != h_sell:
        out.append(("composed", h_sell))
    return list(dict.fromkeys(out))


def dist_candidates(
    n_dev: int,
    *,
    halos: Sequence[str] = ("gathered", "full"),
    modes: Sequence[str] = ("vector", "overlap", "pipeline"),
    grids: Optional[Sequence] = None,
    halo_w_options: Sequence[Optional[int]] = (None,),
) -> list[dict]:
    """The DISTRIBUTED probe set: one dict per (grid, halo, mode,
    halo_w) combination for ``tune_partition``'s communication sweep.

    The grid axis defaults to the three structurally distinct shapes of
    a ``n_dev`` mesh — pure row partitioning ``(P, 1)``, pure column
    partitioning ``(1, P)`` and the most-square 2-D factorization plus
    its transpose — because intermediate rectangles interpolate between
    those extremes in both halo volume and reduction volume.  The mode
    axis skips ``"naive"`` (strictly dominated: same exchange as
    ``"vector"`` plus one dense unpermute) and prunes ``"pipeline"``
    for full halos — staging a full exchange ships the same bytes in
    more messages, so it can only win where gathered/pipeline already
    does.  ``halo_w=None`` means the measured coupling width — wider
    explicit windows only add structurally empty exchange slots, so the
    default sweeps none.
    """
    if grids is None:
        gs: list = [(n_dev, 1)]
        if n_dev > 1:
            gs.append((1, n_dev))
        sq = max(g for g in range(1, int(np.sqrt(n_dev)) + 1)
                 if n_dev % g == 0)
        if sq > 1:
            gs += [(sq, n_dev // sq), (n_dev // sq, sq)]
        grids = list(dict.fromkeys(gs))
    out = []
    for grid in grids:
        for halo in halos:
            for mode in modes:
                if mode == "naive" or (mode == "pipeline" and halo == "full"):
                    continue
                for hw in halo_w_options:
                    out.append(dict(grid=(None if grid in (None, (n_dev, 1))
                                          else tuple(grid)),
                                    halo=str(halo), mode=str(mode),
                                    halo_w=hw))
    return [dict(t) for t in dict.fromkeys(
        tuple(sorted(c.items(), key=lambda kv: kv[0])) for c in out)]


def price_candidate(
    m: F.CSRMatrix,
    c: Candidate,
    *,
    dtype=None,
    index_dtype="auto",
    spec: PM.TPUSpec = PM.TPU_V5E,
    calibration="default",
) -> float:
    """Predicted spMVM seconds of candidate ``c`` on ``m`` — the same
    ``perf_model`` pricing ``select_format`` uses, extended
    over the full static space.  ``calibration=None`` forces the
    uncalibrated data-sheet model (what the calibration fit needs as
    its regressor); the default picks up any installed calibration."""
    n, n_nzr = m.n_rows, m.n_nzr
    vecb = max(4, m.data.dtype.itemsize)
    if c.fmt == "csr":
        vb = m.data.dtype.itemsize if dtype is None else np.dtype(dtype).itemsize
        # CSRDevice streams indices AND row ids per nnz (8 index bytes),
        # and gathers x and scatter-adds into y in XLA: two indexed
        # accesses per nnz.
        return PM.predicted_spmv_seconds(
            m.nnz, n, n_nzr, irregular_factor=ops._CSR_IRREGULAR_FACTOR,
            spec=spec, value_bytes=vb, index_bytes=8, vec_bytes=vecb,
            fmt="csr", calibration=calibration, gathered=2 * m.nnz)
    rl = m.row_lengths()
    vb = np.dtype(dtype).itemsize if dtype is not None \
        else m.data.dtype.itemsize
    ib = F.resolve_index_dtype(index_dtype, m.shape[1]).itemsize
    da = max(_DEFAULT_DIAG_ALIGN, c.chunk_l)
    if c.fmt == "wsell":
        sigma = 8 * c.b_r if c.sigma is None else c.sigma
        return ops.wsell_seconds(
            m, F.window_plan(m, sigma), b_r=c.b_r, diag_align=da,
            sigma=sigma, spec=spec, value_bytes=vb, index_bytes=ib,
            vec_bytes=vecb, calibration=calibration)
    elems = F.estimate_storage_elements(rl, c.fmt, c.b_r, da, c.sigma)
    perm_bytes = 0.0
    if c.fmt in PM.SORTED_ROW_FORMATS:
        perm_bytes = PM.perm_traffic_bytes(n, vecb)
    if c.fmt == "cmrs":
        # Same max(memory, compute) pricing as select_format: the int8
        # row_in_strip stream adds a byte per slot, and the one-hot
        # reduction matmul can bound the kernel instead of HBM.
        ib += PM.CMRS_RIS_BYTES
    t = PM.predicted_spmv_seconds(
        elems, n, n_nzr, perm_bytes=perm_bytes, spec=spec,
        value_bytes=vb, index_bytes=ib, vec_bytes=vecb,
        fmt=c.fmt, calibration=calibration, gathered=elems)
    if c.fmt == "cmrs":
        t = max(t, PM.cmrs_reduce_seconds(elems, c.b_r, spec))
    return t


def prune_candidates(
    m: F.CSRMatrix,
    candidates: Sequence[Candidate],
    *,
    top_k: int = 6,
    dtype=None,
    index_dtype="auto",
    spec: PM.TPUSpec = PM.TPU_V5E,
    heuristic: Optional[Candidate] = None,
) -> list[Candidate]:
    """Keep the ``top_k`` model-cheapest candidates, ALWAYS including
    the heuristic default (appended back if the model would drop it —
    the guarantee that tuning can never do worse than dispatch by more
    than measurement noise).  Ordered cheapest-predicted first."""
    if heuristic is None:
        heuristic = heuristic_candidate(m, dtype=dtype,
                                        index_dtype=index_dtype)
    priced = sorted(
        dict.fromkeys(candidates),
        key=lambda c: price_candidate(m, c, dtype=dtype,
                                      index_dtype=index_dtype, spec=spec))
    kept = priced[: max(top_k, 1)]
    if heuristic not in kept:
        kept.append(heuristic)
    return kept
