"""Sparse matrix storage formats from Kreutzer et al. 2011 (+ successors).

Implements the host-side (numpy) construction of the formats the paper
compares, with the TPU-adapted memory layouts consumed by the Pallas
kernels in ``repro.kernels``:

* CSR           — the CPU baseline / interchange format.
* ELLPACK       — rows compressed left, padded to the *global* max row
                  length, stored jagged-diagonal-major (column-major in
                  the paper's ``val[j*N + i]`` sense).
* ELLPACK-R     — same storage as ELLPACK plus an explicit ``rowlen``
                  array so the kernel skips padding (paper Listing 1).
* pJDS          — the paper's contribution: rows sorted by non-zero count,
                  then padded per *block* of ``b_r`` consecutive rows to
                  the block-local maximum (paper Fig. 1, Listing 2).
* SELL-C-sigma  — beyond-paper: the published successor of pJDS (sorting
                  window sigma instead of a global sort); pJDS is the
                  sigma = n_rows special case.

TPU adaptation (see DESIGN.md §2): the paper pads row counts to the warp
size (32) so a warp issues coalesced loads.  On TPU the analogous unit is
the (sublane, lane) = (8, 128) vector register tile, so

* ``b_r`` (rows per block)   defaults to 128  → rows live on lanes,
* jagged-diagonal counts are padded to multiples of 8 → full sublanes.

Layout of the blocked arrays: ``val``/``col_idx`` have shape
``(total_jds, b_r)`` — jagged diagonals major, rows minor — which is
exactly the paper's column-major ELLPACK layout, restricted to one block,
and gives the Pallas kernels clean (8k, 128) VMEM tiles.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Tuple

import numpy as np

from repro import obs

__all__ = [
    "CSRMatrix",
    "ELLMatrix",
    "PJDSMatrix",
    "SELLMatrix",
    "csr_from_dense",
    "csr_to_dense",
    "csr_from_coo",
    "validate_csr",
    "CSRValidationError",
    "ValidationReport",
    "csr_to_ell",
    "csr_to_pjds",
    "csr_to_sell",
    "CMRSMatrix",
    "csr_to_cmrs",
    "ell_to_dense",
    "pjds_to_dense",
    "sell_to_dense",
    "WindowedSELLMatrix",
    "WindowPlan",
    "window_plan",
    "window_reach",
    "csr_to_wsell",
    "wsell_to_dense",
    "WINDOW_UNIT",
    "cmrs_to_dense",
    "format_nbytes",
    "storage_elements",
    "data_reduction_vs_ellpack",
    "windowed_sort_perm",
    "windowed_block_lengths",
    "estimate_storage_elements",
    "csr_remote_columns_by_distance",
    "csr_transpose",
    "csr_diagonal",
    "structural_fingerprint",
    "PAD_COL",
    "min_index_dtype",
    "resolve_index_dtype",
    "assert_padding_invariant",
]

_DEFAULT_BR = 128          # rows per pJDS block (lane dimension on TPU)
_DEFAULT_DIAG_ALIGN = 8    # jagged-diagonal padding (sublane dimension)

# ----------------------------------------------------------------------
# Padding sentinel (audited end-to-end; see assert_padding_invariant).
#
# Every blocked format pads its val/col_idx arrays.  The invariant is:
#
#   padded entries store  val == 0  AND  col_idx == PAD_COL (== 0).
#
# PAD_COL is an IN-RANGE column, so the kernels' RHS gather reads x[0]
# for padded lanes without masking; correctness comes from val == 0
# (the product contributes nothing to the accumulator).  This is what
# lets every kernel and ref skip per-entry masks on the hot path, and
# it must survive index compression: PAD_COL == 0 is representable in
# any index dtype.  Code that rewrites stored values (e.g.
# ``operator.with_values``) must preserve the zeros in padded slots.
# ----------------------------------------------------------------------
PAD_COL = 0

# When True every converter audits its freshly built arrays (numpy-level,
# O(stored elements)).  Enabled in debug builds (i.e. unless python runs
# with -O); flip module-globally to force either way.
PAD_AUDIT = bool(__debug__)


def min_index_dtype(span: int) -> np.dtype:
    """Narrowest signed integer dtype that can address columns
    ``[0, span)``.  int16 covers spans up to 2**15 — comfortably the
    per-device column slices the distributed partitioner produces —
    otherwise int32."""
    return np.dtype(np.int16) if span <= 2 ** 15 else np.dtype(np.int32)


def resolve_index_dtype(index_dtype, span: int) -> np.dtype:
    """Resolve an ``index_dtype`` build argument: ``"auto"`` compresses
    to :func:`min_index_dtype`; an explicit dtype is validated against
    the addressable span (a lossy narrowing is a build error, not a
    silent wrap)."""
    if index_dtype == "auto":
        return min_index_dtype(span)
    dt = np.dtype(index_dtype)
    if dt.kind != "i":
        raise ValueError(f"index_dtype must be a signed integer; got {dt}")
    if span > np.iinfo(dt).max + 1:
        raise ValueError(
            f"index_dtype {dt} cannot address {span} columns "
            f"(max span {np.iinfo(dt).max + 1})")
    return dt


# --------------------------------------------------------------------------
# CSR (interchange format)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CSRMatrix:
    """Host-side CSR. ``indptr`` int64, ``indices`` int32."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    shape: Tuple[int, int]

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def row_lengths(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)

    @property
    def n_nzr(self) -> float:
        """Average non-zeros per row (the paper's N_nzr)."""
        return self.nnz / max(self.n_rows, 1)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Reference numpy spMVM (oracle for everything else)."""
        y = np.zeros(self.n_rows, dtype=np.result_type(self.data, x))
        for i in range(self.n_rows):
            lo, hi = self.indptr[i], self.indptr[i + 1]
            if hi > lo:
                y[i] = np.dot(self.data[lo:hi], x[self.indices[lo:hi]])
        return y


def csr_from_dense(a: np.ndarray) -> CSRMatrix:
    n_rows, n_cols = a.shape
    mask = a != 0
    indptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(mask.sum(axis=1), out=indptr[1:])
    indices = np.nonzero(mask)[1].astype(np.int32)
    data = a[mask]
    return CSRMatrix(indptr, indices, data, (n_rows, n_cols))


def csr_to_dense(m: CSRMatrix) -> np.ndarray:
    a = np.zeros(m.shape, dtype=m.data.dtype)
    for i in range(m.n_rows):
        lo, hi = m.indptr[i], m.indptr[i + 1]
        a[i, m.indices[lo:hi]] = m.data[lo:hi]
    return a


def csr_from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: Tuple[int, int],
    sum_duplicates: bool = True,
) -> CSRMatrix:
    """Build CSR from COO triplets (vectorised; no scipy dependency).

    Sorted-per-row invariant: the ``lexsort((cols, rows))`` below runs
    BEFORE the ``sum_duplicates`` branch, so the output's within-row
    column indices are ascending on BOTH paths.  Callers that pass
    ``sum_duplicates=False`` (``reorder.permute_symmetric``) therefore
    still satisfy the sorted invariant that ``validate_csr`` enforces
    and that int16 span
    compression (``resolve_index_dtype``) assumes — they merely skip
    deduplication, not the sort.  (On the dedup path the invariant also
    follows from ``np.unique`` of the ``row * n_cols + col`` key.)"""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    if sum_duplicates and len(rows):
        key = rows * shape[1] + cols
        uniq, inv = np.unique(key, return_inverse=True)
        summed = np.zeros(len(uniq), dtype=vals.dtype)
        np.add.at(summed, inv, vals)
        rows = (uniq // shape[1]).astype(np.int64)
        cols = (uniq % shape[1]).astype(np.int64)
        vals = summed
    indptr = np.zeros(shape[0] + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    np.cumsum(indptr, out=indptr)
    return CSRMatrix(indptr, cols.astype(np.int32), vals, shape)


class CSRValidationError(ValueError):
    """A host CSR matrix failed admission validation.  ``report`` is the
    :class:`ValidationReport` with per-issue counts."""

    def __init__(self, message: str, report: "ValidationReport"):
        super().__init__(message)
        self.report = report


@dataclasses.dataclass
class ValidationReport:
    """What :func:`validate_csr` found (and, under ``repair=True``,
    fixed).  ``issues`` maps issue name -> count; ``ok`` is pre-repair
    cleanliness, ``repaired`` whether a rebuilt matrix was returned."""

    issues: dict
    repaired: bool = False

    @property
    def ok(self) -> bool:
        return not self.issues


def validate_csr(m: CSRMatrix, *, repair: bool = False
                 ) -> tuple[CSRMatrix, ValidationReport]:
    """Admission check for a host CSR matrix: structural integrity of
    ``indptr`` (length, monotone, bounds), column indices in range and
    sorted per row, no within-row duplicates, finite values.

    ``repair=False`` raises :class:`CSRValidationError` on the first
    report of ANY issue; ``repair=True`` rebuilds the matrix instead —
    out-of-range columns and non-finite values are DROPPED, duplicates
    summed, rows re-sorted (via :func:`csr_from_coo`) — and returns the
    repaired copy.  A non-monotone / mis-sized ``indptr`` is structural
    corruption with no trustworthy row boundaries, so it raises even
    under ``repair=True``.  Returns ``(matrix, report)``; the input is
    returned untouched (and unscanned structure shared) when clean.
    """
    indptr = np.asarray(m.indptr)
    indices = np.asarray(m.indices)
    data = np.asarray(m.data)
    n_rows, n_cols = m.shape
    issues: dict = {}

    structural = []
    if indptr.ndim != 1 or len(indptr) != n_rows + 1:
        structural.append("indptr_shape")
    else:
        if int(indptr[0]) != 0 or int(indptr[-1]) != len(indices):
            structural.append("indptr_bounds")
        if np.any(np.diff(indptr) < 0):
            structural.append("indptr_non_monotone")
    if len(indices) != len(data):
        structural.append("indices_data_mismatch")
    if structural:
        report = ValidationReport({k: 1 for k in structural})
        raise CSRValidationError(
            f"CSR structure is corrupt ({', '.join(structural)}): row "
            "boundaries cannot be trusted, not repairable", report)

    out_of_range = (indices < 0) | (indices >= n_cols)
    n_oor = int(out_of_range.sum())
    if n_oor:
        issues["out_of_range_indices"] = n_oor
    finite = np.isfinite(data)
    n_nonfinite = int((~finite).sum())
    if n_nonfinite:
        issues["non_finite_values"] = n_nonfinite

    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
    # sorted-within-row and duplicate detection in one pass over the
    # (row, col) key sequence: a non-increasing step inside a row is
    # either out of order or a duplicate
    if len(indices):
        keys = rows * max(n_cols, 1) + np.clip(indices, 0, n_cols - 1)
        step = np.diff(keys)
        same_row = np.diff(rows) == 0
        n_dup = int(((step == 0) & same_row).sum())
        n_unsorted = int(((step < 0) & same_row).sum())
        if n_dup:
            issues["duplicate_indices"] = n_dup
        if n_unsorted:
            issues["unsorted_indices"] = n_unsorted

    if not issues:
        return m, ValidationReport({})
    if not repair:
        raise CSRValidationError(
            "CSR failed validation: "
            + ", ".join(f"{k}={v}" for k, v in issues.items())
            + " (pass repair=True / validate='repair' to rebuild)",
            ValidationReport(dict(issues)))
    keep = finite & ~out_of_range
    fixed = csr_from_coo(rows[keep], indices[keep].astype(np.int64),
                         data[keep], m.shape, sum_duplicates=True)
    fixed = CSRMatrix(fixed.indptr, fixed.indices,
                      fixed.data.astype(data.dtype), m.shape)
    return fixed, ValidationReport(dict(issues), repaired=True)


# --------------------------------------------------------------------------
# ELLPACK / ELLPACK-R
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ELLMatrix:
    """ELLPACK(-R), jagged-diagonal-major: ``val[j, i]`` = j-th nonzero of
    row i (the paper's ``val[j*N + i]``).  Padded entries have val 0 and a
    clamped (valid) column index so gathers stay in range.

    ``rowlen`` turns plain ELLPACK into ELLPACK-R (paper Listing 1).
    """

    val: np.ndarray       # (max_nzr_pad, n_rows_pad)
    col_idx: np.ndarray   # (max_nzr_pad, n_rows_pad) int32
    rowlen: np.ndarray    # (n_rows_pad,) int32
    shape: Tuple[int, int]
    n_rows_pad: int

    @property
    def max_nzr(self) -> int:
        return self.val.shape[0]


def _pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def csr_to_ell(
    m: CSRMatrix,
    row_align: int = _DEFAULT_BR,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    index_dtype="auto",
) -> ELLMatrix:
    rl = m.row_lengths()
    max_nzr = _pad_to(max(int(rl.max(initial=0)), 1), diag_align)
    n_pad = _pad_to(m.n_rows, row_align)
    idt = resolve_index_dtype(index_dtype, m.shape[1])
    val = np.zeros((max_nzr, n_pad), dtype=m.data.dtype)
    col = np.full((max_nzr, n_pad), PAD_COL, dtype=idt)
    for i in range(m.n_rows):
        lo, hi = m.indptr[i], m.indptr[i + 1]
        val[: hi - lo, i] = m.data[lo:hi]
        col[: hi - lo, i] = m.indices[lo:hi]
    rowlen = np.zeros(n_pad, dtype=np.int32)
    rowlen[: m.n_rows] = rl
    e = ELLMatrix(val, col, rowlen, m.shape, n_pad)
    if PAD_AUDIT:
        assert_padding_invariant(e)
    return e


def ell_to_dense(e: ELLMatrix) -> np.ndarray:
    a = np.zeros((e.shape[0], e.shape[1]), dtype=e.val.dtype)
    for i in range(e.shape[0]):
        for j in range(int(e.rowlen[i])):
            a[i, e.col_idx[j, i]] += e.val[j, i]
    return a


# --------------------------------------------------------------------------
# pJDS — the paper's contribution
# --------------------------------------------------------------------------
@dataclasses.dataclass
class PJDSMatrix:
    """Padded Jagged Diagonals Storage (paper Fig. 1), TPU-blocked.

    Rows are sorted by descending non-zero count; blocks of ``b_r``
    consecutive *sorted* rows are padded to the block-local max length
    (rounded up to ``diag_align`` sublanes).  Block ``b`` occupies rows
    ``block_start[b]:block_start[b+1]`` of the flat ``(total_jds, b_r)``
    ``val``/``col_idx`` arrays — this is the paper's per-column
    ``col_start[]`` offset array at block granularity.

    The operation computed by the kernels is in the *permuted* basis
    (paper §2.1): ``y_p = A_p @ x_p`` with ``x_p = x[perm]``; with
    ``permuted_cols=True`` the stored column indices already live in the
    permuted basis (symmetric permutation, the right choice for the
    Krylov solvers in ``core.solvers``).
    """

    val: np.ndarray         # (total_jds, b_r)
    col_idx: np.ndarray     # (total_jds, b_r) int32
    block_start: np.ndarray # (n_blocks + 1,) int32
    block_len: np.ndarray   # (n_blocks,) int32  == diff(block_start)
    rowlen: np.ndarray      # (n_rows_pad,) int32, sorted order
    perm: np.ndarray        # (n_rows_pad,) int32: perm[p] = original row at sorted pos p
    inv_perm: np.ndarray    # (n_rows_pad,) int32
    shape: Tuple[int, int]
    b_r: int
    n_rows_pad: int
    permuted_cols: bool

    @property
    def n_blocks(self) -> int:
        return len(self.block_len)

    @property
    def total_jds(self) -> int:
        return self.val.shape[0]

    def permute(self, x: np.ndarray) -> np.ndarray:
        """Take ``x`` (original basis) to the sorted/permuted basis."""
        xp = np.zeros(self.n_rows_pad, dtype=x.dtype)
        n = min(self.shape[1], len(x))
        # perm includes padded positions pointing past n_rows; guard them.
        valid = self.perm < n
        xp[valid] = x[self.perm[valid]]
        return xp

    def unpermute(self, yp: np.ndarray) -> np.ndarray:
        """Take a padded permuted vector back to the original basis."""
        y = np.zeros(self.shape[0], dtype=yp.dtype)
        valid = self.perm < self.shape[0]
        y[self.perm[valid]] = yp[valid]
        return y


def csr_to_pjds(
    m: CSRMatrix,
    b_r: int = _DEFAULT_BR,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    permuted_cols: bool = True,
    index_dtype="auto",
) -> PJDSMatrix:
    rl = m.row_lengths()
    n_pad = _pad_to(m.n_rows, b_r)
    rl_pad = np.zeros(n_pad, dtype=np.int64)
    rl_pad[: m.n_rows] = rl
    # "sort" step (Fig. 1): stable sort by descending row length.
    perm = np.argsort(-rl_pad, kind="stable").astype(np.int32)
    return _pjds_with_perm(m, perm, b_r, diag_align, permuted_cols,
                           index_dtype)


def pjds_to_dense(p: PJDSMatrix) -> np.ndarray:
    """Densify in the ORIGINAL basis (undoes row/col permutation)."""
    n_rows, n_cols = p.shape
    a = np.zeros((n_rows, n_cols), dtype=p.val.dtype)
    for b in range(p.n_blocks):
        s, e = int(p.block_start[b]), int(p.block_start[b + 1])
        for r in range(p.b_r):
            pos = b * p.b_r + r
            orig = int(p.perm[pos])
            if orig >= n_rows:
                continue
            for j in range(s, e):
                v = p.val[j, r]
                if v != 0:
                    c = int(p.col_idx[j, r])
                    if p.permuted_cols:
                        c = int(p.perm[c])
                    a[orig, c] += v
    return a


# --------------------------------------------------------------------------
# SELL-C-sigma (beyond paper: pJDS with a bounded sorting window)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SELLMatrix:
    """SELL-C-sigma: like pJDS but rows are sorted only inside windows of
    ``sigma`` rows, preserving locality of the original ordering.
    ``sigma = n_rows`` reproduces pJDS; ``sigma = C`` is pure sliced
    ELLPACK.  Storage layout is identical to :class:`PJDSMatrix`.
    """

    pjds: PJDSMatrix
    sigma: int


def windowed_sort_perm(rowlen: np.ndarray, sigma: int) -> np.ndarray:
    """Permutation sorting rows by DESCENDING length inside each window
    of ``sigma`` rows (stable within the window) — the SELL-C-sigma sort
    step, shared by the converter, the storage estimator, and the
    distributed partitioner so their padding always agrees.
    ``perm[p]`` = original row at sorted position ``p``;
    ``|perm[p] - p| < sigma`` for every entry."""
    rl = np.asarray(rowlen, dtype=np.int64)
    n = len(rl)
    perm = np.arange(n, dtype=np.int32)
    for w in range(0, n, sigma):
        hi = min(w + sigma, n)
        sub = np.argsort(-rl[w:hi], kind="stable")
        perm[w:hi] = (w + sub).astype(np.int32)
    return perm


def csr_to_sell(
    m: CSRMatrix,
    c: int = _DEFAULT_BR,
    sigma: int | None = None,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    permuted_cols: bool = True,
    index_dtype="auto",
) -> SELLMatrix:
    if sigma is None:
        sigma = 8 * c
    rl = m.row_lengths()
    n_pad = _pad_to(m.n_rows, c)
    rl_pad = np.zeros(n_pad, dtype=np.int64)
    rl_pad[: m.n_rows] = rl
    perm = windowed_sort_perm(rl_pad, sigma)
    # Reuse the pJDS constructor machinery by faking the sort: build a CSR
    # with rows pre-permuted, convert with an identity-sort guarantee, then
    # compose permutations.
    pj = _pjds_with_perm(m, perm, c, diag_align, permuted_cols, index_dtype)
    return SELLMatrix(pjds=pj, sigma=sigma)


def _pjds_with_perm(
    m: CSRMatrix,
    perm: np.ndarray,
    b_r: int,
    diag_align: int,
    permuted_cols: bool,
    index_dtype="auto",
) -> PJDSMatrix:
    """pJDS blocking with an externally supplied row permutation."""
    if permuted_cols and m.shape[0] != m.shape[1]:
        raise ValueError("symmetric permutation requires a square matrix")
    n_pad = len(perm)
    inv_perm = np.empty_like(perm)
    inv_perm[perm] = np.arange(n_pad, dtype=np.int32)
    rl = m.row_lengths()
    rl_pad = np.zeros(n_pad, dtype=np.int64)
    rl_pad[: m.n_rows] = rl
    sorted_rl = rl_pad[perm]
    n_blocks = n_pad // b_r
    block_len = np.zeros(n_blocks, dtype=np.int32)
    for b in range(n_blocks):
        blk = sorted_rl[b * b_r : (b + 1) * b_r]
        block_len[b] = _pad_to(max(int(blk.max(initial=0)), 1), diag_align)
    block_start = np.zeros(n_blocks + 1, dtype=np.int32)
    np.cumsum(block_len, out=block_start[1:])
    total = int(block_start[-1])
    # With a symmetric permutation the stored indices live in the PERMUTED
    # column space, whose addressable span is the padded row count.
    idt = resolve_index_dtype(index_dtype,
                              n_pad if permuted_cols else m.shape[1])
    val = np.zeros((total, b_r), dtype=m.data.dtype)
    col = np.full((total, b_r), PAD_COL, dtype=idt)
    for b in range(n_blocks):
        s = block_start[b]
        for r in range(b_r):
            p = b * b_r + r
            orig = perm[p]
            if orig >= m.n_rows:
                continue
            lo, hi = m.indptr[orig], m.indptr[orig + 1]
            cols_r = m.indices[lo:hi]
            if permuted_cols:
                cols_r = inv_perm[cols_r]
            val[s : s + (hi - lo), r] = m.data[lo:hi]
            col[s : s + (hi - lo), r] = cols_r.astype(idt)
    pj = PJDSMatrix(
        val=val,
        col_idx=col,
        block_start=block_start,
        block_len=block_len,
        rowlen=sorted_rl.astype(np.int32),
        perm=perm.astype(np.int32),
        inv_perm=inv_perm.astype(np.int32),
        shape=m.shape,
        b_r=b_r,
        n_rows_pad=n_pad,
        permuted_cols=permuted_cols,
    )
    if PAD_AUDIT:
        assert_padding_invariant(pj)
    return pj


def sell_to_dense(s: SELLMatrix) -> np.ndarray:
    return pjds_to_dense(s.pjds)


# --------------------------------------------------------------------------
# Windowed SELL-C-sigma: in-window columns as window-local offsets
# --------------------------------------------------------------------------
# x entries per step of a window's start: 8 sublane rows of 128 lanes,
# the tile the TPU compiler must be able to prove a window start aligned
# to (DESIGN.md §2).
WINDOW_UNIT = 1024
# Widest window, in units: the kernel gathers a (chunk_l, 128) tile with
# one lane gather and select per 128-entry row of the window, 8 per
# unit, so past 4 units (32 rows, 4096 entries) the in-tile gather costs
# more than the kernel's grid step itself.
WINDOW_UNITS_MAX = 4
# A window one unit wider must serve at least this share of the
# non-zeros more, or the narrower window is kept.
WINDOW_SHARE_TOL = 1e-3


@dataclasses.dataclass
class WindowPlan:
    """Where each sigma-window's x window lies, and which non-zeros it
    serves (:func:`window_plan`).  Columns ascend within a CSR row, so a
    row's in-window non-zeros are one run, ``[row_lo, row_lo + rowlen)``
    in the CSR arrays."""

    units: int              # window width in WINDOW_UNITs
    start: np.ndarray       # (n_sigma_windows,) int64 window start, units
    row_lo: np.ndarray      # (n_rows,) int64 first in-window non-zero
    rowlen: np.ndarray      # (n_rows,) int64 in-window non-zeros per row
    nnz: int
    x_len: int              # x padded to whole units, at least one window

    @property
    def window(self) -> int:
        """Window width in x entries."""
        return self.units * WINDOW_UNIT

    @property
    def share(self) -> float:
        """Share of the non-zeros served from a window."""
        return float(self.rowlen.sum()) / self.nnz if self.nnz else 0.0


def _window_anchors(n_win: int, sigma: int, x_units: int) -> np.ndarray:
    """Each sigma-window's anchor: the unit holding its middle row,
    clamped to x."""
    return np.minimum((np.arange(n_win, dtype=np.int64) * sigma
                       + sigma // 2) // WINDOW_UNIT, x_units - 1)


def window_reach(m: CSRMatrix, sigma: int) -> int:
    """The most non-zeros any :func:`window_plan` of ``m`` can serve:
    those whose unit lies within ``WINDOW_UNITS_MAX - 1`` units of their
    sigma-window's anchor, the span every width and start the plan
    weighs stays inside (clamped at x's ends too).  One pass over the
    non-zeros and no runs, so dispatch can tell cheaply that windows
    cannot pay before it makes the plan."""
    if m.nnz == 0:
        return 0
    n_rows, n_cols = m.shape
    u_max = WINDOW_UNITS_MAX
    n_win = max(-(-n_rows // sigma), 1)
    anchor = _window_anchors(n_win, sigma, max(-(-n_cols // WINDOW_UNIT), 1))
    win_nnz = np.diff(m.indptr[np.minimum(np.arange(n_win + 1) * sigma,
                                          n_rows)])
    # unit - (anchor - (u_max - 1)) lies in [0, 2 u_max - 2] within the
    # span; below it, negative, it wraps past that as unsigned
    off = m.indices // WINDOW_UNIT
    off -= np.repeat((anchor - (u_max - 1)).astype(off.dtype), win_nnz)
    return int(np.count_nonzero(off.view(f"u{off.itemsize}")
                                < 2 * u_max - 1))


def window_plan(m: CSRMatrix, sigma: int) -> WindowPlan:
    """Give every sigma-window of rows one x window: ``units`` aligned
    WINDOW_UNITs, the same width for the whole matrix, placed where it
    covers most of the window's non-zeros.

    The width is the smallest past which one more unit serves less than
    :data:`WINDOW_SHARE_TOL` more of the non-zeros, at most
    :data:`WINDOW_UNITS_MAX`.  Each window is anchored on the unit that
    holds its middle row and may start up to ``units - 1`` units before
    it, clamped to x; one histogram of the non-zeros' unit offsets from
    that anchor prices every width and start.  The non-zeros are counted
    in runs of one row and one unit (columns ascend within a row), so
    the per-non-zero work is a few vectorised passes."""
    n_rows, n_cols = m.shape
    u_max = WINDOW_UNITS_MAX
    n_win = max(-(-n_rows // sigma), 1)
    x_units = max(-(-n_cols // WINDOW_UNIT), 1)
    x_len = lambda u: max(x_units, u) * WINDOW_UNIT        # noqa: E731
    nnz = m.nnz
    if nnz == 0:
        zero = np.zeros(n_rows, np.int64)
        return WindowPlan(units=1, start=np.zeros(n_win, np.int64),
                          row_lo=zero, rowlen=zero, nnz=0, x_len=x_len(1))
    unit = m.indices // WINDOW_UNIT
    rows = np.flatnonzero(np.diff(m.indptr))        # rows with non-zeros
    row_first = np.zeros(nnz, bool)
    row_first[m.indptr[rows]] = True
    brk = row_first.copy()
    np.not_equal(unit[1:], unit[:-1], out=brk[1:])
    brk |= row_first
    run0 = np.flatnonzero(brk)
    del brk
    run_len = np.diff(np.append(run0, nnz))
    run_row = rows[np.cumsum(row_first[run0]) - 1]
    del row_first
    run_win = run_row // sigma
    anchor = _window_anchors(n_win, sigma, x_units)
    # bin u_max + (unit - anchor) for unit offsets within +-(u_max - 1),
    # bins 0 and 2 * u_max for those further below and above
    nb = 2 * u_max + 1
    rel = unit[run0] - (anchor - u_max)[run_win]
    np.clip(rel, 0, nb - 1, out=rel)
    hist = np.bincount(run_win * nb + rel, weights=run_len,
                       minlength=n_win * nb).astype(np.int64)
    cum = np.zeros((n_win, nb + 1), np.int64)
    np.cumsum(hist.reshape(n_win, nb), axis=1, out=cum[:, 1:])
    wins = np.arange(n_win)
    best = []
    for u in range(1, u_max + 1):
        top = max(x_units, u) - u
        served = np.full(n_win, -1, np.int64)
        start = np.zeros(n_win, np.int64)
        for d in range(1 - u, 1):
            s = np.clip(anchor + d, 0, top)
            a = s - anchor + u_max
            got = cum[wins, a + u] - cum[wins, a]
            better = got > served
            served = np.where(better, got, served)
            start = np.where(better, s, start)
        best.append((u, start, int(served.sum())))
    pick = best[-1]
    for (u, start, got), (_, _, wider) in zip(best, best[1:]):
        if (wider - got) / nnz < WINDOW_SHARE_TOL:
            pick = (u, start, got)
            break
    u, start, _ = pick
    lo = (start - anchor + u_max)[run_win]
    inside = (rel >= lo) & (rel < lo + u)
    rowlen = np.bincount(run_row[inside], weights=run_len[inside],
                         minlength=n_rows).astype(np.int64)
    # a row's in-window runs are consecutive: its run ends where the last
    # of them ends
    row_hi = m.indptr[:-1].astype(np.int64)
    row_hi[run_row[inside]] = run0[inside] + run_len[inside]
    return WindowPlan(units=u, start=start, row_lo=row_hi - rowlen,
                      rowlen=rowlen, nnz=nnz, x_len=x_len(u))


@dataclasses.dataclass
class WindowedSELLMatrix:
    """SELL-C-sigma whose row blocks each read one window of x.

    Rows are sorted by their IN-WINDOW length inside sigma-windows and
    blocked as in :class:`PJDSMatrix` (``(total, b_r)`` chunks, rows on
    lanes), with ``permuted_cols=False``.  Every row block reads the
    x window of its sigma-window: ``window`` entries from
    ``wbase[b] * WINDOW_UNIT``.  A stored slot keeps its column as an
    int16 offset into that window (``col_off``); padding slots store
    offset 0 and value 0, as the ``PAD_COL`` contract has it.  Non-zeros
    outside their block's window are not stored in the slots: they form
    the remainder, ``(rem_row, rem_col, rem_val)`` with ``rem_row`` the
    storage (sorted) row position, sorted by row, no padding.
    """

    val: np.ndarray         # (total, b_r)
    col_off: np.ndarray     # (total, b_r) int16, window-local
    block_start: np.ndarray # (n_blocks + 1,) int32
    block_len: np.ndarray   # (n_blocks,) int32
    wbase: np.ndarray       # (n_blocks,) int32, window start in units
    rowlen: np.ndarray      # (n_rows_pad,) int32 in-window, sorted order
    perm: np.ndarray        # (n_rows_pad,) int32
    inv_perm: np.ndarray    # (n_rows_pad,) int32
    rem_row: np.ndarray     # (n_rem,) int32, storage row, non-decreasing
    rem_col: np.ndarray     # (n_rem,) global column
    rem_val: np.ndarray     # (n_rem,)
    shape: Tuple[int, int]
    b_r: int
    n_rows_pad: int
    sigma: int
    window: int             # x entries per window
    x_len: int              # x padded to this length before the apply

    @property
    def n_blocks(self) -> int:
        return len(self.block_len)

    @property
    def total_jds(self) -> int:
        return self.val.shape[0]

    @property
    def window_share(self) -> float:
        """Share of the stored non-zeros served from a window."""
        nnz = int(self.rowlen.sum()) + len(self.rem_row)
        return int(self.rowlen.sum()) / nnz if nnz else 0.0


def csr_to_wsell(
    m: CSRMatrix,
    c: int = _DEFAULT_BR,
    sigma: int | None = None,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    index_dtype="auto",
    plan: WindowPlan | None = None,
) -> WindowedSELLMatrix:
    """Windowed SELL-C-sigma from CSR (vectorised numpy).  ``sigma``
    defaults to ``8 * c`` and must be a multiple of ``c``, so that every
    row block lies in one sigma-window; ``plan`` (from
    :func:`window_plan` with the same sigma) skips recomputing it.  The
    window offsets are int16 whatever ``index_dtype``; ``index_dtype``
    sets the remainder's global column dtype."""
    if sigma is None:
        sigma = 8 * c
    if sigma % c:
        raise ValueError(f"windowed SELL needs sigma ({sigma}) a multiple "
                         f"of the block height c ({c})")
    if plan is None:
        plan = window_plan(m, sigma)
    n_rows = m.n_rows
    n_pad = _pad_to(max(n_rows, 1), c)
    n_blocks = n_pad // c
    rl = np.diff(m.indptr)
    rl_in = np.zeros(n_pad, dtype=np.int64)
    rl_in[:n_rows] = plan.rowlen
    perm = windowed_sort_perm(rl_in, sigma)
    inv_perm = np.empty_like(perm)
    inv_perm[perm] = np.arange(n_pad, dtype=np.int32)
    sorted_rl = rl_in[perm]
    blk_max = np.maximum(sorted_rl.reshape(n_blocks, c).max(axis=1), 1)
    block_len = (-(-blk_max // diag_align) * diag_align).astype(np.int32)
    block_start = np.zeros(n_blocks + 1, dtype=np.int32)
    np.cumsum(block_len, out=block_start[1:])
    wbase = plan.start[np.arange(n_blocks) * c // sigma].astype(np.int32)

    # in-window slots: the row's j-th in-window non-zero goes to jagged
    # diagonal j of its block, on the lane of its sorted row position:
    # flat slot row0 + j * c, row0 = block_start * c + lane.  Within a
    # row the slot advances by c; a cumulative sum of those steps, with
    # each row's first step jumping to its row0, lays out every row.
    rl_row = plan.rowlen
    p = inv_perm[:n_rows].astype(np.int64)
    total = int(block_start[-1])
    n_in = int(rl_row.sum())
    idx = np.int32 if total * c < 2 ** 31 else np.int64
    row0 = block_start[p // c].astype(np.int64) * c + p % c
    has = np.flatnonzero(rl_row)
    first = np.cumsum(rl_row) - rl_row          # in-window entries before
    step = np.full(n_in, c, idx)
    last = np.append(0, (row0 + (rl_row - 1) * c)[has[:-1]])
    step[first[has]] = row0[has] - last
    flat = np.cumsum(step, dtype=idx)
    # +1 where a row's run starts, -1 after it ends: runs of different
    # rows are disjoint, so no index gets two starts or two ends
    edge = np.zeros(m.nnz + 1, np.int8)
    edge[plan.row_lo[has]] += 1
    edge[(plan.row_lo + rl_row)[has]] -= 1
    inside = np.cumsum(edge[:-1], dtype=np.int8).view(bool)
    val = np.zeros(total * c, dtype=m.data.dtype)
    col_off = np.full(total * c, PAD_COL, dtype=np.int16)
    val[flat] = m.data[inside]
    col_off[flat] = m.indices[inside] - np.repeat(
        (wbase[p // c] * WINDOW_UNIT).astype(np.int32), rl_row)

    # the remainder: the rows' non-zeros before and after their run
    part = np.flatnonzero(rl_row < rl)
    cnt = rl[part]
    e_row = np.repeat(part, cnt)
    e = (np.repeat(m.indptr[part] - (np.cumsum(cnt) - cnt), cnt)
         + np.arange(int(cnt.sum()), dtype=np.int64))
    lo = plan.row_lo[e_row]
    keep = (e < lo) | (e >= lo + rl_row[e_row])
    out, rem_pos = e[keep], inv_perm[e_row[keep]]
    order = np.argsort(rem_pos, kind="stable")   # CSR keeps columns sorted
    idt = resolve_index_dtype(index_dtype, m.shape[1])
    w = WindowedSELLMatrix(
        val=val.reshape(total, c), col_off=col_off.reshape(total, c),
        block_start=block_start, block_len=block_len, wbase=wbase,
        rowlen=sorted_rl.astype(np.int32), perm=perm.astype(np.int32),
        inv_perm=inv_perm.astype(np.int32),
        rem_row=rem_pos[order].astype(np.int32),
        rem_col=m.indices[out[order]].astype(idt),
        rem_val=m.data[out[order]],
        shape=m.shape, b_r=c, n_rows_pad=n_pad, sigma=sigma,
        window=plan.window, x_len=plan.x_len)
    if PAD_AUDIT:
        assert_padding_invariant(w)
    return w


def wsell_to_dense(w: WindowedSELLMatrix) -> np.ndarray:
    """Densify in the ORIGINAL basis: the slots at their window's
    columns, plus the remainder."""
    a = np.zeros(w.shape, dtype=w.val.dtype)
    blk = np.repeat(np.arange(w.n_blocks), w.block_len)
    pos = blk[:, None] * w.b_r + np.arange(w.b_r)[None, :]
    cols = w.wbase[blk][:, None].astype(np.int64) * WINDOW_UNIT + w.col_off
    keep = w.val != 0
    np.add.at(a, (w.perm[pos[keep]], cols[keep]), w.val[keep])
    np.add.at(a, (w.perm[w.rem_row], w.rem_col.astype(np.int64)), w.rem_val)
    return a


# --------------------------------------------------------------------------
# CMRS — Compressed Multi-Row Storage (arXiv:1203.2946), TPU-blocked
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CMRSMatrix:
    """CMRS adapted to the TPU tiling: rows stay in ORIGINAL order (no
    sort, no permutation epilogue) and are grouped into *strips* of
    ``b_r`` consecutive rows.  Each strip's nonzeros are packed densely,
    row-major, into ``(strip_su, b_r)`` lane-major tiles: entry ``k`` of
    a strip lands at sublane ``k // b_r``, lane ``k % b_r`` relative to
    the strip's first sublane-row.  ``row_in_strip`` is the paper's
    per-entry row stream (int8, values in ``[0, b_r)``) that routes each
    slot back to its row inside the strip.

    ``strip_su[s] = ceil(strip_nnz / b_r)`` padded to ``diag_align``
    (min 1); ``strip_start`` is its exclusive prefix sum in sublane-rows,
    so strip ``s`` owns tile rows ``strip_start[s]:strip_start[s+1]``.
    Padding slots carry the usual sentinel (``val == 0``,
    ``col == PAD_COL``) plus ``row_in_strip == 0``; ``strip_nnz`` keeps
    the true per-strip count so the pad audit and ``cmrs_to_dense`` can
    tell padding from stored entries exactly.

    Storage is ~``nnz`` padded to tile granularity — per-row padding
    vanishes entirely, which is where CMRS beats ELLPACK/pJDS on
    power-law patterns — at the cost of ``b_r`` flops per slot in the
    kernel's one-hot segment reduction (``perf_model.cmrs_reduce_seconds``).
    """

    val: np.ndarray            # (total_su, b_r)
    col_idx: np.ndarray        # (total_su, b_r) int16/int32
    row_in_strip: np.ndarray   # (total_su, b_r) int8
    strip_start: np.ndarray    # (n_strips + 1,) int32, sublane-row offsets
    strip_len: np.ndarray      # (n_strips,) int32 == diff(strip_start)
    strip_nnz: np.ndarray      # (n_strips,) int64, true nonzeros per strip
    shape: Tuple[int, int]
    b_r: int
    n_rows_pad: int

    @property
    def n_strips(self) -> int:
        return len(self.strip_len)

    @property
    def total_su(self) -> int:
        return int(self.strip_start[-1])


def csr_to_cmrs(
    m: CSRMatrix,
    b_r: int = _DEFAULT_BR,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    index_dtype="auto",
) -> CMRSMatrix:
    """Pack ``m`` into CMRS strips of ``b_r`` rows (original order)."""
    n = m.n_rows
    n_pad = _pad_to(max(n, 1), b_r)
    n_strips = n_pad // b_r
    rl = m.row_lengths()
    idt = resolve_index_dtype(index_dtype, m.n_cols)

    strip_nnz = np.zeros(n_strips, dtype=np.int64)
    counts = np.add.reduceat(
        np.concatenate([rl, np.zeros(n_pad - n, dtype=rl.dtype)]),
        np.arange(0, n_pad, b_r))
    strip_nnz[:] = counts
    strip_len = np.array(
        [_pad_to(max(-(-int(c) // b_r), 1), diag_align) for c in strip_nnz],
        dtype=np.int32)
    strip_start = np.zeros(n_strips + 1, dtype=np.int32)
    np.cumsum(strip_len, out=strip_start[1:])

    total = int(strip_start[-1])
    val = np.zeros((total, b_r), dtype=m.data.dtype)
    col = np.full((total, b_r), PAD_COL, dtype=idt)
    ris = np.zeros((total, b_r), dtype=np.int8)
    for s in range(n_strips):
        r0, r1 = s * b_r, min((s + 1) * b_r, n)
        lo, hi = int(m.indptr[r0]), int(m.indptr[r1])
        cnt = hi - lo
        if cnt == 0:
            continue
        su = int(strip_len[s])
        flat_v = np.zeros(su * b_r, dtype=m.data.dtype)
        flat_c = np.full(su * b_r, PAD_COL, dtype=idt)
        flat_r = np.zeros(su * b_r, dtype=np.int8)
        flat_v[:cnt] = m.data[lo:hi]
        flat_c[:cnt] = m.indices[lo:hi].astype(idt)
        flat_r[:cnt] = np.repeat(
            np.arange(r1 - r0, dtype=np.int64), rl[r0:r1]).astype(np.int8)
        s0 = int(strip_start[s])
        val[s0 : s0 + su] = flat_v.reshape(su, b_r)
        col[s0 : s0 + su] = flat_c.reshape(su, b_r)
        ris[s0 : s0 + su] = flat_r.reshape(su, b_r)

    cm = CMRSMatrix(
        val=val, col_idx=col, row_in_strip=ris,
        strip_start=strip_start, strip_len=strip_len, strip_nnz=strip_nnz,
        shape=m.shape, b_r=b_r, n_rows_pad=n_pad)
    if PAD_AUDIT:
        assert_padding_invariant(cm)
    return cm


def cmrs_to_dense(c: CMRSMatrix) -> np.ndarray:
    a = np.zeros(c.shape, dtype=c.val.dtype)
    for s in range(c.n_strips):
        s0, su = int(c.strip_start[s]), int(c.strip_len[s])
        cnt = int(c.strip_nnz[s])
        v = c.val[s0 : s0 + su].reshape(-1)[:cnt]
        ci = c.col_idx[s0 : s0 + su].reshape(-1)[:cnt]
        ri = c.row_in_strip[s0 : s0 + su].reshape(-1)[:cnt]
        np.add.at(a, (s * c.b_r + ri.astype(np.int64), ci.astype(np.int64)), v)
    return a


# --------------------------------------------------------------------------
# Transpose metadata (the operator protocol's rmatvec "device" path)
# --------------------------------------------------------------------------
def csr_transpose(m: CSRMatrix) -> CSRMatrix:
    """A^T as a host CSR — i.e. the CSC view of ``m`` re-read as CSR.

    This is the "CSC-of-blocks" build of the operator protocol: feeding
    the result through the normal blocked converters gives a device
    representation whose FORWARD kernels compute ``A^T x``, so the
    transpose path reuses the gather-structured spMVM instead of a
    scatter (DESIGN.md §8).

    Each non-zero moves to its column's row of A^T in its row order (a
    stable order by column), so the rows of A^T keep their columns
    ascending and a duplicate of ``m`` stays a duplicate, which matvec
    sums either way.  The stable order is one sort of the int64 keys
    ``column << b | position``, which a 31-bit column and a position
    below 2**32 fit; it is several times quicker than a stable argsort
    or a 16-bit radix one.  Each column's row of A^T starts at its
    count's running sum."""
    n_rows, n_cols = m.shape
    nnz = len(m.indices)
    shift = max((nnz - 1).bit_length(), 1)
    order = m.indices.astype(np.int64)
    order <<= shift
    order |= np.arange(nnz, dtype=np.int64)
    order.sort()
    order &= (1 << shift) - 1
    indptr = np.zeros(n_cols + 1, np.int64)
    np.cumsum(np.bincount(m.indices, minlength=n_cols), out=indptr[1:])
    rows = np.repeat(np.arange(n_rows, dtype=np.int32), m.row_lengths())
    return CSRMatrix(indptr, rows[order], m.data[order], (n_cols, n_rows))


def csr_diagonal(m: CSRMatrix) -> np.ndarray:
    """diag(A) for a square CSR (missing entries are 0) — the Jacobi
    preconditioner's input, extracted once host-side."""
    if m.shape[0] != m.shape[1]:
        raise ValueError("diagonal requires a square matrix")
    d = np.zeros(m.n_rows, dtype=m.data.dtype)
    rows = np.repeat(np.arange(m.n_rows, dtype=np.int64), m.row_lengths())
    on_diag = m.indices == rows
    # accumulate (not assign): duplicate (i, i) entries sum in matvec,
    # so the diagonal must agree
    np.add.at(d, rows[on_diag], m.data[on_diag])
    return d


# --------------------------------------------------------------------------
# Structural fingerprint (the autotuner's cache key component)
# --------------------------------------------------------------------------
@obs.span("repro.fingerprint")
def structural_fingerprint(m: CSRMatrix) -> str:
    """sha1 digest of the matrix STRUCTURE: shape + indptr + indices,
    deliberately excluding the stored values.

    Every quantity the tuner's search space and the perf model depend on
    — row lengths, padding, column spans, halo coupling — is a function
    of the structure alone, so tuned kernel statics transfer across
    value updates (a solver re-assembling coefficients on a fixed mesh
    keeps its cache hit), while any structural edit (new entry, reorder,
    resize) changes the digest and invalidates the cached decision.
    """
    h = hashlib.sha1()
    h.update(np.asarray(m.shape, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(m.indptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(m.indices, dtype=np.int64).tobytes())
    return h.hexdigest()


# --------------------------------------------------------------------------
# Distributed-partition helper: measured halo coupling
# --------------------------------------------------------------------------
def csr_remote_columns_by_distance(
    sl: CSRMatrix, p: int, n_loc: int, n_dev: int
) -> dict:
    """For device ``p``'s row slice ``sl`` (a CSR over the GLOBAL column
    space) under a uniform n_loc-row ring partition: the slice-local
    column indices it references in each OTHER device's slice, keyed by
    signed ring distance d (owner = (p + d) % n_dev, |d| <= n_dev//2).

    Each value is sorted and unique — the gather set of the paper's
    "local gather + point-to-point" halo exchange, i.e. exactly the
    entries of the neighbor's x slice that must cross the wire.
    """
    cols = sl.indices.astype(np.int64)
    own_lo, own_hi = p * n_loc, (p + 1) * n_loc
    rcols = cols[(cols < own_lo) | (cols >= own_hi)]
    owner = rcols // n_loc
    d = (owner - p + n_dev) % n_dev
    d = np.where(d > n_dev // 2, d - n_dev, d)
    return {
        int(dd): np.unique(rcols[d == dd] % n_loc).astype(np.int32)
        for dd in np.unique(d)
    }


# --------------------------------------------------------------------------
# Padding-sentinel audit
# --------------------------------------------------------------------------
def _check_pad(name: str, val_pad: np.ndarray, col_pad: np.ndarray) -> None:
    if val_pad.size and np.any(val_pad != 0):
        raise AssertionError(
            f"{name}: padded entries carry non-zero values — the unmasked "
            f"kernels would add them into y")
    if col_pad.size and np.any(col_pad != PAD_COL):
        raise AssertionError(
            f"{name}: padded entries carry column != PAD_COL ({PAD_COL}) — "
            f"the RHS gather would touch arbitrary (possibly stale-halo) "
            f"entries of x")


def assert_padding_invariant(fmt) -> None:
    """Audit the padding sentinel invariant (see :data:`PAD_COL`): every
    padded slot of a blocked format must store ``val == 0`` and
    ``col_idx == PAD_COL``.  Raises AssertionError on violation.  Called
    by the converters when :data:`PAD_AUDIT` is set (debug builds);
    callable directly on any format object."""
    if isinstance(fmt, SELLMatrix):
        fmt = fmt.pjds
    if isinstance(fmt, ELLMatrix):
        j = np.arange(fmt.val.shape[0])[:, None]
        pad = j >= fmt.rowlen[None, :]
        _check_pad("ELLMatrix", fmt.val[pad], fmt.col_idx[pad])
        return
    if isinstance(fmt, PJDSMatrix):
        for b in range(fmt.n_blocks):
            s, e = int(fmt.block_start[b]), int(fmt.block_start[b + 1])
            rl = fmt.rowlen[b * fmt.b_r : (b + 1) * fmt.b_r]  # sorted order
            j = np.arange(e - s)[:, None]
            pad = j >= rl[None, :]
            _check_pad(f"PJDSMatrix block {b}", fmt.val[s:e][pad],
                       fmt.col_idx[s:e][pad])
        return
    if isinstance(fmt, WindowedSELLMatrix):
        blk = np.repeat(np.arange(fmt.n_blocks), fmt.block_len)
        j = np.arange(fmt.total_jds) - fmt.block_start[blk]
        lane_rl = fmt.rowlen.reshape(fmt.n_blocks, fmt.b_r)[blk]
        pad = j[:, None] >= lane_rl
        _check_pad("WindowedSELLMatrix", fmt.val[pad], fmt.col_off[pad])
        return
    if isinstance(fmt, CMRSMatrix):
        for s in range(fmt.n_strips):
            s0, su = int(fmt.strip_start[s]), int(fmt.strip_len[s])
            cnt = int(fmt.strip_nnz[s])
            v = fmt.val[s0 : s0 + su].reshape(-1)[cnt:]
            c = fmt.col_idx[s0 : s0 + su].reshape(-1)[cnt:]
            _check_pad(f"CMRSMatrix strip {s}", v, c)
            r = fmt.row_in_strip[s0 : s0 + su].reshape(-1)[cnt:]
            if r.size and np.any(r != 0):
                raise AssertionError(
                    f"CMRSMatrix strip {s}: padded entries carry "
                    f"row_in_strip != 0 — the segment reduction would "
                    f"scatter stale zeros into arbitrary rows")
        return
    if isinstance(fmt, CSRMatrix):
        return              # CSR stores no padding
    raise TypeError(type(fmt))


# --------------------------------------------------------------------------
# Memory accounting (paper Table 1, "data reduction" column)
# --------------------------------------------------------------------------
def storage_elements(fmt) -> int:
    """Number of stored value elements (incl. padding zeros) — the paper's
    measure for the ELLPACK-vs-pJDS comparison."""
    if isinstance(fmt, CSRMatrix):
        return fmt.nnz
    if isinstance(fmt, ELLMatrix):
        return int(fmt.val.size)
    if isinstance(fmt, PJDSMatrix):
        return int(fmt.val.size)
    if isinstance(fmt, SELLMatrix):
        return int(fmt.pjds.val.size)
    if isinstance(fmt, CMRSMatrix):
        return int(fmt.val.size)
    if isinstance(fmt, WindowedSELLMatrix):
        return int(fmt.val.size) + len(fmt.rem_val)
    raise TypeError(type(fmt))


def format_nbytes(fmt, value_bytes: int | None = None,
                  index_bytes: int | None = None) -> int:
    """Total footprint: values + column indices + per-format metadata.

    ``value_bytes`` / ``index_bytes`` default to the widths ACTUALLY
    stored (so an int16-index / bf16-value build reports its compressed
    footprint); pass explicit widths to price a hypothetical storage
    precision instead."""
    if isinstance(fmt, SELLMatrix):
        return format_nbytes(fmt.pjds, value_bytes, index_bytes)
    if isinstance(fmt, WindowedSELLMatrix):
        vb = fmt.val.dtype.itemsize if value_bytes is None else value_bytes
        ib = fmt.col_off.dtype.itemsize if index_bytes is None \
            else index_bytes
        # slots + remainder (value, global column, row) + block offsets,
        # window starts and the row permutation
        return (fmt.val.size * (vb + ib)
                + len(fmt.rem_val) * (vb + fmt.rem_col.dtype.itemsize + 4)
                + (2 * fmt.n_blocks + 1) * 4 + fmt.n_rows_pad * 4)
    if value_bytes is None:
        value_bytes = (fmt.data if isinstance(fmt, CSRMatrix)
                       else fmt.val).dtype.itemsize
    if index_bytes is None:
        index_bytes = (fmt.indices if isinstance(fmt, CSRMatrix)
                       else fmt.col_idx).dtype.itemsize
    e = storage_elements(fmt)
    base = e * (value_bytes + index_bytes)
    if isinstance(fmt, CSRMatrix):
        return base + (fmt.n_rows + 1) * 8
    if isinstance(fmt, ELLMatrix):
        return base + fmt.n_rows_pad * 4          # rowlen (ELLPACK-R)
    if isinstance(fmt, PJDSMatrix):
        return base + (fmt.n_blocks + 1) * 4 + fmt.n_rows_pad * 4  # col_start + perm
    if isinstance(fmt, CMRSMatrix):
        # + the int8 row-in-strip stream and the strip offsets
        return base + e * 1 + (fmt.n_strips + 1) * 4
    raise TypeError(type(fmt))


def data_reduction_vs_ellpack(m: CSRMatrix, b_r: int = _DEFAULT_BR) -> float:
    """Paper Table 1: fraction of ELLPACK storage saved by pJDS."""
    ell = csr_to_ell(m, row_align=b_r)
    pj = csr_to_pjds(m, b_r=b_r, permuted_cols=(m.shape[0] == m.shape[1]))
    return 1.0 - storage_elements(pj) / storage_elements(ell)


# --------------------------------------------------------------------------
# Storage estimators from row lengths alone (no matrix build).
# The dispatch layer (kernels.ops.select_format) prices each candidate
# format with these before converting anything.
# --------------------------------------------------------------------------
def windowed_block_lengths(
    rowlen: np.ndarray,
    b_r: int = _DEFAULT_BR,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    sigma: int | None = None,
) -> np.ndarray:
    """Per-block padded jagged-diagonal counts of a blocked (pJDS / SELL)
    layout, computed from row lengths alone.  ``sigma=None`` is the global
    sort (pJDS); ``sigma <= b_r`` degenerates to no sort (sliced ELLPACK).
    Matches the ``block_len`` the real converters produce."""
    rl = np.asarray(rowlen, dtype=np.int64)
    n_pad = _pad_to(max(len(rl), 1), b_r)
    rl_pad = np.zeros(n_pad, dtype=np.int64)
    rl_pad[: len(rl)] = rl
    if sigma is None or sigma >= n_pad:
        srt = -np.sort(-rl_pad)
    else:
        srt = rl_pad[windowed_sort_perm(rl_pad, sigma)]
    blk_max = srt.reshape(-1, b_r).max(axis=1)
    return np.array(
        [_pad_to(max(int(b), 1), diag_align) for b in blk_max], dtype=np.int32
    )


def estimate_storage_elements(
    rowlen: np.ndarray,
    fmt: str,
    b_r: int = _DEFAULT_BR,
    diag_align: int = _DEFAULT_DIAG_ALIGN,
    sigma: int | None = None,
) -> int:
    """Stored value elements (incl. padding) a format WOULD use, from row
    lengths alone.  Agrees with ``storage_elements`` on the built matrix."""
    rl = np.asarray(rowlen, dtype=np.int64)
    if fmt == "csr":
        return int(rl.sum())
    if fmt in ("ellpack", "ellpack_r"):
        n_pad = _pad_to(max(len(rl), 1), b_r)
        return n_pad * _pad_to(max(int(rl.max(initial=0)), 1), diag_align)
    if fmt == "pjds":
        return int(windowed_block_lengths(rl, b_r, diag_align, None).sum()) * b_r
    if fmt == "sell":
        if sigma is None:
            sigma = 8 * b_r
        return int(windowed_block_lengths(rl, b_r, diag_align, sigma).sum()) * b_r
    if fmt == "cmrs":
        n_pad = _pad_to(max(len(rl), 1), b_r)
        rl_pad = np.zeros(n_pad, dtype=np.int64)
        rl_pad[: len(rl)] = rl
        strip_nnz = rl_pad.reshape(-1, b_r).sum(axis=1)
        su = np.array(
            [_pad_to(max(-(-int(c) // b_r), 1), diag_align)
             for c in strip_nnz], dtype=np.int64)
        return int(su.sum()) * b_r
    raise ValueError(f"unknown format {fmt!r}")
