"""Pure-jnp oracles for the sparse kernels (the ``ref.py`` layer).

Each function is the mathematical specification of the matching Pallas
kernel, written with plain vectorised jnp ops (no pallas, no control
flow).  Tests assert ``allclose(kernel, ref)`` over shape/dtype sweeps;
the distributed layer and benchmarks also use these as a fast jittable
fallback on CPU.

All refs operate on the DEVICE layout produced by ``ops.to_device_*``:
zero padding in ``val`` and clamped-valid padding in ``col_idx`` make
masking unnecessary for correctness (padded terms contribute 0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import WINDOW_UNIT

from ._backend import acc_dtype as _acc_dtype

__all__ = ["pjds_matvec_ref", "pjds_matmat_ref", "ell_matvec_ref",
           "csr_matvec_ref", "window_columns",
           "remainder_matvec_ref", "remainder_rmatvec_ref",
           "csr_rmatvec_ref", "ell_rmatvec_ref", "blocked_rmatvec_ref",
           "cmrs_matvec_ref", "cmrs_rmatvec_ref",
           "partial_reduce_epilogue_ref"]


def pjds_matvec_ref(val: jax.Array, col_idx: jax.Array, row_block: jax.Array,
                    x: jax.Array, n_blocks: int) -> jax.Array:
    """pJDS y = A x in the permuted basis (paper Listing 2).

    val/col_idx: (total_jds, b_r); row_block: (total_jds,) int32 mapping
    each jagged-diagonal row to its pJDS row block; x: (n_pad,).
    Returns y: (n_blocks * b_r,).
    """
    b_r = val.shape[1]
    dt = _acc_dtype(val.dtype, x.dtype)
    gathered = x[col_idx].astype(dt) * val.astype(dt)      # (total_jds, b_r)
    y_blk = jax.ops.segment_sum(gathered, row_block, num_segments=n_blocks)
    return y_blk.reshape(n_blocks * b_r)


def pjds_matmat_ref(val: jax.Array, col_idx: jax.Array, row_block: jax.Array,
                    x: jax.Array, n_blocks: int) -> jax.Array:
    """pJDS Y = A X, multi-RHS.  x: (n_pad, n_rhs) -> (n_blocks*b_r, n_rhs)."""
    b_r = val.shape[1]
    dt = _acc_dtype(val.dtype, x.dtype)
    gathered = x[col_idx].astype(dt)                       # (total, b_r, n_rhs)
    contrib = gathered * val.astype(dt)[..., None]
    y_blk = jax.ops.segment_sum(contrib, row_block, num_segments=n_blocks)
    return y_blk.reshape(n_blocks * b_r, x.shape[1])


def window_columns(col_off: jax.Array, wbase: jax.Array,
                   row_block: jax.Array) -> jax.Array:
    """Global columns of a windowed SELL operand's slots, rebuilt from
    the window-local offsets: ``wbase[block] * WINDOW_UNIT + offset``.
    Padding slots land on their window's first entry, inside the padded
    x, and meet a zero value."""
    return (wbase[row_block][:, None] * WINDOW_UNIT
            + col_off.astype(jnp.int32))


def remainder_matvec_ref(rem_val: jax.Array, rem_row: jax.Array,
                         rem_col: jax.Array, x: jax.Array,
                         n_rows_pad: int) -> jax.Array:
    """The windowed SELL remainder's y = A_out x: a gather of x and a
    sorted segment sum into the storage rows.  x: (n,) or (n, k);
    returns (n_rows_pad[, k])."""
    dt = _acc_dtype(rem_val.dtype, x.dtype)
    xg = x[rem_col.astype(jnp.int32)].astype(dt)
    v = rem_val.astype(dt)
    contrib = v[:, None] * xg if xg.ndim == 2 else v * xg
    return jax.ops.segment_sum(contrib, rem_row, num_segments=n_rows_pad,
                               indices_are_sorted=True)


def remainder_rmatvec_ref(rem_val: jax.Array, rem_row: jax.Array,
                          rem_col: jax.Array, y: jax.Array,
                          n_cols: int) -> jax.Array:
    """Transpose of :func:`remainder_matvec_ref`: y in the storage row
    order, (n_rows_pad[, k]) -> (n_cols[, k])."""
    dt = _acc_dtype(rem_val.dtype, y.dtype)
    yg = y[rem_row].astype(dt)
    v = rem_val.astype(dt)
    contrib = v[:, None] * yg if yg.ndim == 2 else v * yg
    return jax.ops.segment_sum(contrib, rem_col.astype(jnp.int32),
                               num_segments=n_cols)


def partial_reduce_epilogue_ref(y_sorted: jax.Array, own_pos: jax.Array,
                                red_send_pos: jax.Array, red_lens: tuple):
    """Local half of the 2-D partial-sum reduction epilogue.

    A 2-D-partitioned device's kernel output ``y_sorted`` holds PARTIAL
    sums for its whole row block in the SORTED row basis.  The epilogue
    never unpermutes the full block: it gathers the device's OWN y slice
    (``own_pos``, the sorted positions of its segment) and, per grid-row
    ring distance, the compact buffer of partial rows to ship
    (``red_send_pos[kk, :red_lens[kk]]``; padding lanes gather position 0
    and are dropped by the receiver's scatter sentinel).  The collective
    ppermute + scatter-add lives in ``core.dist_spmv``; this function is
    the kernel-side, unit-testable piece.

    Returns ``(y_own, bufs)`` with one buffer per entry of ``red_lens``
    (``None`` for empty distances).
    """
    y_own = y_sorted[own_pos]
    bufs = [y_sorted[red_send_pos[kk, :h]] if h else None
            for kk, h in enumerate(red_lens)]
    return y_own, bufs


def cmrs_matvec_ref(val: jax.Array, col_idx: jax.Array,
                    row_in_strip: jax.Array, strip_map: jax.Array,
                    x: jax.Array, n_strips: int) -> jax.Array:
    """CMRS y = A x in the ORIGINAL row order (no permutation).

    val/col_idx/row_in_strip: (total_su, b_r); strip_map: (total_su,)
    int32 mapping each sublane-row to its strip.  Each slot scatters to
    global row ``strip_map * b_r + row_in_strip`` — padding slots carry
    val == 0 so their scatter target (row 0 of the strip) is harmless.
    x: (n_pad,) or (n_pad, k); returns (n_strips * b_r[, k]).
    """
    b_r = val.shape[1]
    dt = _acc_dtype(val.dtype, x.dtype)
    rows = strip_map[:, None] * b_r + row_in_strip.astype(jnp.int32)
    gathered = x[col_idx].astype(dt)           # (total_su, b_r[, k])
    v = val.astype(dt)
    contrib = gathered * (v[..., None] if gathered.ndim == 3 else v)
    flat = contrib.reshape(-1, *contrib.shape[2:])
    return jax.ops.segment_sum(flat, rows.reshape(-1),
                               num_segments=n_strips * b_r)


def cmrs_rmatvec_ref(val: jax.Array, col_idx: jax.Array,
                     row_in_strip: jax.Array, strip_map: jax.Array,
                     y: jax.Array, n_cols: int) -> jax.Array:
    """CMRS z = A^T y: gather y at each slot's global row, scatter by
    column.  y: (n_rows_pad,) or (n_rows_pad, k); returns (n_cols[, k])."""
    b_r = val.shape[1]
    dt = _acc_dtype(val.dtype, y.dtype)
    rows = strip_map[:, None] * b_r + row_in_strip.astype(jnp.int32)
    gathered = y[rows].astype(dt)              # (total_su, b_r[, k])
    v = val.astype(dt)
    contrib = gathered * (v[..., None] if gathered.ndim == 3 else v)
    flat = contrib.reshape(-1, *contrib.shape[2:])
    return jax.ops.segment_sum(flat, col_idx.reshape(-1).astype(jnp.int32),
                               num_segments=n_cols)


def csr_matvec_ref(data: jax.Array, indices: jax.Array, row_ids: jax.Array,
                   x: jax.Array, n_rows: int) -> jax.Array:
    """CSR y = A x as a flat gather + segment-sum over the nnz stream —
    the dispatch layer's fallback for matrices too small/empty to be
    worth a blocked format (no Pallas kernel: the irregular baseline).
    ``x`` may carry a trailing RHS-block axis: (n,) or (n, k)."""
    dt = _acc_dtype(data.dtype, x.dtype)
    xg = x[indices].astype(dt)                 # (nnz,) or (nnz, k)
    d = data.astype(dt)
    contrib = d[:, None] * xg if xg.ndim == 2 else d * xg
    return jax.ops.segment_sum(contrib, row_ids, num_segments=n_rows)


def csr_rmatvec_ref(data: jax.Array, indices: jax.Array, row_ids: jax.Array,
                    y: jax.Array, n_cols: int) -> jax.Array:
    """CSR x = A^T y via the SWAPPED gather: read y along rows, scatter-
    accumulate along columns (segment ids = the column stream).  ``y``
    may carry a trailing RHS-block axis: (n_rows,) or (n_rows, k)."""
    dt = _acc_dtype(data.dtype, y.dtype)
    yg = y[row_ids].astype(dt)                 # (nnz,) or (nnz, k)
    d = data.astype(dt)
    contrib = d[:, None] * yg if yg.ndim == 2 else d * yg
    return jax.ops.segment_sum(contrib, indices, num_segments=n_cols)


def ell_rmatvec_ref(val: jax.Array, col_idx: jax.Array, rowlen: jax.Array,
                    y: jax.Array, n_cols: int) -> jax.Array:
    """ELLPACK-R x = A^T y: per-entry scatter-accumulate into the column
    space.  y: (n_pad,) or (n_pad, k) in STORAGE row order."""
    dt = _acc_dtype(val.dtype, y.dtype)
    j = jnp.arange(val.shape[0], dtype=jnp.int32)[:, None]
    mask = j < rowlen[None, :]
    v = jnp.where(mask, val, 0).astype(dt)
    contrib = v[..., None] * y.astype(dt)[None, :] if y.ndim == 2 \
        else v * y.astype(dt)[None, :]
    flat = contrib.reshape(-1, *contrib.shape[2:])
    return jax.ops.segment_sum(flat, col_idx.reshape(-1),
                               num_segments=n_cols)


def blocked_rmatvec_ref(val: jax.Array, col_idx: jax.Array,
                        row_block: jax.Array, y: jax.Array,
                        n_cols: int) -> jax.Array:
    """pJDS/SELL x = A^T y: the transpose of the blocked gather is a
    scatter-accumulate over ``col_idx`` (rows read from y at the entry's
    permuted row position).  y: (n_rows_pad,) or (n_rows_pad, k) in the
    PERMUTED (storage) basis."""
    b_r = val.shape[1]
    dt = _acc_dtype(val.dtype, y.dtype)
    rows = row_block[:, None] * b_r + jnp.arange(b_r, dtype=jnp.int32)[None]
    yg = y[rows].astype(dt)                    # (total_jds, b_r[, k])
    v = val.astype(dt)
    contrib = v[..., None] * yg if yg.ndim == 3 else v * yg
    flat = contrib.reshape(-1, *contrib.shape[2:])
    return jax.ops.segment_sum(flat, col_idx.reshape(-1),
                               num_segments=n_cols)


def ell_matvec_ref(val: jax.Array, col_idx: jax.Array, rowlen: jax.Array,
                   x: jax.Array) -> jax.Array:
    """ELLPACK-R y = A x (paper Listing 1), jagged-diagonal-major layout.

    val/col_idx: (max_nzr, n_pad); rowlen: (n_pad,); x: (n_pad_cols,) or
    (n_pad_cols, k) for a block of RHS vectors.
    The rowlen mask reproduces ELLPACK-R semantics exactly (padded values
    are zero anyway, but masking keeps NaN/Inf padding safe).
    """
    dt = _acc_dtype(val.dtype, x.dtype)
    j = jnp.arange(val.shape[0], dtype=jnp.int32)[:, None]
    mask = j < rowlen[None, :]
    xg = x[col_idx].astype(dt)           # (max_nzr, n_pad[, k])
    v = val.astype(dt)
    if xg.ndim == 3:
        v, mask = v[..., None], mask[..., None]
    contrib = jnp.where(mask, xg * v, 0)
    return contrib.sum(axis=0)
