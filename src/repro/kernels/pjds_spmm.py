"""Pallas TPU kernel for pJDS sparse matrix x dense matrix (multi-RHS).

Y = A_pjds @ X with X: (n_cols, n_rhs).  This is the kernel behind
``repro.sparse.SparseFFN`` (pJDS-stored pruned FFN weights applied to a
batch of activations) and the block solvers — the paper's format
promoted to a first-class LM feature (DESIGN.md §4).

It rides the grouped grid of the spMV kernels
(``_backend.grouped_matvec_call``).  The RHS gather runs in XLA ahead
of the kernel, one RHS tile of ``rhs_t`` columns at a time, into a
``(rhs_t, total_jds, b_r)`` stream — RHS columns on the leading axis,
so every tile keeps the matrix's (sublane, lane) layout and a narrow
block (k ~ 4 in the distributed block solvers) pads no lanes.  Each
stored value then multiplies ``rhs_t`` gathered RHS entries, which
lifts the arithmetic intensity from the spMVM's ~2/12 flop/byte toward
~2*rhs_t/(12 + 8*rhs_t) per streamed slot.  int16 index / bf16 value
streams cut the per-nonzero matrix bytes the same way they do for the
spMVM kernels; accumulation stays f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._backend import acc_dtype, gather_rhs, grouped_matvec_call

__all__ = ["pjds_matmat_kernel_call"]


def _rhs_row_sum(dt):
    def reduce_rows(val, xg):                 # (cl, b_r), (rhs_t, cl, b_r)
        return jnp.sum(val.astype(dt)[None] * xg.astype(dt), axis=1,
                       keepdims=True)
    return reduce_rows


@functools.partial(
    jax.jit,
    static_argnames=("n_blocks", "chunk_l", "max_chunks", "rhs_t",
                     "interpret"),
)
def pjds_matmat_kernel_call(
    val: jax.Array,
    col_idx: jax.Array,
    chunk_map: jax.Array,
    x: jax.Array,
    *,
    n_blocks: int,
    chunk_l: int = 8,
    max_chunks: int | None = None,
    rhs_t: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Y = A_pjds @ X (permuted basis).

    val/col_idx: (total_jds, b_r), col_idx int16 or int32;
    chunk_map: (total_jds//chunk_l,) non-decreasing int32;
    x: (n_cols, n_rhs) with n_rhs % min(rhs_t, n_rhs) == 0 — the RHS
    tile shrinks to n_rhs for narrow blocks (k < rhs_t), so small
    multi-RHS counts run as a single tile instead of failing the
    alignment check.
    max_chunks: static chunk ceiling of any output group (None: total).
    Returns (n_blocks * b_r, n_rhs) in the accumulator dtype.
    """
    n_rhs = x.shape[1]
    dt = acc_dtype(val.dtype, x.dtype)
    if n_rhs == 0:                      # empty RHS block: nothing to do
        return jnp.zeros((n_blocks * val.shape[1], 0), dt)
    rhs_t = min(rhs_t, n_rhs)
    if n_rhs % rhs_t:
        raise ValueError("n_rhs not a multiple of rhs_t")
    # One gathered RHS tile is live at a time: the gather for k columns
    # would hold k stream copies in HBM at once.
    ys = [grouped_matvec_call(
              _rhs_row_sum(dt), (val, gather_rhs(col_idx, x[:, t:t + rhs_t])),
              chunk_map, n_blocks=n_blocks, chunk_l=chunk_l,
              max_chunks=max_chunks, dt=dt, interpret=interpret,
              name="pjds_spmm")
          for t in range(0, n_rhs, rhs_t)]
    return jnp.concatenate(ys).T
