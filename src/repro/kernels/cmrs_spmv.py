"""Pallas TPU kernel for CMRS sparse matrix-vector multiplication.

CMRS (arXiv:1203.2946) on the TPU tiling (DESIGN.md §13): rows stay in
ORIGINAL order, grouped into strips of ``b_r`` consecutive rows, and
each strip's nonzeros are packed densely into ``(strip_su, b_r)``
lane-major tiles with an int8 ``row_in_strip`` stream routing every
slot back to its row.  Relative to pJDS this trades per-row padding for
an in-kernel segment reduction:

* The grid, the gathered RHS stream and the grouped output blocks are
  pJDS's exactly (``_backend.grouped_matvec_call``); only the chunk
  reduction differs.
* A pJDS chunk reduces over sublanes (every slot of lane r belongs to
  row r).  A CMRS chunk's slots belong to ARBITRARY rows of the strip,
  so each sublane of the chunk is multiplied by a one-hot ``(b_r, b_r)``
  routing matrix built from its ``row_in_strip`` row — a segment-sum
  phrased as MXU matmuls, costing ``2 * b_r`` flops per stored slot
  (``perf_model.cmrs_reduce_seconds``; dispatch prices the kernel as
  ``max(memory_term, compute_term)``).
* Padding slots carry val == 0 / col == PAD_COL / row_in_strip == 0:
  they gather x[0] and route a zero product into row 0 — harmless, no
  masking needed (the ``formats.PAD_COL`` contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._backend import acc_dtype, gather_rhs, grouped_matvec_call

__all__ = ["cmrs_matvec_kernel_call"]


def _routed_sum(dt):
    """``reduce_rows`` of the CMRS kernel: route each slot's product to
    lane ``row_in_strip`` of the output row.  The routing stream is
    transposed once so that sublane r's routes form a column, and each
    sublane contributes ``contrib[r] @ onehot_r`` with
    ``onehot_r[l, j] = (route[r, l] == j)``."""
    def reduce_rows(val, xg, ris):
        contrib = val.astype(dt) * xg.astype(dt)          # (chunk_l, b_r)
        chunk_l, b_r = contrib.shape
        routes = ris.astype(jnp.int32).T                  # (b_r, chunk_l)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (b_r, b_r), 1)
        part = jnp.zeros((1, b_r), dt)
        for r in range(chunk_l):
            onehot = (routes[:, r:r + 1] == lanes).astype(dt)
            part = part + jnp.dot(contrib[r:r + 1, :], onehot,
                                  preferred_element_type=dt,
                                  precision=jax.lax.Precision.HIGHEST)
        return part
    return reduce_rows


@functools.partial(
    jax.jit, static_argnames=("n_strips", "chunk_l", "max_chunks", "interpret"))
def cmrs_matvec_kernel_call(
    val: jax.Array,
    col_idx: jax.Array,
    row_in_strip: jax.Array,
    chunk_map: jax.Array,
    x: jax.Array,
    *,
    n_strips: int,
    chunk_l: int = 8,
    max_chunks: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """y = A_cmrs @ x in the ORIGINAL row order.

    val/col_idx/row_in_strip: (total_su, b_r) with total_su % chunk_l
                 == 0 (guaranteed when the format was built with
                 ``diag_align`` a multiple of ``chunk_l``; the
                 ``ops.to_device_cmrs`` wrapper checks).  col_idx int16
                 or int32, row_in_strip int8.
    chunk_map:   (total_su // chunk_l,) non-decreasing int32 strip id
                 per chunk.
    x:           (n_cols,) RHS, original column order.
    max_chunks:  static chunk ceiling of any output group of strips
                 (``CMRSDevice`` carries it); None falls back to the
                 total chunk count.
    interpret:   None = compiled on TPU, interpret elsewhere.
    Returns y:   (n_strips * b_r,) in the accumulator dtype.
    """
    dt = acc_dtype(val.dtype, x.dtype)
    return grouped_matvec_call(
        _routed_sum(dt), (val, gather_rhs(col_idx, x), row_in_strip),
        chunk_map, n_blocks=n_strips, chunk_l=chunk_l,
        max_chunks=max_chunks, dt=dt, interpret=interpret, name="cmrs_spmv")
