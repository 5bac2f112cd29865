"""Pallas TPU kernel for pJDS (and SELL-C-sigma) sparse matrix-vector
multiplication.

This is the TPU adaptation of paper Listing 2, rebuilt around the memory
stream (DESIGN.md §2/§2b):

* ``val``/``col_idx`` are ``(total_jds, b_r)`` with rows on LANES
  (b_r = 128 by default) and jagged diagonals on SUBLANES — the paper's
  column-major ELLPACK layout restricted to each sorted row block.
  ``col_idx`` may be int16 (compressed index stream) or int32; ``val``
  may be bf16 (compressed value stream) or f32/f64 — accumulation is
  always at least f32.
* The RHS gather ``x[col_idx]`` runs in XLA ahead of the kernel
  (``_backend.gather_rhs``): the TPU compiler has no gather from a large
  VMEM array.  The kernel streams ``val`` and the gathered ``xg`` in
  ``(chunk_l, b_r)`` tiles and reduces each chunk over its sublanes.
  That XLA gather costs ~8.6 ns per slot on a v5e and bounds this
  kernel's apply; an operand whose rows are local is built as the
  windowed SELL-C-sigma instead, whose kernel (``wsell_spmv.py``)
  gathers from a VMEM window of x and leaves XLA only the non-zeros
  outside it.
* The grid is ``(group, chunk)`` over groups of ``OUT_BLOCKS`` row
  blocks (``_backend.grouped_matvec_call``): the group's
  ``(OUT_BLOCKS, b_r)`` output block stays pinned in VMEM and is written
  back to HBM once; scalar-prefetched extents drive the tile index maps.

SELL-C-sigma stores the same chunk layout with the row sort confined to
sigma windows, so its kernel is this one followed by the window-local
unpermute ``y[inv_perm]`` in XLA (``ops.sell_matvec``).

Padded entries follow the ``formats.PAD_COL`` sentinel contract: column
0 (in range — the gather reads x[0] without masking) and value 0 (the
product contributes nothing).
"""
from __future__ import annotations

import functools

import jax

from ._backend import acc_dtype, gather_rhs, grouped_matvec_call, row_sum

__all__ = ["pjds_matvec_kernel_call"]


@functools.partial(
    jax.jit, static_argnames=("n_blocks", "chunk_l", "max_chunks", "interpret"))
def pjds_matvec_kernel_call(
    val: jax.Array,
    col_idx: jax.Array,
    chunk_map: jax.Array,
    x: jax.Array,
    *,
    n_blocks: int,
    chunk_l: int = 8,
    max_chunks: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """y = A_pjds @ x (permuted basis).

    ``chunk_l`` must divide every pJDS block length (guaranteed when the
    format was built with ``diag_align`` a multiple of ``chunk_l``); the
    ``ops.to_device_pjds`` wrapper checks this.

    val/col_idx: (total_jds, b_r) with total_jds % chunk_l == 0; col_idx
                 int16 or int32.
    chunk_map:   (total_jds // chunk_l,) non-decreasing int32 row-block
                 id per chunk.
    x:           (n_cols,) RHS in the basis the column indices address.
    max_chunks:  static chunk ceiling of any output group
                 (``_backend.group_max_chunks``; ``PJDSDevice`` carries
                 it); None falls back to the total chunk count.
    interpret:   None = compiled on TPU, interpret elsewhere
                 (``ops.resolve_interpret``).
    Returns y:   (n_blocks * b_r,) in the accumulator dtype.
    """
    dt = acc_dtype(val.dtype, x.dtype)
    return grouped_matvec_call(
        row_sum(dt), (val, gather_rhs(col_idx, x)), chunk_map,
        n_blocks=n_blocks, chunk_l=chunk_l, max_chunks=max_chunks, dt=dt,
        interpret=interpret, name="pjds_spmv")
