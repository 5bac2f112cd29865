"""Pallas TPU kernel for windowed SELL-C-sigma sparse matrix-vector
multiplication: the RHS gather inside the kernel.

The other blocked kernels stream an RHS gathered ahead of them in XLA
(``_backend.gather_rhs``), because Mosaic lowers a gather only between
2-D arrays of one shape (DESIGN.md §2).  XLA's scalar gather reads one
element per ~8.6 ns on a v5e, which bounds those kernels by far.  This
kernel gathers inside the tile instead, for operands whose rows are
local (``formats.WindowedSELLMatrix``):

* Every row block reads one window of x, ``wrows`` rows of 128 lanes
  starting at a multiple of ``formats.WINDOW_UNIT`` entries.  x is
  viewed as ``(rows, 128)``; the block's window start is
  scalar-prefetched and the ``(wrows, 128)`` window is fetched into
  VMEM by element offset (``_backend.grouped_matvec_call(window=)``).
* A stored slot holds its column as an int16 offset into the window:
  window row ``off >> 7``, lane ``off & 127``.  For each window row r,
  one lane gather of that row broadcast to the ``(chunk_l, b_r)`` tile
  picks the lanes, and a select keeps the slots whose row is r: ``wrows``
  in-tile shuffles per chunk, no gathered operand in HBM.
* Then the pJDS chunk reduction (``_backend.row_sum``) runs as before,
  on pJDS's grouped grid.
* Given ``out_perm``, each output group starts from the remainder's
  rows and ends by restoring its rows' original order with the same
  in-tile shuffles (``_backend.grouped_matvec_call(out_perm=)``): the
  rows are sorted only inside sigma-windows, and where sigma divides
  the group's ``OUT_BLOCKS * b_r`` rows no row leaves its group.

Non-zeros outside their block's window are not in the slots; the caller
adds them as an XLA remainder (``ops.wsell_matvec``).  Padding slots
hold offset 0 and value 0 (the ``formats.PAD_COL`` contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._backend import (LANES, acc_dtype, grouped_matvec_call, row_sum,
                       tile_gather)

__all__ = ["wsell_matvec_kernel_call"]


def _window_row_sum(dt):
    """``reduce_rows`` of the windowed kernel: gather each slot's x from
    the block's ``(wrows, LANES)`` window, then reduce over sublanes."""
    reduce = row_sum(dt)

    def reduce_rows(off, val, xw):
        return reduce(val, tile_gather(xw, off.astype(jnp.int32)))
    return reduce_rows


@functools.partial(
    jax.jit, static_argnames=("n_blocks", "chunk_l", "max_chunks", "window",
                              "interpret"))
def wsell_matvec_kernel_call(
    val: jax.Array,
    col_off: jax.Array,
    chunk_map: jax.Array,
    wbase: jax.Array,
    xw: jax.Array,
    *,
    n_blocks: int,
    window: int,
    chunk_l: int = 8,
    max_chunks: int | None = None,
    interpret: bool | None = None,
    out_perm: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """y = A_in x over the in-window slots, in storage row order, or
    given ``out_perm``, ``addend + A_in x`` in original row order.

    val:       (total[+ tail], b_r) values; rows past ``col_off``'s are
               not read (the operand keeps its remainder's values there).
    col_off:   (total, b_r) int16 window-local column offsets.
    chunk_map: (total // chunk_l,) non-decreasing int32 row block per
               chunk.
    wbase:     (n_blocks,) int32 window start per row block, in
               ``formats.WINDOW_UNIT``s.
    xw:        x viewed as ``(x_len // LANES, LANES)``, float32 or wider,
               long enough for every window.
    window:    static window width in entries, a multiple of
               ``formats.WINDOW_UNIT``.
    out_perm:  ``(inv_perm, addend)``, each ``(n_groups * OUT_BLOCKS *
               b_r,)``: every original row's storage position, sorted
               only inside its output group, and y's start in storage
               order, in the accumulator dtype.
    Returns y: (n_blocks * b_r,) in the accumulator dtype.
    """
    if col_off.shape[1] != LANES:
        raise ValueError(f"the windowed kernel needs b_r == {LANES}; got "
                         f"{col_off.shape[1]}")
    dt = acc_dtype(val.dtype, xw.dtype)
    wrows = window // LANES
    return grouped_matvec_call(
        _window_row_sum(dt), (col_off, val), chunk_map,
        n_blocks=n_blocks, chunk_l=chunk_l, max_chunks=max_chunks, dt=dt,
        interpret=interpret, name="wsell_spmv", window=(wbase, xw, wrows),
        out_perm=out_perm)
