"""Pallas TPU kernel for ELLPACK-R sparse matrix-vector multiplication.

TPU adaptation of paper Listing 1 — the baseline the paper improves on.

Layout: ``val``/``col_idx`` are ``(max_nzr, n_pad)`` jagged-diagonal-major
(the paper's ``val[j*N + i]``), tiled as (chunk_l sublanes, tile_r lanes).
``col_idx`` may be an int16 compressed stream; ``val`` may be bf16 (f32
accumulation), same contract as the blocked kernels.  The RHS gather
runs in XLA ahead of the kernel (``_backend.gather_rhs``), which streams
the gathered ``xg`` beside ``val``.

ELLPACK-R semantics on TPU: the *storage* is padded to the global max row
length (that is ELLPACK's deficiency the paper fixes), but the *compute*
skips whole tiles whose rows are all shorter than the current jagged
diagonal — the scalar-prefetched ``tile_chunks`` array holds the
per-row-tile chunk count, the tile-granular analogue of the per-thread
``rowmax[]`` early exit.  Unlike a GPU warp, a TPU grid step is
all-or-nothing, so skipping happens at (chunk_l x tile_r) tile
granularity; without the pJDS sort, one long row in a tile forces the
whole tile through — exactly the "light boxes" hardware-reservation
waste of paper Fig. 2b, reproduced structurally.  Skipped steps also
clamp their val/xg index maps to the tile's last real chunk, so the
early exit saves the kernel's DMA traffic as well as the compute.

Grid ``(group, tile in group, chunk)``: ``OUT_BLOCKS`` row tiles share
one ``(OUT_BLOCKS, tile_r)`` output block (the (8, 128) block rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import (OUT_BLOCKS, acc_dtype, chunk_clamp, compiler_params,
                       gather_rhs, resolve_interpret)

__all__ = ["ell_matvec_kernel_call"]


def _ellr_spmv_kernel(tile_chunks_ref, val_ref, xg_ref, y_ref):
    g = pl.program_id(0)   # output group of row tiles
    s = pl.program_id(1)   # row tile within the group
    j = pl.program_id(2)   # jagged-diagonal chunk

    @pl.when((s == 0) & (j == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # ELLPACK-R early exit: skip chunks past this tile's longest row.
    @pl.when(j < tile_chunks_ref[g * OUT_BLOCKS + s])
    def _body():
        dt = y_ref.dtype
        contrib = val_ref[...].astype(dt) * xg_ref[...].astype(dt)
        y_ref[pl.ds(s, 1), :] += jnp.sum(contrib, axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("chunk_l", "tile_r", "interpret"),
)
def ell_matvec_kernel_call(
    val: jax.Array,
    col_idx: jax.Array,
    tile_chunks: jax.Array,
    x: jax.Array,
    *,
    chunk_l: int = 8,
    tile_r: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """y = A_ell @ x.

    val/col_idx: (max_nzr, n_pad), max_nzr % chunk_l == 0, n_pad % tile_r == 0;
    col_idx int16 or int32.
    tile_chunks: (n_pad // tile_r,) int32 — ceil(tile_row_max / chunk_l).
    interpret:   None = compiled on TPU, interpret elsewhere.
    """
    max_nzr, n_pad = val.shape
    if max_nzr % chunk_l or n_pad % tile_r:
        raise ValueError("shape not aligned to (chunk_l, tile_r)")
    n_chunks = max_nzr // chunk_l
    n_tiles = n_pad // tile_r
    n_groups = -(-n_tiles // OUT_BLOCKS)
    dt = acc_dtype(val.dtype, x.dtype)

    # The final group's tail tiles get chunk count 0: they re-read the
    # last real tile and skip compute.
    tile_chunks = jnp.pad(tile_chunks, (0, n_groups * OUT_BLOCKS - n_tiles))

    def mat_map(g, s, j, tc):
        # Skipped chunks clamp to the tile's last computed chunk (an
        # all-empty tile has tile_chunks == 0: chunk_clamp guards it).
        i = g * OUT_BLOCKS + s
        return chunk_clamp(j, tc[i]), jnp.minimum(i, n_tiles - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups, OUT_BLOCKS, n_chunks),
        in_specs=[
            pl.BlockSpec((chunk_l, tile_r), mat_map),                 # val
            pl.BlockSpec((chunk_l, tile_r), mat_map),                 # xg
        ],
        out_specs=pl.BlockSpec((OUT_BLOCKS, tile_r),
                               lambda g, s, j, tc: (g, 0)),
    )
    xg = gather_rhs(col_idx, x)
    with jax.named_scope("repro.kernel"):
        y = pl.pallas_call(
            _ellr_spmv_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_groups * OUT_BLOCKS, tile_r),
                                           dt),
            compiler_params=compiler_params(),
            interpret=resolve_interpret(interpret),
            name="ellr_spmv",
        )(tile_chunks, val, xg)
        return y.reshape(-1)[:n_pad]
