"""Shared backend plumbing for the Pallas kernels.

Lives below ``kernels.ops`` (which imports the kernel modules) so the
kernels themselves can resolve defaults without a circular import;
``ops.resolve_backend`` / ``ops.resolve_interpret`` re-export these as
the public spellings.

Two rules of the TPU compiler shape every kernel here (DESIGN.md §2b):

* **No gather from a large VMEM array.**  Mosaic lowers a gather only
  between 2-D arrays of one shape (a shuffle inside a tile), so
  ``x[col_idx]`` over the whole of x cannot run inside a kernel.
  :func:`gather_rhs` runs it ahead of the kernel in XLA instead, and the
  kernels stream the gathered operand ``xg`` tile for tile beside
  ``val``.  That costs one write and one read of ``xg`` (the RHS width
  per stored slot) on top of the value and index streams, and XLA's
  scalar gather is slow per element.  The windowed SELL-C-sigma kernel
  is the exception: it fetches a short window of x per row block and
  gathers from it with in-tile shuffles (``window=`` below,
  ``wsell_spmv.py``).
* **Blocks are multiples of (8, 128) or whole arrays.**  One row block's
  output is a single ``(1, b_r)`` row, so the blocked kernels write
  groups of :data:`OUT_BLOCKS` row blocks: the grid walks every chunk of
  a group while the ``(OUT_BLOCKS, b_r)`` output block stays pinned in
  VMEM, and each chunk adds its row sum into its block's sublane
  (``slot``).

Both rules leave one gather that does run inside a kernel: from a
``(R, LANES)`` array into a tile of one shape, by ``R`` lane gathers and
selects (:func:`tile_gather`).  The windowed SELL-C-sigma kernel reads
x that way, and restores its output rows' order that way too
(``out_perm=`` below).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import WINDOW_UNIT

__all__ = ["resolve_interpret", "acc_dtype", "chunk_clamp", "gather_rhs",
           "OUT_BLOCKS", "LANES", "VMEM_LIMIT_BYTES", "compiler_params",
           "group_max_chunks", "grouped_matvec_call", "row_sum",
           "tile_gather"]

# Row blocks per kernel output block: the sublane height of one f32 tile.
OUT_BLOCKS = 8

# Lanes of one vector register: the width of a row of x's window.
LANES = 128

# Scoped VMEM the kernels may use.  A step holds a few (chunk_l, b_r)
# tiles and one output block, double-buffered: well under a MiB for
# every shape the tuner emits, so the limit only has to stay above
# v5e's 16 MiB default with room for the spMM tiles.
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def compiler_params() -> pltpu.CompilerParams:
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place the kernels' ``interpret`` default is decided:
    ``None`` (the default everywhere) means *compiled* Pallas on TPU and
    interpret mode elsewhere (CPU/GPU lack a Mosaic backend, interpret
    is the only way the kernels run there at all).  An explicit bool is
    the escape hatch — e.g. ``interpret=True`` on TPU to debug a kernel
    with host prints."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def acc_dtype(*dts):
    """Accumulator dtype rule shared by every kernel and ref: sub-f32
    value/RHS streams (bf16/f16 storage) accumulate — and return — in
    f32; f32/f64 stay put.  Low-precision STORAGE never means
    low-precision ARITHMETIC."""
    r = jnp.result_type(*dts)
    if r in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return r


def chunk_clamp(c, cnt):
    """Clamp a grid chunk index to a block's last REAL chunk — the shared
    piece of every prefetched BlockSpec index map: steps past the block's
    extent keep DMA'ing the same tile (no new transfer) while the kernel
    body's ``pl.when`` skips their compute.  The inner max guards blocks
    whose chunk count is 0 (all-empty ELLPACK-R tiles)."""
    return jnp.minimum(c, jnp.maximum(cnt - 1, 0))


def gather_rhs(col_idx: jax.Array, x: jax.Array) -> jax.Array:
    """``x[col_idx]`` in XLA, ahead of the kernel: the RHS value of every
    stored slot, shaped like ``col_idx``.  A multi-RHS block ``x`` of
    shape (n_cols, k) gathers into ``(k,) + col_idx.shape``, RHS columns
    leading, so each column keeps the matrix's tile layout.  Padding
    slots hold ``formats.PAD_COL`` (column 0), so they gather x[0] and
    meet a zero value in the kernel.  A single ``x`` is gathered from a
    copy padded to whole ``(8, 128)`` tiles, which XLA:TPU places in
    VMEM where it fits; gathered straight from x in HBM, a Graph500
    graph's scattered columns took 15.6 ns a slot on a TPU v5e against
    8.6, varying by ~10% from one apply to the next (PERF.md §6);
    ``tests/test_chip_compile.py`` fails if that copy leaves VMEM.  Runs
    under the device scope ``repro.gather_rhs``, the index cast and the
    pad included."""
    with jax.named_scope("repro.gather_rhs"):
        idx = col_idx.astype(jnp.int32)
        if x.ndim == 1:
            return jnp.pad(x, (0, -x.shape[0] % (OUT_BLOCKS * LANES)))[idx]
        return x.T[:, idx]


def tile_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table.reshape(-1)[idx]`` inside a kernel, for a ``(R, LANES)``
    ``table`` and an int32 tile ``idx`` of flat positions in it: for each
    row r of ``table``, one lane gather of row r broadcast to ``idx``'s
    shape, kept where ``idx`` lies in row r.  ``R`` in-tile shuffles and
    selects, the only gather Mosaic lowers (DESIGN.md §2)."""
    lane = idx & (LANES - 1)
    row = idx >> 7                         # LANES == 2 ** 7
    out = jnp.zeros(idx.shape, table.dtype)
    for r in range(table.shape[0]):
        tr = jnp.broadcast_to(table[r:r + 1, :], idx.shape)
        out = jnp.where(row == r, jnp.take_along_axis(tr, lane, axis=1), out)
    return out


def group_max_chunks(chunk_map) -> int:
    """Static chunk ceiling of any output group (``OUT_BLOCKS`` row blocks
    sharing one output block): the chunk-axis extent of the grouped
    kernel grid.  ``chunk_map`` is the host-side non-decreasing block id
    per chunk."""
    cm = np.asarray(chunk_map)
    if cm.size == 0:
        return 1
    return int(np.bincount(cm // OUT_BLOCKS).max())


def _group_extents(chunk_map: jax.Array, n_groups: int):
    """Per-group (first chunk, chunk count) plus each chunk's sublane slot
    in its group's output block — the scalar-prefetch operands.
    ``chunk_map`` must be non-decreasing (distributed operands pad with
    the LAST block id, which keeps it so)."""
    gmap = chunk_map // OUT_BLOCKS
    start = jnp.searchsorted(gmap, jnp.arange(n_groups, dtype=gmap.dtype),
                             side="left").astype(jnp.int32)
    cnt = jnp.diff(jnp.append(start, jnp.int32(chunk_map.shape[0])))
    slot = (chunk_map - gmap * OUT_BLOCKS).astype(jnp.int32)
    return start, cnt, slot


def grouped_matvec_call(reduce_rows, streams, chunk_map, *, n_blocks: int,
                        chunk_l: int, max_chunks: int | None, dt,
                        interpret: bool | None, name: str,
                        window=None, out_perm=None) -> jax.Array:
    """The grouped Pallas grid shared by the pJDS/SELL, CMRS and pJDS
    multi-RHS kernels.

    ``streams`` are ``(total, b_r)`` arrays tiled ``(chunk_l, b_r)`` and
    walked chunk by chunk (``val``, the gathered RHS ``xg``, and for
    CMRS the row routing); a multi-RHS gathered stream is
    ``(n_rhs, total, b_r)`` and rides whole along its leading axis.
    ``reduce_rows(*tiles)`` turns one chunk's tiles into its block's
    ``(1, b_r)`` partial row (``(n_rhs, 1, b_r)`` for multi-RHS).  Grid
    ``(group, chunk)``: chunks of one group are contiguous (the chunk map
    is non-decreasing), so the scalar-prefetched extents drive the
    stream index maps and steps past a group's extent clamp to its last
    tile and skip compute.  ``max_chunks`` is the static group ceiling
    (:func:`group_max_chunks`); None falls back to the total chunk count.
    Returns y: ``([n_rhs,] n_blocks * b_r)`` in storage row order, dtype
    ``dt``.  The ``pallas_call`` and its output slice run under the
    device scope ``repro.kernel``; the grid extents stay outside it,
    as the ELLPACK-R grid's preparation does.

    ``window``, for the windowed SELL-C-sigma kernel, is ``(wbase, xw,
    wrows)``: each row block's window start in ``formats.WINDOW_UNIT``s,
    x viewed as ``(rows, LANES)``, and the static window height in rows.
    ``wbase`` is scalar-prefetched beside the extents, and the chunk's
    block's ``(wrows, LANES)`` window of ``xw`` rides as one more input,
    fetched by element offset and passed to ``reduce_rows`` after the
    tiles.  Pallas skips the fetch while consecutive steps share a
    window.

    ``out_perm``, for a single RHS whose rows are sorted only inside
    windows that never cross an output group, is ``(inv_perm, addend)``:
    two ``(n_groups * OUT_BLOCKS * b_r,)`` arrays, the storage position
    of each original row (identity past the rows) and a dtype-``dt``
    start for y in storage order.  Each rides one ``(OUT_BLOCKS, b_r)``
    block per group, fetched once.  A group's output block starts from
    its ``addend`` block instead of zeros, and its last grid step
    (``c == max_chunks - 1``, which every group runs) rewrites the block
    in original row order by :func:`tile_gather`, so y leaves the kernel
    in original order.  Without it the call is the plain grouped grid.
    """
    total, b_r = streams[0].shape
    if total % chunk_l:
        raise ValueError(f"stream length {total} not a multiple of "
                         f"chunk_l={chunk_l}")
    lead = streams[-1].shape[:-2]          # (n_rhs,) or ()
    n_chunks = total // chunk_l
    n_groups = max(-(-n_blocks // OUT_BLOCKS), 1)
    start, cnt, slot = _group_extents(chunk_map, n_groups)

    n_pre = 3 if window is None else 4
    group_rows = OUT_BLOCKS * b_r

    def kernel(start_ref, cnt_ref, slot_ref, *refs):
        *in_refs, y_ref = refs[n_pre - 3:]
        if out_perm is not None:
            *in_refs, perm_ref, add_ref = in_refs
        g = pl.program_id(0)
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _init():
            if out_perm is None:
                y_ref[...] = jnp.zeros_like(y_ref)
            else:
                y_ref[...] = add_ref[...]

        @pl.when(c < cnt_ref[g])
        def _body():
            s = slot_ref[start_ref[g] + c]
            y_ref[..., pl.ds(s, 1), :] += reduce_rows(
                *(r[...] for r in in_refs))

        if out_perm is not None:
            @pl.when(c == pl.num_programs(1) - 1)
            def _unpermute():
                y_ref[...] = tile_gather(y_ref[...],
                                         perm_ref[...] - g * group_rows)

    def spec(a):
        pre = (0,) * (a.ndim - 2)
        return pl.BlockSpec(
            a.shape[:-2] + (chunk_l, b_r),
            lambda g, c, s, n, sl, *_: pre + (s[g] + chunk_clamp(c, n[g]),
                                              0))

    prefetch = [start, cnt, slot]
    in_specs = [spec(a) for a in streams]
    inputs = list(streams)
    if window is not None:
        wbase, xw, wrows = window

        def window_at(g, c, s, n, sl, wb):
            blk = g * OUT_BLOCKS + sl[s[g] + chunk_clamp(c, n[g])]
            # the product lets the compiler prove the start aligned
            return wb[blk] * (WINDOW_UNIT // LANES), 0

        prefetch.append(wbase)
        in_specs.append(pl.BlockSpec(
            (pl.Element(wrows), pl.Element(LANES)), window_at))
        inputs.append(xw)
    if out_perm is not None:
        if lead or b_r != LANES:
            raise ValueError(f"out_perm needs a single RHS and b_r == "
                             f"{LANES}")
        for a in out_perm:
            if a.shape != (n_groups * group_rows,):
                raise ValueError(f"out_perm arrays must hold "
                                 f"{n_groups * group_rows} rows; got "
                                 f"{a.shape}")
            in_specs.append(pl.BlockSpec((OUT_BLOCKS, b_r),
                                         lambda g, c, *_: (g, 0)))
            inputs.append(a.reshape(-1, b_r))
    pre = (0,) * len(lead)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(n_groups, n_chunks if max_chunks is None else max_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(lead + (OUT_BLOCKS, b_r),
                               lambda g, c, s, n, sl, *_: pre + (g, 0)),
    )
    with jax.named_scope("repro.kernel"):
        y = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                lead + (n_groups * OUT_BLOCKS, b_r), dt),
            compiler_params=compiler_params(),
            interpret=resolve_interpret(interpret),
            name=name,
        )(*prefetch, *inputs)
        return y.reshape(lead + (-1,))[..., : n_blocks * b_r]


def row_sum(dt):
    """``reduce_rows`` of the pJDS/SELL kernel: every slot of lane r
    belongs to row r of the block, so a chunk reduces over sublanes."""
    def reduce_rows(val, xg):
        return jnp.sum(val.astype(dt) * xg.astype(dt), axis=0, keepdims=True)
    return reduce_rows

