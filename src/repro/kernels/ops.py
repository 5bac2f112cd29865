"""jit'd public wrappers around the Pallas kernels + the unified
dispatch layer.

Two levels of API live here:

* **Per-format containers and matvecs** — ``to_device_pjds`` /
  ``to_device_ell`` / ``to_device_sell`` / ``to_device_wsell`` /
  ``to_device_csr`` move a
  host-side format (``repro.core.formats``) onto the device with the
  kernel-side metadata (chunk maps, tile chunk counts, window inverse
  permutations) precomputed; ``pjds_matvec`` / ``ell_matvec`` /
  ``sell_matvec`` / ``wsell_matvec`` / ``csr_matvec`` / ``pjds_matmat``
  dispatch to either
  the Pallas kernel (``backend='kernel'``, interpret-mode on CPU) or the
  pure-jnp oracle (``backend='ref'``, fast on CPU and used inside the
  distributed layer).

* **The unified entry point** — ``spmv(a, x, format="auto")`` wraps any
  matrix in a :class:`SparseDevice`: it inspects row-length statistics,
  prices each candidate format with ``core.perf_model``'s overhead
  estimates (``select_format``), converts once, caches the device
  representation, and computes y = A x in the ORIGINAL basis regardless
  of which format won.  Callers never touch permutations or padding.
  See DESIGN.md §5 for the selection heuristic.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import warnings
import weakref
from typing import Literal, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import formats as F
from repro.core import perf_model as PM
from . import ref as R
from ._backend import (LANES, OUT_BLOCKS, acc_dtype, group_max_chunks,
                       resolve_interpret)
from .pjds_spmv import pjds_matvec_kernel_call
from .pjds_spmm import pjds_matmat_kernel_call
from .ellr_spmv import ell_matvec_kernel_call
from .cmrs_spmv import cmrs_matvec_kernel_call
from .wsell_spmv import wsell_matvec_kernel_call

__all__ = [
    "PJDSDevice",
    "ELLDevice",
    "SELLDevice",
    "WSELLDevice",
    "CSRDevice",
    "CMRSDevice",
    "SparseDevice",
    "to_device_pjds",
    "to_device_ell",
    "ell_tile",
    "to_device_sell",
    "to_device_wsell",
    "to_device_csr",
    "to_device_cmrs",
    "pjds_matvec",
    "pjds_matmat",
    "ell_matvec",
    "sell_matvec",
    "wsell_matvec",
    "csr_matvec",
    "cmrs_matvec",
    "select_format",
    "as_device",
    "spmv",
    "clear_device_cache",
    "resolve_backend",
    "resolve_interpret",
]

Backend = Literal["auto", "kernel", "ref"]
FormatName = Literal["auto", "csr", "ellpack_r", "pjds", "sell", "wsell",
                     "cmrs"]
Tune = Literal["off", "auto", "force"]


def resolve_backend(backend: Backend) -> str:
    """The one place ``backend="auto"`` is decided: the Pallas kernels on
    TPU, the jnp refs everywhere else (on CPU the kernels only run in
    interpret mode — Python per grid step — so the refs are the fast
    path).  Explicit ``"kernel"``/``"ref"`` pass through untouched.

    The companion :func:`resolve_interpret` (re-exported from
    ``kernels._backend``) is the same decision one level down: with the
    kernel backend selected, ``interpret=None`` means compiled Pallas on
    TPU and interpret mode elsewhere — so ``backend="kernel"`` off-TPU
    still runs (slowly, for testing), never crashes."""
    if backend in ("kernel", "ref"):
        return backend
    if backend != "auto":
        raise ValueError(f"unknown backend {backend!r}")
    return "kernel" if jax.default_backend() == "tpu" else "ref"


_resolve_backend = resolve_backend   # the satellite-task spelling


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PJDSDevice:
    """Device-resident pJDS operand.  Registered as a pytree so it can be
    closed over / passed through jit and shard_map.

    ``val`` carries the (possibly bf16-compressed) value stream and
    ``col_idx`` the (possibly int16-compressed) index stream exactly as
    built by ``formats.csr_to_pjds(index_dtype=...)``; ``max_chunks`` is
    the static chunk ceiling of any kernel output group
    (``_backend.group_max_chunks``) that the prefetched grid needs (None
    falls back to the total chunk count — correct, more grid steps)."""

    val: jax.Array                     # (total_jds, b_r)
    col_idx: jax.Array                 # (total_jds, b_r) int16/int32
    chunk_map: jax.Array               # (total_jds // chunk_l,) int32
    row_block: jax.Array               # (total_jds,) int32 (for the ref)
    n_blocks: int = dataclasses.field(metadata=dict(static=True))
    b_r: int = dataclasses.field(metadata=dict(static=True))
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    max_chunks: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_rows_pad(self) -> int:
        return self.n_blocks * self.b_r


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLDevice:
    val: jax.Array                     # (max_nzr, n_pad)
    col_idx: jax.Array                 # (max_nzr, n_pad) int32
    rowlen: jax.Array                  # (n_pad,) int32
    tile_chunks: jax.Array             # (n_pad // tile_r,) int32
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    tile_r: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SELLDevice:
    """Device-resident SELL-C-sigma operand: pJDS chunk layout plus the
    window-local inverse permutation that takes y back to row order."""

    val: jax.Array                     # (total_jds, b_r)
    col_idx: jax.Array                 # (total_jds, b_r) int16/int32
    chunk_map: jax.Array               # (total_jds // chunk_l,) int32
    row_block: jax.Array               # (total_jds,) int32 (for the ref)
    inv_perm: jax.Array                # (n_blocks * b_r,) int32, window-local
    n_blocks: int = dataclasses.field(metadata=dict(static=True))
    b_r: int = dataclasses.field(metadata=dict(static=True))
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    sigma: int = dataclasses.field(metadata=dict(static=True))
    max_chunks: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_rows_pad(self) -> int:
        return self.n_blocks * self.b_r


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WSELLDevice:
    """Device-resident windowed SELL-C-sigma operand
    (``formats.WindowedSELLMatrix``): the SELL chunk layout with int16
    window-local column offsets, each row block's window start, and the
    out-of-window remainder.  ``val`` holds the slots' values in its
    first ``col_off.shape[0]`` rows and the remainder's values after
    them, row-major and zero-padded, so that one value leaf carries
    every stored value (``DeviceOperator.values``).  ``inv_perm`` runs
    on to whole kernel output groups (identity past ``n_rows_pad``), as
    the kernel's in-group unpermute reads it."""

    val: jax.Array                     # (total + tail, b_r)
    col_off: jax.Array                 # (total, b_r) int16, window-local
    chunk_map: jax.Array               # (total // chunk_l,) int32
    row_block: jax.Array               # (total,) int32 (for the ref)
    wbase: jax.Array                   # (n_blocks,) int32, WINDOW_UNITs
    inv_perm: jax.Array                # (n_groups * OUT_BLOCKS * b_r,) int32
    rem_row: jax.Array                 # (n_rem,) int32 storage row, sorted
    rem_col: jax.Array                 # (n_rem,) global column
    n_blocks: int = dataclasses.field(metadata=dict(static=True))
    b_r: int = dataclasses.field(metadata=dict(static=True))
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    sigma: int = dataclasses.field(metadata=dict(static=True))
    window: int = dataclasses.field(metadata=dict(static=True))
    x_len: int = dataclasses.field(metadata=dict(static=True))
    window_share: float = dataclasses.field(metadata=dict(static=True))
    max_chunks: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_rows_pad(self) -> int:
        return self.n_blocks * self.b_r

    @property
    def slot_val(self) -> jax.Array:
        """The slots' values, ``(total, b_r)``."""
        return self.val[: self.col_off.shape[0]]

    @property
    def rem_val(self) -> jax.Array:
        """The remainder's values, ``(n_rem,)``."""
        tail = self.val[self.col_off.shape[0]:].reshape(-1)
        return tail[: self.rem_row.shape[0]]

    @property
    def row_inv_perm(self) -> jax.Array:
        """``inv_perm`` over the padded rows alone, ``(n_rows_pad,)``."""
        return self.inv_perm[: self.n_rows_pad]

    @property
    def fuses_unpermute(self) -> bool:
        """Whether the kernel restores the row order itself: every
        sigma-window lies in one output group of ``OUT_BLOCKS * b_r``
        rows."""
        return (OUT_BLOCKS * self.b_r) % self.sigma == 0

    def columns(self) -> jax.Array:
        """The slots' global columns, rebuilt in XLA (not stored)."""
        return R.window_columns(self.col_off, self.wbase, self.row_block)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRDevice:
    """Device-resident CSR as flat nnz streams (gather + segment-sum ref;
    no Pallas kernel — the irregular baseline for tiny matrices)."""

    data: jax.Array                    # (nnz,)
    indices: jax.Array                 # (nnz,) int32
    row_ids: jax.Array                 # (nnz,) int32
    n_rows: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CMRSDevice:
    """Device-resident CMRS operand (``formats.CMRSMatrix``): strips of
    b_r consecutive ORIGINAL-order rows, nonzeros packed densely with an
    int8 row-in-strip routing stream.  ``chunk_map`` plays pJDS's role —
    strip id per (chunk_l, b_r) tile chunk for the scalar-prefetched
    kernel grid; ``strip_map`` is its per-sublane-row sibling for the
    segment-sum refs."""

    val: jax.Array                     # (total_su, b_r)
    col_idx: jax.Array                 # (total_su, b_r) int16/int32
    row_in_strip: jax.Array            # (total_su, b_r) int8
    chunk_map: jax.Array               # (total_su // chunk_l,) int32
    strip_map: jax.Array               # (total_su,) int32 (for the ref)
    n_strips: int = dataclasses.field(metadata=dict(static=True))
    b_r: int = dataclasses.field(metadata=dict(static=True))
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    max_chunks: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_rows_pad(self) -> int:
        return self.n_strips * self.b_r


def _blocked_maps(block_len: np.ndarray, chunk_l: int, n_blocks: int):
    row_block = np.repeat(np.arange(n_blocks, dtype=np.int32), block_len)
    return row_block, row_block[::chunk_l].copy()


def to_device_pjds(p: F.PJDSMatrix, chunk_l: int = 8,
                   dtype=None) -> PJDSDevice:
    if np.any(p.block_len % chunk_l):
        raise ValueError(
            f"chunk_l={chunk_l} must divide every block length; rebuild the "
            f"pJDS matrix with diag_align a multiple of chunk_l"
        )
    # block id per jagged-diagonal row, then per chunk
    row_block, chunk_map = _blocked_maps(p.block_len, chunk_l, p.n_blocks)
    val = p.val if dtype is None else p.val.astype(dtype)
    return PJDSDevice(
        val=jnp.asarray(val),
        col_idx=jnp.asarray(p.col_idx),
        chunk_map=jnp.asarray(chunk_map),
        row_block=jnp.asarray(row_block),
        n_blocks=p.n_blocks,
        b_r=p.b_r,
        chunk_l=chunk_l,
        max_chunks=group_max_chunks(chunk_map),
    )


# ELLPACK-R's row tile is a lane slice of one (max_nzr, n_pad) array, so
# the TPU compiler takes it only in whole 128-lane vregs; the blocked
# formats store each block as a whole (total, b_r) array and take any b_r.
ELL_LANES = 128


def ell_tile(b_r: int) -> int:
    """The ELLPACK-R row tile built for a requested ``b_r``: rounded up
    to whole :data:`ELL_LANES`."""
    return -(-b_r // ELL_LANES) * ELL_LANES


def to_device_ell(e: F.ELLMatrix, chunk_l: int = 8, tile_r: int = 128,
                  dtype=None) -> ELLDevice:
    if tile_r % ELL_LANES:
        raise ValueError(f"ELLPACK-R tile_r={tile_r} must be a multiple of "
                         f"{ELL_LANES} lanes; use ell_tile(b_r)")
    if e.val.shape[0] % chunk_l or e.n_rows_pad % tile_r:
        raise ValueError("ELL shapes not aligned to (chunk_l, tile_r); "
                         "rebuild with matching row_align/diag_align")
    tile_max = e.rowlen.reshape(-1, tile_r).max(axis=1)
    tile_chunks = ((tile_max + chunk_l - 1) // chunk_l).astype(np.int32)
    val = e.val if dtype is None else e.val.astype(dtype)
    return ELLDevice(
        val=jnp.asarray(val),
        col_idx=jnp.asarray(e.col_idx),
        rowlen=jnp.asarray(e.rowlen),
        tile_chunks=jnp.asarray(tile_chunks),
        chunk_l=chunk_l,
        tile_r=tile_r,
    )


def to_device_sell(s: F.SELLMatrix, chunk_l: int = 8,
                   dtype=None) -> SELLDevice:
    p = s.pjds
    if np.any(p.block_len % chunk_l):
        raise ValueError(
            f"chunk_l={chunk_l} must divide every chunk length; rebuild the "
            f"SELL matrix with diag_align a multiple of chunk_l"
        )
    row_block, chunk_map = _blocked_maps(p.block_len, chunk_l, p.n_blocks)
    val = p.val if dtype is None else p.val.astype(dtype)
    return SELLDevice(
        val=jnp.asarray(val),
        col_idx=jnp.asarray(p.col_idx),
        chunk_map=jnp.asarray(chunk_map),
        row_block=jnp.asarray(row_block),
        inv_perm=jnp.asarray(p.inv_perm),
        n_blocks=p.n_blocks,
        b_r=p.b_r,
        chunk_l=chunk_l,
        sigma=s.sigma,
        max_chunks=group_max_chunks(chunk_map),
    )


def to_device_wsell(w: F.WindowedSELLMatrix, chunk_l: int = 8,
                    dtype=None) -> WSELLDevice:
    if np.any(w.block_len % chunk_l):
        raise ValueError(
            f"chunk_l={chunk_l} must divide every chunk length; rebuild the "
            f"windowed SELL matrix with diag_align a multiple of chunk_l")
    row_block, chunk_map = _blocked_maps(w.block_len, chunk_l, w.n_blocks)
    # the remainder's values ride in whole chunks after the slots'
    n_rem = len(w.rem_val)
    tail = -(-n_rem // (chunk_l * w.b_r)) * chunk_l
    rem = np.zeros(tail * w.b_r, w.val.dtype)
    rem[:n_rem] = w.rem_val
    val = np.concatenate([w.val, rem.reshape(tail, w.b_r)])
    if dtype is not None:
        val = val.astype(dtype)
    group = OUT_BLOCKS * w.b_r
    n_out = max(-(-w.n_rows_pad // group), 1) * group
    inv_perm = np.concatenate([w.inv_perm, np.arange(
        w.n_rows_pad, n_out, dtype=w.inv_perm.dtype)])
    return WSELLDevice(
        val=jnp.asarray(val),
        col_off=jnp.asarray(w.col_off),
        chunk_map=jnp.asarray(chunk_map),
        row_block=jnp.asarray(row_block),
        wbase=jnp.asarray(w.wbase),
        inv_perm=jnp.asarray(inv_perm),
        rem_row=jnp.asarray(w.rem_row),
        rem_col=jnp.asarray(w.rem_col),
        n_blocks=w.n_blocks,
        b_r=w.b_r,
        chunk_l=chunk_l,
        sigma=w.sigma,
        window=w.window,
        x_len=w.x_len,
        window_share=w.window_share,
        max_chunks=group_max_chunks(chunk_map),
    )


def to_device_csr(m: F.CSRMatrix, dtype=None) -> CSRDevice:
    data = m.data if dtype is None else m.data.astype(dtype)
    row_ids = np.repeat(np.arange(m.n_rows, dtype=np.int32),
                        m.row_lengths())
    return CSRDevice(
        data=jnp.asarray(data),
        indices=jnp.asarray(m.indices),
        row_ids=jnp.asarray(row_ids),
        n_rows=m.n_rows,
    )


def to_device_cmrs(c: F.CMRSMatrix, chunk_l: int = 8,
                   dtype=None) -> CMRSDevice:
    if np.any(c.strip_len % chunk_l):
        raise ValueError(
            f"chunk_l={chunk_l} must divide every strip length; rebuild the "
            f"CMRS matrix with diag_align a multiple of chunk_l"
        )
    strip_map, chunk_map = _blocked_maps(c.strip_len, chunk_l, c.n_strips)
    val = c.val if dtype is None else c.val.astype(dtype)
    return CMRSDevice(
        val=jnp.asarray(val),
        col_idx=jnp.asarray(c.col_idx),
        row_in_strip=jnp.asarray(c.row_in_strip),
        chunk_map=jnp.asarray(chunk_map),
        strip_map=jnp.asarray(strip_map),
        n_strips=c.n_strips,
        b_r=c.b_r,
        chunk_l=chunk_l,
        max_chunks=group_max_chunks(chunk_map),
    )


def pjds_matvec(a: PJDSDevice, x: jax.Array,
                backend: Backend = "ref") -> jax.Array:
    """y = A x in the permuted basis; y has n_rows_pad entries."""
    if resolve_backend(backend) == "kernel":
        return pjds_matvec_kernel_call(
            a.val, a.col_idx, a.chunk_map, x,
            n_blocks=a.n_blocks, chunk_l=a.chunk_l, max_chunks=a.max_chunks,
        )
    return R.pjds_matvec_ref(a.val, a.col_idx, a.row_block, x, a.n_blocks)


def pjds_matmat(a: PJDSDevice, x: jax.Array, backend: Backend = "ref",
                rhs_t: int = 128) -> jax.Array:
    """Y = A X; X: (n_cols_pad, n_rhs)."""
    if resolve_backend(backend) == "kernel":
        return pjds_matmat_kernel_call(
            a.val, a.col_idx, a.chunk_map, x,
            n_blocks=a.n_blocks, chunk_l=a.chunk_l, max_chunks=a.max_chunks,
            rhs_t=rhs_t,
        )
    return R.pjds_matmat_ref(a.val, a.col_idx, a.row_block, x, a.n_blocks)


def ell_matvec(a: ELLDevice, x: jax.Array,
               backend: Backend = "ref") -> jax.Array:
    if resolve_backend(backend) == "kernel":
        return ell_matvec_kernel_call(
            a.val, a.col_idx, a.tile_chunks, x,
            chunk_l=a.chunk_l, tile_r=a.tile_r,
        )
    return R.ell_matvec_ref(a.val, a.col_idx, a.rowlen, x)


def sell_matvec(a: SELLDevice, x: jax.Array,
                backend: Backend = "ref") -> jax.Array:
    """y = A x with rows back in the ORIGINAL order; y has n_rows_pad
    entries.  The kernel path is the pJDS kernel over the SELL chunks
    (same storage layout) followed by the window-local unpermute."""
    if resolve_backend(backend) == "kernel":
        y = pjds_matvec_kernel_call(
            a.val, a.col_idx, a.chunk_map, x,
            n_blocks=a.n_blocks, chunk_l=a.chunk_l, max_chunks=a.max_chunks,
        )
        with jax.named_scope("repro.unpermute"):
            return y[a.inv_perm]
    return R.sell_matvec_ref(a.val, a.col_idx, a.row_block, a.inv_perm, x,
                             a.n_blocks)


def _window_x(x: jax.Array, x_len: int) -> jax.Array:
    """x cut or zero-padded to the windowed operand's ``x_len`` (entries
    past the matrix's columns meet only padding slots)."""
    n = x.shape[0]
    if n >= x_len:
        return x[:x_len]
    return jnp.pad(x, [(0, x_len - n)] + [(0, 0)] * (x.ndim - 1))


def _remainder(a: WSELLDevice, x: jax.Array, n_rows: int, dt) -> jax.Array:
    """The remainder's products in storage order, ``(n_rows[, k])``, under
    the device scope ``repro.gather_rhs``: the one XLA gather of x
    left."""
    with jax.named_scope("repro.gather_rhs"):
        return R.remainder_matvec_ref(a.rem_val, a.rem_row, a.rem_col, x,
                                      n_rows).astype(dt)


def _add_remainder(a: WSELLDevice, y: jax.Array, x: jax.Array) -> jax.Array:
    """y (storage order) plus the remainder's products."""
    if not a.rem_row.shape[0]:
        return y
    return y + _remainder(a, x, a.n_rows_pad, y.dtype)


def wsell_matvec(a: WSELLDevice, x: jax.Array,
                 backend: Backend = "ref") -> jax.Array:
    """y = A x with rows back in the ORIGINAL order; y has n_rows_pad
    entries.  The kernel path gathers the in-window slots inside the
    kernel (``wsell_spmv``).  Where every sigma-window lies in one
    output group (``fuses_unpermute``), each group starts from the
    remainder's rows and the kernel restores the row order itself;
    otherwise the remainder is added in storage order and SELL's
    window-local unpermute runs in XLA.  The ref path rebuilds the
    slots' global columns and runs the pJDS ref."""
    xp = _window_x(x, a.x_len)
    if resolve_backend(backend) == "kernel":
        xw = xp.astype(jnp.promote_types(xp.dtype, jnp.float32))
        out_perm = None
        if a.fuses_unpermute:
            dt = acc_dtype(a.val.dtype, xw.dtype)
            n_out = a.inv_perm.shape[0]
            addend = (_remainder(a, x, n_out, dt) if a.rem_row.shape[0]
                      else jnp.zeros(n_out, dt))
            out_perm = (a.inv_perm, addend)
        y = wsell_matvec_kernel_call(
            a.val, a.col_off, a.chunk_map, a.wbase,
            xw.reshape(-1, LANES), n_blocks=a.n_blocks,
            window=a.window, chunk_l=a.chunk_l, max_chunks=a.max_chunks,
            out_perm=out_perm)
        if out_perm is not None:
            return y
    else:
        y = R.pjds_matvec_ref(a.slot_val, a.columns(), a.row_block, xp,
                              a.n_blocks)
    y = _add_remainder(a, y, x)
    with jax.named_scope("repro.unpermute"):
        return y[a.row_inv_perm]


def wsell_matmat(a: WSELLDevice, x: jax.Array,
                 backend: Backend = "ref") -> jax.Array:
    """Y = A X, rows in the ORIGINAL order: the multi-RHS pJDS path on
    the slots' rebuilt global columns, plus the remainder."""
    pj = PJDSDevice(val=a.slot_val, col_idx=a.columns(),
                    chunk_map=a.chunk_map, row_block=a.row_block,
                    n_blocks=a.n_blocks, b_r=a.b_r, chunk_l=a.chunk_l,
                    max_chunks=a.max_chunks)
    y = _add_remainder(a, pjds_matmat(pj, _window_x(x, a.x_len), backend), x)
    with jax.named_scope("repro.unpermute"):
        return y[a.row_inv_perm]


def wsell_rmatmat(a: WSELLDevice, y_p: jax.Array, n_cols: int) -> jax.Array:
    """X = A^T Y with ``y_p`` in the storage row order: the blocked
    scatter-accumulate over the rebuilt global columns plus the
    remainder's."""
    x = R.blocked_rmatvec_ref(a.slot_val, a.columns(), a.row_block, y_p,
                              a.x_len)[:n_cols]
    if a.rem_row.shape[0]:
        x = x + R.remainder_rmatvec_ref(a.rem_val, a.rem_row, a.rem_col,
                                        y_p, n_cols).astype(x.dtype)
    return x


def csr_matvec(a: CSRDevice, x: jax.Array,
               backend: Backend = "ref") -> jax.Array:
    # No Pallas kernel for CSR — the ref path IS the implementation.
    del backend
    return R.csr_matvec_ref(a.data, a.indices, a.row_ids, x, a.n_rows)


def cmrs_matvec(a: CMRSDevice, x: jax.Array,
                backend: Backend = "ref") -> jax.Array:
    """y = A x in the ORIGINAL row order; y has n_rows_pad entries."""
    if resolve_backend(backend) == "kernel":
        return cmrs_matvec_kernel_call(
            a.val, a.col_idx, a.row_in_strip, a.chunk_map, x,
            n_strips=a.n_strips, chunk_l=a.chunk_l, max_chunks=a.max_chunks,
        )
    return R.cmrs_matvec_ref(a.val, a.col_idx, a.row_in_strip, a.strip_map,
                             x, a.n_strips)


# --------------------------------------------------------------------------
# Unified dispatch: SparseDevice + spmv(a, x, format="auto")
# --------------------------------------------------------------------------
_CSR_MIN_ROWS_FACTOR = 2       # below 2*b_r rows, block padding dominates
_CSR_IRREGULAR_FACTOR = 4.0    # scalar gather stream can't saturate HBM
_ELL_OVERHEAD_TOL = 0.05       # near-constant rows: skip sorting entirely


def windows_fit(b_r: int, sigma: Optional[int]) -> bool:
    """Whether a windowed SELL-C-sigma operand can be built at these
    statics: rows on the 128 lanes, and sigma-windows of whole blocks."""
    return b_r == LANES and (sigma is None or sigma % b_r == 0)


def wsell_seconds(m: F.CSRMatrix, plan: F.WindowPlan, *, b_r: int,
                  diag_align: int, sigma: Optional[int],
                  spec: PM.TPUSpec = PM.TPU_V5E, value_bytes: int = 4,
                  index_bytes: int = 4, vec_bytes: int = 4,
                  calibration="default") -> float:
    """Predicted time of one windowed SELL-C-sigma apply: the in-window
    slots' values and int16 offsets, one x window per row block, the
    window-local unpermute, and the remainder, whose gather of x and
    sorted scatter-add into y are two XLA indexed accesses per entry
    (``perf_model.gather_seconds``).  ``index_bytes`` is the width of
    the remainder's global columns."""
    if sigma is None:
        sigma = 8 * b_r
    elems = int(F.windowed_block_lengths(plan.rowlen, b_r, diag_align,
                                         sigma).sum()) * b_r
    return _wsell_price(m, m.nnz - int(plan.rowlen.sum()), elems,
                        plan.window, b_r=b_r, spec=spec,
                        value_bytes=value_bytes, index_bytes=index_bytes,
                        vec_bytes=vec_bytes, calibration=calibration)


def _wsell_price(m, n_rem: int, elems: int, window: int, *, b_r, spec,
                 value_bytes, index_bytes, vec_bytes,
                 calibration="default") -> float:
    """:func:`wsell_seconds` from the remainder's size, the stored slots
    and the window width; it grows with each of them."""
    n = m.n_rows
    rem_bytes = n_rem * (value_bytes + index_bytes + 4)
    return PM.predicted_spmv_seconds(
        elems, n, m.n_nzr,
        perm_bytes=PM.perm_traffic_bytes(n, vec_bytes) + rem_bytes,
        spec=spec, value_bytes=value_bytes, index_bytes=2,
        vec_bytes=vec_bytes, fmt="wsell", calibration=calibration,
        gathered=2 * n_rem,
        rhs_bytes=PM.window_rhs_bytes(-(-n // b_r), window, vec_bytes))


def _select(
    m: F.CSRMatrix,
    *,
    b_r: int = 128,
    diag_align: int = 8,
    sigma: Optional[int] = None,
    spec: PM.TPUSpec = PM.TPU_V5E,
    value_dtype=None,
    index_dtype="auto",
    plan: Optional[F.WindowPlan] = None,
) -> tuple:
    """:func:`select_format`, and the window plan it made (None where
    it made none, given none)."""
    n = m.n_rows
    if m.nnz == 0 or n < _CSR_MIN_ROWS_FACTOR * b_r:
        return "csr", plan
    rl = m.row_lengths()
    n_nzr = m.n_nzr
    if sigma is None:
        sigma = 8 * b_r
    vb = np.dtype(value_dtype).itemsize if value_dtype is not None \
        else m.data.dtype.itemsize
    vecb = max(4, m.data.dtype.itemsize)
    ib = F.resolve_index_dtype(index_dtype, m.shape[1]).itemsize

    ell_elems = F.estimate_storage_elements(rl, "ellpack_r", ell_tile(b_r),
                                            diag_align)
    if ell_elems / m.nnz - 1.0 <= _ELL_OVERHEAD_TOL:
        return "ellpack_r", plan   # rows (nearly) constant: no sort, no perm

    sell_elems = F.estimate_storage_elements(rl, "sell", b_r, diag_align,
                                             sigma)
    pjds_elems = F.estimate_storage_elements(rl, "pjds", b_r, diag_align)
    candidates = {
        "ellpack_r": PM.predicted_spmv_seconds(
            ell_elems, n, n_nzr, spec=spec, value_bytes=vb, index_bytes=ib,
            vec_bytes=vecb, fmt="ellpack_r", gathered=ell_elems),
        "sell": PM.predicted_spmv_seconds(
            sell_elems, n, n_nzr,
            perm_bytes=PM.perm_traffic_bytes(n, vecb),
            spec=spec, value_bytes=vb, index_bytes=ib, vec_bytes=vecb,
            fmt="sell", gathered=sell_elems),
        "pjds": PM.predicted_spmv_seconds(
            pjds_elems, n, n_nzr,
            perm_bytes=PM.perm_traffic_bytes(n, vecb),
            spec=spec, value_bytes=vb, index_bytes=ib, vec_bytes=vecb,
            fmt="pjds", gathered=pjds_elems),
    }
    cmrs_elems = F.estimate_storage_elements(rl, "cmrs", b_r, diag_align)
    candidates["cmrs"] = max(
        PM.predicted_spmv_seconds(
            cmrs_elems, n, n_nzr, spec=spec, value_bytes=vb,
            index_bytes=ib + PM.CMRS_RIS_BYTES, vec_bytes=vecb, fmt="cmrs",
            gathered=cmrs_elems),
        PM.cmrs_reduce_seconds(cmrs_elems, b_r, spec))
    if windows_fit(b_r, sigma):
        kw = dict(b_r=b_r, spec=spec, value_bytes=vb, index_bytes=ib,
                  vec_bytes=vecb)
        # The plan costs passes over every non-zero's run.  The most any
        # plan can serve prices the windowed SELL from below (fewest
        # slots, narrowest window); where that is no cheaper than the
        # best gathered format, ties going to those, no plan is made.
        # Either way the choice is the plan's.
        hopeless = (
            plan is None
            and _wsell_price(m, m.nnz - F.window_reach(m, sigma),
                             -(-n // b_r) * diag_align * b_r,
                             F.WINDOW_UNIT, **kw)
            >= min(candidates.values()))
        if not hopeless:
            if plan is None:
                plan = F.window_plan(m, sigma)
            candidates["wsell"] = wsell_seconds(
                m, plan, diag_align=diag_align, sigma=sigma, **kw)
    return min(candidates, key=candidates.get), plan


def select_format(
    m: F.CSRMatrix,
    *,
    b_r: int = 128,
    diag_align: int = 8,
    sigma: Optional[int] = None,
    spec: PM.TPUSpec = PM.TPU_V5E,
    value_dtype=None,
    index_dtype="auto",
    plan: Optional[F.WindowPlan] = None,
) -> str:
    """Pick a storage format from row-length statistics and, for the
    windowed SELL-C-sigma, from where the columns lie.

    Deterministic for a fixed matrix: prices each candidate's predicted
    spMVM time (``perf_model.predicted_spmv_seconds``) from its
    estimated padded storage (``formats.estimate_storage_elements``)
    plus the HBM cost of any out-of-kernel permutation, then takes the
    first minimum in the fixed order ellpack_r < sell < pjds < cmrs <
    wsell.  Every format but the windowed SELL gathers x in XLA, once
    per stored slot, which on the chip costs far more than the slot's
    bytes (``perf_model.gather_seconds``); the windowed SELL gathers
    inside the kernel and pays that cost only for the non-zeros outside
    their block's window (:func:`wsell_seconds`, ``plan`` from
    ``formats.window_plan``, computed here when not given, and not at all
    where ``formats.window_reach`` shows that no plan can win).  So it wins
    where rows are local, a banded matrix, and a matrix whose columns
    scatter keeps a gathered format.  It is a candidate only where
    :func:`windows_fit`.
    CSR wins only for degenerate inputs (empty, or too few rows to fill
    blocks).  CMRS is priced as ``max(memory, compute)``: its densely
    packed strips store ~nnz elements regardless of row-length skew —
    where ELLPACK/pJDS pad — but every slot costs ``2 * b_r`` MXU flops
    in the kernel's one-hot segment reduction
    (``perf_model.cmrs_reduce_seconds``), so it wins exactly when the
    padding bytes it saves outweigh that compute floor (power-law /
    hub-dominated patterns).
    The pricing sees the byte widths that will actually be STORED —
    ``value_dtype`` (bf16 storage halves the value stream) and
    ``index_dtype`` (int16 when the column span fits halves the index
    stream) — so compressed variants are priced correctly; RHS/LHS
    traffic stays priced at the uncompressed vector width (the vectors
    do not shrink with the matrix).  The full rationale is DESIGN.md §5.
    """
    return _select(m, b_r=b_r, diag_align=diag_align, sigma=sigma,
                   spec=spec, value_dtype=value_dtype,
                   index_dtype=index_dtype, plan=plan)[0]


def sandwich(pre_perm, pre_inv, apply, v):
    """``apply`` in a reordered basis: ``v`` gathered into it by
    ``pre_perm``, the result back out by ``pre_inv``, both under the
    device scope ``repro.unpermute``; ``apply(v)`` where there is no
    permutation.  B = P A P^T is symmetric-permuted, so A^T wears the
    same sandwich as A."""
    if pre_perm is None:
        return apply(v)
    with jax.named_scope("repro.unpermute"):
        v = v[pre_perm]
    y = apply(v)
    with jax.named_scope("repro.unpermute"):
        return y[pre_inv]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparseDevice:
    """A matrix ready for ``spmv``: one chosen format, converted once.

    Whatever the inner format, ``matvec`` consumes x and returns y in the
    ORIGINAL basis (length ``shape[0]``) — permutations, padding and
    basis changes are internal.  Device arrays are cached per host
    matrix by ``as_device``; hold on to the wrapper (or keep the host
    matrix alive) to amortise conversion across calls.

    Registered as a pytree (device arrays are the leaves) so it can flow
    through ``jit`` / ``shard_map`` / ``lax.while_loop`` carriers — the
    substrate the :mod:`repro.core.operator` protocol builds on.
    """

    fmt: str = dataclasses.field(metadata=dict(static=True))
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    dev: Union[PJDSDevice, ELLDevice, SELLDevice, WSELLDevice, CSRDevice,
               CMRSDevice]
    inv_perm: Optional[jax.Array]      # pjds only: undo the global row sort
    # Preprocessing (reorder=) permutation: the stored matrix is
    # B = P A P^T with perm[k] = old index at new position k
    # (core.reorder's convention), and every entry point sandwiches —
    # y = B_path(x[pre_perm])[pre_inv] — so callers always see the
    # ORIGINAL basis.  None (default) = no preprocessing, zero overhead.
    pre_perm: Optional[jax.Array] = None
    pre_inv: Optional[jax.Array] = None

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def value_dtype(self):
        """Dtype of the STORED value stream (bf16 for compressed builds);
        results still come back in the accumulator dtype (>= f32)."""
        return (self.dev.data if self.fmt == "csr" else self.dev.val).dtype

    @property
    def index_dtype(self):
        """Dtype of the stored column-index stream (int16 or int32; the
        window-local offsets of the windowed SELL)."""
        if self.fmt == "csr":
            return self.dev.indices.dtype
        if self.fmt == "wsell":
            return self.dev.col_off.dtype
        return self.dev.col_idx.dtype

    def matvec(self, x: jax.Array, backend: Backend = "auto") -> jax.Array:
        """y = A x, original basis, length shape[0]."""
        backend = resolve_backend(backend)
        if x.ndim == 2:
            return self.matmat(x, backend)
        self._check_cols(x)
        return sandwich(self.pre_perm, self.pre_inv,
                        lambda v: self._matvec_stored(v, backend), x)

    def _matvec_stored(self, x: jax.Array, backend: str) -> jax.Array:
        if self.fmt == "csr":
            return csr_matvec(self.dev, x, backend)
        if self.fmt == "ellpack_r":
            return ell_matvec(self.dev, x, backend)[: self.n_rows]
        if self.fmt == "sell":
            return sell_matvec(self.dev, x, backend)[: self.n_rows]
        if self.fmt == "wsell":
            return wsell_matvec(self.dev, x, backend)[: self.n_rows]
        if self.fmt == "pjds":
            y_p = pjds_matvec(self.dev, x, backend)
            with jax.named_scope("repro.unpermute"):
                return y_p[self.inv_perm][: self.n_rows]
        if self.fmt == "cmrs":
            return cmrs_matvec(self.dev, x, backend)[: self.n_rows]
        raise ValueError(f"unknown format {self.fmt!r}")

    def matmat(self, x: jax.Array, backend: Backend = "auto") -> jax.Array:
        """Y = A X for a block of RHS vectors, original basis.

        x: (n_cols, k) -> (shape[0], k).  The blocked formats ride the
        multi-RHS pJDS path (the storage layouts are identical, only the
        row unpermute differs) and honor ``backend``; CSR/ELLPACK have
        no multi-RHS Pallas kernel, so they always use the generalized
        refs — an explicit ``backend="kernel"`` falls back silently.
        """
        backend = resolve_backend(backend)
        self._check_cols(x)
        return sandwich(self.pre_perm, self.pre_inv,
                        lambda v: self._matmat_stored(v, backend), x)

    def _matmat_stored(self, x: jax.Array, backend: str) -> jax.Array:
        if self.fmt == "csr":
            return R.csr_matvec_ref(self.dev.data, self.dev.indices,
                                    self.dev.row_ids, x, self.dev.n_rows)
        if self.fmt == "ellpack_r":
            return R.ell_matvec_ref(self.dev.val, self.dev.col_idx,
                                    self.dev.rowlen, x)[: self.n_rows]
        if self.fmt in ("sell", "pjds"):
            d = self.dev
            a = d if self.fmt == "pjds" else PJDSDevice(
                val=d.val, col_idx=d.col_idx, chunk_map=d.chunk_map,
                row_block=d.row_block, n_blocks=d.n_blocks, b_r=d.b_r,
                chunk_l=d.chunk_l, max_chunks=d.max_chunks)
            y_p = pjds_matmat(a, x, backend)
            inv = d.inv_perm if self.fmt == "sell" else self.inv_perm
            with jax.named_scope("repro.unpermute"):
                return y_p[inv][: self.n_rows]
        if self.fmt == "wsell":
            return wsell_matmat(self.dev, x, backend)[: self.n_rows]
        if self.fmt == "cmrs":
            d = self.dev
            return R.cmrs_matvec_ref(d.val, d.col_idx, d.row_in_strip,
                                     d.strip_map, x,
                                     d.n_strips)[: self.n_rows]
        raise ValueError(f"unknown format {self.fmt!r}")

    def rmatvec(self, y: jax.Array, backend: Backend = "auto") -> jax.Array:
        """x = A^T y, original basis: (shape[0],) -> (shape[1],).

        The blocked formats run the transpose as a scatter-accumulate
        over their stored column indices (``ref.blocked_rmatvec_ref``);
        CSR swaps the roles of its gather and its segment ids.  For a
        kernel-speed transpose build the CSC-of-blocks device operand
        instead (``core.operator.operator(a, transpose="device")``).
        """
        # the transpose refs handle 1-D and 2-D y with one code path
        return self.rmatmat(y, backend)

    def rmatmat(self, y: jax.Array, backend: Backend = "auto") -> jax.Array:
        """X = A^T Y, original basis: (shape[0][, k]) -> (shape[1][, k])."""
        del backend    # scatter path only; see operator(transpose="device")
        self._check_rows(y)
        return sandwich(self.pre_perm, self.pre_inv,
                        self._rmatmat_stored, y)

    def _rmatmat_stored(self, y: jax.Array) -> jax.Array:
        n_cols = self.shape[1]
        if self.fmt == "csr":
            return R.csr_rmatvec_ref(self.dev.data, self.dev.indices,
                                     self.dev.row_ids, y, n_cols)
        if self.fmt == "ellpack_r":
            y_pad = self._pad_rows(y, self.dev.val.shape[1])
            return R.ell_rmatvec_ref(self.dev.val, self.dev.col_idx,
                                     self.dev.rowlen, y_pad, n_cols)
        if self.fmt in ("sell", "pjds"):
            d = self.dev
            inv = d.inv_perm if self.fmt == "sell" else self.inv_perm
            y_p = self._scatter_to_storage(y, inv)
            return R.blocked_rmatvec_ref(d.val, d.col_idx, d.row_block,
                                         y_p, n_cols)
        if self.fmt == "wsell":
            y_p = self._scatter_to_storage(y, self.dev.row_inv_perm)
            return wsell_rmatmat(self.dev, y_p, n_cols)
        if self.fmt == "cmrs":
            d = self.dev
            y_pad = self._pad_rows(y, d.n_rows_pad)
            return R.cmrs_rmatvec_ref(d.val, d.col_idx, d.row_in_strip,
                                      d.strip_map, y_pad, n_cols)
        raise ValueError(f"unknown format {self.fmt!r}")

    def _pad_rows(self, y: jax.Array, n_pad: int) -> jax.Array:
        pad = [(0, n_pad - self.n_rows)] + [(0, 0)] * (y.ndim - 1)
        return jnp.pad(y[: self.n_rows], pad)

    def _scatter_to_storage(self, y: jax.Array, inv_perm) -> jax.Array:
        """Inverse of the matvec epilogue ``y_p[inv_perm][:n_rows]``:
        place y's entries at their storage (permuted) positions, zeros in
        the padded rows (whose stored values are zero anyway)."""
        n_pad = inv_perm.shape[0]
        y_p = jnp.zeros((n_pad,) + y.shape[1:], y.dtype)
        return y_p.at[inv_perm[: self.n_rows]].set(y[: self.n_rows])

    def _check_cols(self, x: jax.Array) -> None:
        n = x.shape[0] if x.ndim == 2 else x.shape[-1]
        if n < self.shape[1]:
            # jax clamps out-of-range gathers, which would silently
            # return garbage instead of failing.
            raise ValueError(
                f"x has {n} entries; matrix has {self.shape[1]} columns")

    def _check_rows(self, y: jax.Array) -> None:
        if y.shape[0] < self.shape[0]:
            raise ValueError(
                f"y has {y.shape[0]} entries; matrix has {self.shape[0]} rows")

    @property
    def stored_slots(self) -> int:
        """Value slots the apply streams, padding included."""
        if self.fmt == "csr":
            return int(self.dev.data.size)
        return int(self.dev.val.size)

    @property
    def window_share(self) -> float:
        """Share of the matrix's non-zeros the apply serves from a window
        of x inside the kernel: 0 for every operand without windows."""
        return self.dev.window_share if self.fmt == "wsell" else 0.0

    @property
    def fused_unpermute(self) -> float:
        """Share of the rows whose unpermute the kernel path runs inside
        the kernel: 1 for a windowed SELL whose sigma-windows lie in
        single output groups, 0 for every other operand."""
        return float(self.fmt == "wsell" and self.dev.fuses_unpermute)

    @property
    def grid_fill(self) -> float:
        """Share of the kernel grid's steps that compute a chunk: the
        stored ``(chunk_l, b_r)`` chunks over the grouped grid's
        ``n_groups * max_chunks`` steps (ELLPACK-R: each tile's chunks
        over its ``n_groups * OUT_BLOCKS * n_chunks`` steps), the rest
        being steps past a group's extent that fetch nothing and skip
        their compute; 1.0 for CSR, which has no grid."""
        d = self.dev
        if self.fmt == "csr":
            return 1.0
        if self.fmt == "ellpack_r":
            n_tiles = d.tile_chunks.shape[0]
            steps = (-(-n_tiles // OUT_BLOCKS) * OUT_BLOCKS
                     * (d.val.shape[0] // d.chunk_l))
            return int(np.asarray(d.tile_chunks).sum()) / max(steps, 1)
        n_chunks = d.chunk_map.shape[0]
        n_blocks = d.n_strips if self.fmt == "cmrs" else d.n_blocks
        n_groups = max(-(-n_blocks // OUT_BLOCKS), 1)
        return n_chunks / (n_groups * (d.max_chunks or n_chunks) or 1)


# Conversion cache: host matrix -> device representation.  Keyed by the
# host object's id and the build parameters; a weakref callback evicts
# the entry when the host matrix is garbage-collected (id reuse safety),
# and the stored weakref is re-checked on hit.
_DEVICE_CACHE: dict = {}

# Dense ndarray inputs can't be id-cached (callers rebuild them freely),
# so they get a small content-addressed LRU: (shape, dtype, byte digest)
# -> the converted CSRMatrix.  Returning the SAME CSR object for equal
# content lets the id-keyed device cache above hit too, closing the hole
# where every dense call silently reconverted from scratch.
_DENSE_CSR_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_DENSE_CSR_CACHE_MAX = 16


def _dense_to_csr_cached(a: np.ndarray) -> F.CSRMatrix:
    key = (a.shape, a.dtype.str,
           hashlib.sha1(np.ascontiguousarray(a).tobytes()).hexdigest())
    hit = _DENSE_CSR_CACHE.get(key)
    if hit is not None:
        _DENSE_CSR_CACHE.move_to_end(key)
        return hit
    m = F.csr_from_dense(a)
    _DENSE_CSR_CACHE[key] = m
    while len(_DENSE_CSR_CACHE) > _DENSE_CSR_CACHE_MAX:
        _DENSE_CSR_CACHE.popitem(last=False)
    return m


def clear_device_cache() -> None:
    _DEVICE_CACHE.clear()
    _DENSE_CSR_CACHE.clear()


def _cache_put(key, m, dev) -> None:
    try:
        ref = weakref.ref(m, lambda _unused, k=key: _DEVICE_CACHE.pop(k, None))
    except TypeError:            # not weakref-able: skip caching
        return
    _DEVICE_CACHE[key] = (ref, dev)


def as_device(
    a: Union[F.CSRMatrix, np.ndarray, SparseDevice],
    format: FormatName = "auto",
    *,
    b_r: int = 128,
    diag_align: int = 8,
    sigma: Optional[int] = None,
    chunk_l: int = 16,
    dtype=None,
    index_dtype="auto",
    tune: Tune = "off",
    validate: str = "off",
    reorder: str = "off",
) -> SparseDevice:
    """Wrap a matrix as a :class:`SparseDevice`, converting at most once.

    ``a`` may be a host CSRMatrix, a dense ndarray (content-hashed into a
    small LRU, so repeated calls with equal data reuse one conversion),
    or an existing SparseDevice (returned unchanged; ``format`` must
    agree or be auto).

    Storage compression knobs:

    * ``dtype`` — the stored VALUE dtype (e.g. ``jnp.bfloat16`` halves
      the value stream; accumulation stays f32).
    * ``index_dtype`` — the stored column-index dtype; ``"auto"``
      (default) compresses to int16 whenever the column span fits
      (``formats.min_index_dtype``), falling back to int32.

    ``chunk_l`` defaults to 16 — the measured sweet spot of the
    grid-step-count vs padding trade now that the prefetched kernels
    stream (chunk_l, b_r) tiles per step (benchmarks/bench_kernels.py
    records the sweep); pass 8 to reproduce the old minimal-padding
    builds.

    ``tune`` switches from the static heuristic to the EMPIRICAL
    autotuner (``repro.tune``, DESIGN.md §9): ``"auto"`` looks the
    matrix's structural fingerprint up in the persistent tuning cache,
    measuring the pruned candidate set on a miss; ``"force"``
    re-measures and overwrites the cached decision.  The tuned statics
    (format, b_r, diag_align, chunk_l, sigma) then REPLACE the
    corresponding arguments — an explicit ``format`` (not ``"auto"``)
    restricts the search to that format, and the ``dtype`` /
    ``index_dtype`` storage policy is part of the cache key, never
    overridden.  A caller-supplied ``diag_align`` is ignored under
    tuning: the build must match the measured geometry exactly.

    ``reorder`` is the PREPROCESSING stage (``core.reorder.preprocess``,
    DESIGN.md §13): ``"rcm"`` applies the reverse Cuthill-McKee
    symmetric permutation before conversion (and before tuning — the
    reordered structure is what gets fingerprinted and measured);
    ``"auto"`` applies it only when the calibrated perf model predicts
    the bandwidth/storage gain beats the one-time permute cost plus the
    per-matvec permute/unpermute sandwich; ``"off"`` (default) skips it.
    The permutation is recorded on the returned ``SparseDevice``
    (``pre_perm``/``pre_inv``), so ``matvec``/``rmatvec`` transparently
    accept and return vectors in the ORIGINAL basis.  Non-square
    matrices and ``reorder="auto"`` quietly skip (RCM is a symmetric
    permutation); an explicit ``"rcm"`` on a non-square matrix raises.

    ``validate`` is the admission gate for host matrices
    (``formats.validate_csr``): ``"check"`` raises
    ``formats.CSRValidationError`` on out-of-range/unsorted indices,
    duplicates, non-finite values or corrupt ``indptr``; ``"repair"``
    rebuilds the matrix (dropping poisoned entries, merging duplicates)
    and converts the repaired copy; ``"off"`` (default) trusts the
    input.  Existing SparseDevice inputs skip validation (they were
    admitted when first converted).

    This is the conversion/caching layer under the operator protocol —
    new code should usually go one level up and call
    ``repro.core.operator.operator(a)``, which adds transpose,
    ``__matmul__`` and autodiff on top of the device representation
    built here (DESIGN.md §8).
    """
    if isinstance(a, SparseDevice):
        if format not in ("auto", a.fmt):
            raise ValueError(
                f"matrix already converted to {a.fmt!r}; asked for {format!r}")
        return a
    if isinstance(a, np.ndarray):
        a = _dense_to_csr_cached(a)
    if not isinstance(a, F.CSRMatrix):
        raise TypeError(f"cannot dispatch on {type(a)}")

    if validate not in ("off", "check", "repair"):
        raise ValueError(f"validate must be 'off', 'check' or 'repair'; "
                         f"got {validate!r}")
    if validate != "off":
        a, _report = F.validate_csr(a, repair=(validate == "repair"))

    if tune not in ("off", "auto", "force"):
        raise ValueError(f"tune must be 'off', 'auto' or 'force'; "
                         f"got {tune!r}")
    if reorder not in ("off", "auto", "rcm"):
        raise ValueError(f"reorder must be 'off', 'auto' or 'rcm'; "
                         f"got {reorder!r}")

    key = (id(a), format, b_r, diag_align, sigma, chunk_l,
           np.dtype(dtype).name if dtype is not None else None,
           "auto" if index_dtype == "auto" else np.dtype(index_dtype).name,
           reorder, tune)
    if tune != "force":      # force must re-measure, never serve a hit
        hit = _DEVICE_CACHE.get(key)
        if hit is not None and hit[0]() is a:
            return hit[1]

    # Preprocessing stage: runs BEFORE tuning so the reordered structure
    # is what gets fingerprinted, priced and measured.
    a_orig = a
    pre_perm = pre_inv = None
    if reorder != "off":
        from repro.core import reorder as RO   # deferred: light module
        pp = RO.preprocess(a, reorder=reorder,
                           value_bytes=(np.dtype(dtype).itemsize
                                        if dtype is not None
                                        else a.data.dtype.itemsize))
        if pp.applied:
            a = pp.matrix
            pre_perm = jnp.asarray(pp.perm.astype(np.int32))
            pre_inv = jnp.asarray(pp.inv_perm.astype(np.int32))

    if tune != "off":
        from repro import tune as T   # deferred: tune imports this module
        best = T.autotune(a, format=format, dtype=dtype,
                          index_dtype=index_dtype,
                          force=(tune == "force")).best
        # Rebuild with EXACTLY the geometry the tuner measured
        # (Candidate.build_kwargs, which owns diag_align) — a
        # caller-supplied diag_align would change padding out from
        # under the cached decision.
        sd = as_device(a, dtype=dtype, index_dtype=index_dtype,
                       tune="off", **best.build_kwargs())
        if pre_perm is not None:
            sd = dataclasses.replace(sd, pre_perm=pre_perm,
                                     pre_inv=pre_inv)
        if tune != "force":
            _cache_put(key, a_orig, sd)
        return sd

    # The kernels need diag_align % chunk_l == 0; raise it once here so
    # the selection pricing sees the same padding the builders produce.
    da = max(diag_align, chunk_l)

    with obs.span("repro.convert"):
        fmt, plan = format, None
        if fmt == "auto":
            fmt, plan = _select(a, b_r=b_r, diag_align=da, sigma=sigma,
                                value_dtype=dtype, index_dtype=index_dtype)
        elif fmt == "wsell" and windows_fit(b_r, sigma):
            plan = F.window_plan(a, 8 * b_r if sigma is None else sigma)
        if fmt == "csr":
            stored = a
        elif fmt == "ellpack_r":
            stored = F.csr_to_ell(a, row_align=ell_tile(b_r), diag_align=da,
                                  index_dtype=index_dtype)
        elif fmt == "sell":
            stored = F.csr_to_sell(a, c=b_r, sigma=sigma, diag_align=da,
                                   permuted_cols=False,
                                   index_dtype=index_dtype)
        elif fmt == "pjds":
            stored = F.csr_to_pjds(a, b_r=b_r, diag_align=da,
                                   permuted_cols=False,
                                   index_dtype=index_dtype)
        elif fmt == "wsell":
            if plan is None:
                raise ValueError(
                    f"the windowed SELL needs b_r={LANES} and sigma a "
                    f"multiple of it; got b_r={b_r}, sigma={sigma}")
            stored = F.csr_to_wsell(a, c=b_r, sigma=sigma, diag_align=da,
                                    index_dtype=index_dtype, plan=plan)
        elif fmt == "cmrs":
            stored = F.csr_to_cmrs(a, b_r=b_r, diag_align=da,
                                   index_dtype=index_dtype)
        else:
            raise ValueError(f"unknown format {fmt!r}")

    with obs.span("repro.transfer"):
        if fmt == "csr":
            dev = to_device_csr(stored, dtype=dtype)
        elif fmt == "ellpack_r":
            dev = to_device_ell(stored, chunk_l=chunk_l,
                                tile_r=ell_tile(b_r), dtype=dtype)
        else:
            to_device = {"sell": to_device_sell, "pjds": to_device_pjds,
                         "wsell": to_device_wsell,
                         "cmrs": to_device_cmrs}[fmt]
            dev = to_device(stored, chunk_l=chunk_l, dtype=dtype)
        inv_perm = jnp.asarray(stored.inv_perm) if fmt == "pjds" else None
        sd = obs.settled(SparseDevice(
            fmt=fmt, shape=a.shape, dev=dev, inv_perm=inv_perm,
            pre_perm=pre_perm, pre_inv=pre_inv))
    _cache_put(key, a_orig, sd)
    return sd


def spmv(
    a: Union[F.CSRMatrix, np.ndarray, SparseDevice],
    x: jax.Array,
    format: FormatName = "auto",
    backend: Backend = "auto",
    **convert_kwargs,
) -> jax.Array:
    """y = A x through the unified dispatch layer (original basis).

    .. deprecated::
        ``spmv`` is kept as a thin shim over the operator protocol:
        ``spmv(a, x)`` == ``operator(a) @ x`` (``repro.core.operator``).
        New code should build the operator once and reuse it — it adds
        ``.T``, ``rmatvec`` and ``jax.grad`` support that this function
        does not expose.

    ``format="auto"`` measures the matrix and picks CSR-ref / ELLPACK-R /
    pJDS / SELL-C-sigma (``select_format``); an explicit name forces the
    format.  ``backend="auto"`` resolves in :func:`resolve_backend`.  A
    2-D ``x`` of shape (n_cols, k) is dispatched to the multi-RHS spMM
    path, returning (n_rows, k).  The converted device representation is
    cached, so repeated ``spmv`` calls with the same host matrix convert
    once.  ``convert_kwargs`` (b_r, diag_align, sigma, chunk_l, dtype,
    index_dtype, tune) pass through to :func:`as_device` — in
    particular ``dtype=jnp.bfloat16`` stores a compressed value stream,
    ``index_dtype="auto"`` (the default) compresses indices to int16
    whenever the column span fits, and ``tune="auto"`` replaces the
    static format/statics heuristic with the measured autotuner
    (``repro.tune``; ``"force"`` re-measures, bypassing the persistent
    cache).
    """
    warnings.warn(
        "kernels.ops.spmv is deprecated: build the operator once — "
        "`operator(a) @ x` (repro.core.operator) — or call repro.solve "
        "for whole systems", DeprecationWarning, stacklevel=2)
    from repro.core.operator import operator as _operator
    op = _operator(a, format=format, backend=backend, **convert_kwargs)
    return op @ jnp.asarray(x)
