"""Distributed-memory spMVM / spMM (paper §3) on a JAX device mesh.

Partitioning is over a 2-D device grid ``(gr, gc)`` with ``P = gr*gc``
devices in row-major order (``i = p // gc``, ``j = p % gc``):

* grid row ``i`` owns the contiguous row block ``I_i`` of ``gc * n_loc``
  matrix rows, split among its ``gc`` devices by COLUMN block — device
  ``(i, j)`` stores ``A[I_i, J_j]`` where ``J_j`` is the union of the
  x-slices owned by grid column ``j``;
* every device still owns exactly the ``n_loc`` rows of x and y that a
  1-D partition would give it (device p's y slice is segment ``j`` of
  ``I_i``), so vectors, solvers and the operator protocol are unchanged.

``grid=(P, 1)`` is EXACTLY the paper's 1-D row partition (the default);
``grid=(1, P)`` is pure column partitioning; square-ish grids shrink
both the halo surface and the per-device x working set as O(1/sqrt(P))
— the scaling geometry the paper's model says 1-D cannot deliver.

Two exchanges follow from the geometry:

* **x halo** along each grid COLUMN (ring of ``gr``): device ``(i, j)``
  needs remote x entries of devices ``(i', j)`` at signed ring distance
  ``d = i' - i``; exactly the 1-D halo machinery, reused verbatim
  (``halo_w`` / ``send_idx`` / ``recv_idx`` / ``halo_lens``).
* **y reduction** along each grid ROW (ring of ``gc``): device
  ``(i, j)`` computes PARTIAL sums for the other segments of ``I_i``
  and ships them to their owners, which scatter-add them into their own
  y slice.  The reduction is folded into the kernel epilogue: kernels
  return y in the SORTED row basis, and the partition records the
  sorted POSITIONS of every outgoing partial row (``red_send_pos``) and
  of the device's own segment (``seg_pos``), so no dense unpermute or
  extended y buffer ever materialises — see
  ``kernels.ref.partial_reduce_epilogue_ref``.

Both parts are stored in SELL-C-sigma-windowed blocked storage — going
one step beyond the paper, whose multi-GPU code still used ELLPACK-R and
left "an implementation of the pJDS format in the multi-GPU code" as
future work (paper §3, Conclusions).  The row sort is windowed INSIDE
each device block (sigma rows per window, default 8*b_r), so no
permutation crosses the network and the halo/RHS access pattern keeps
the locality of the original row ordering up to sigma (DESIGN.md §3/§6).

Halo exchange (paper §3: "local gather + point-to-point") has two
implementations, selected by ``halo=``:

* ``"gathered"`` (default) — the paper-faithful compressed exchange: at
  partition time each device records, per ring neighbor, WHICH of its
  columns that neighbor actually references (``send_idx``), padded to a
  static per-neighbor maximum.  At run time each device gathers exactly
  those entries, ``ppermute``s the compact buffers, and scatters the
  received values into a dense ext buffer (``recv_idx``; padding lanes
  carry an out-of-range sentinel and are dropped).  The y reduction is
  compressed the same way (``red_send_pos`` / ``red_recv_idx``).
* ``"full"`` — the bulk baseline: ring-shift whole x slices
  ``2*halo_w`` times and whole partial y segments ``2*red_w`` times.

A purely block-diagonal matrix measures ``halo_w == 0`` and skips the
exchange (and the remote kernel) entirely.

Four communication modes (paper §3.1), distinguished by their data
dependences — inspect the compiled HLO to see the schedules differ:

* ``vector``   — bulk-synchronous: halo exchange completes (barrier),
  then one combined spMVM pass.
* ``naive``    — split kernels, but the halo exchange is *ordered after*
  the local kernel (an ``optimization_barrier`` models MPI libraries
  without asynchronous progress).  The paper predicts no benefit over
  vector mode; the serialized schedule reproduces that.
* ``overlap``  — task mode: the halo ppermutes depend only on x, the
  local kernel depends only on x -> XLA's async collectives MAY overlap
  the halo with the local spMVM ("hope XLA overlaps it").
* ``pipeline`` — double-buffered gathered exchange with an EXPLICIT
  dependency structure: the remote operand is split per ring distance
  into stage operands at partition time; stage s's spMV consumes only
  its own compact buffer, and an ``optimization_barrier`` ties stage
  s+1's received buffer into stage s's input so the next exchange is
  materialised no later than the start of the current remote compute
  (one buffer ahead; deeper prefetch is left to the async scheduler).
  This is the paper's "explicit overlap" result as a dataflow graph
  instead of a dedicated MPI thread.

Multi-RHS: ``dist_matmat`` applies the same partition to a block of
``k`` right-hand sides (x of shape ``(n_global_pad, k)``), riding the
``pjds_matmat`` kernel; the gathered halo/reduction buffers simply
carry ``k`` columns per entry, so the matrix stream AND the per-entry
exchange set-up cost are amortised over ``k`` vectors (SELL-C-sigma
follow-up, arXiv:1307.6209 §"multi-vector").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import formats as F
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels._backend import group_max_chunks

Mode = Literal["vector", "naive", "overlap", "pipeline"]
Halo = Literal["gathered", "full"]

__all__ = ["DistPJDS", "partition_csr", "dist_matvec", "dist_matmat",
           "padded_global_size", "halo_distances", "grid_shapes"]


def halo_distances(w: int) -> list[int]:
    """Signed ring distances of a width-w exchange, in slot order."""
    return [d for d in range(-w, w + 1) if d != 0]


def grid_shapes(n_dev: int) -> list[tuple[int, int]]:
    """All (gr, gc) factorizations of n_dev, 1-D row partition first."""
    out = [(n_dev // gc, gc) for gc in range(1, n_dev + 1)
           if n_dev % gc == 0]
    return out


def _col_ring_pairs(n_dev: int, gc: int, d: int) -> list[tuple[int, int]]:
    """src->dst ppermute pairs shifting by +d within each grid COLUMN
    (the x-halo ring).  gc == 1 recovers the 1-D device ring."""
    gr = n_dev // gc
    return [(q, ((q // gc + d) % gr) * gc + q % gc) for q in range(n_dev)]


def _row_ring_pairs(n_dev: int, gc: int, t: int) -> list[tuple[int, int]]:
    """src->dst ppermute pairs shifting by +t within each grid ROW
    (the partial-sum reduction ring)."""
    return [(q, (q // gc) * gc + (q % gc + t) % gc) for q in range(n_dev)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistPJDS:
    """Stacked per-device local/remote pJDS operands (leading axis = device)."""

    loc_val: jax.Array        # (P, loc_jds, b_r)
    loc_col: jax.Array
    loc_chunk_map: jax.Array  # (P, loc_jds // chunk_l)
    loc_row_block: jax.Array  # (P, loc_jds)
    rem_val: jax.Array        # (P, rem_jds, b_r)
    rem_col: jax.Array        # columns in EXT (halo buffer) coordinates
    rem_chunk_map: jax.Array
    rem_row_block: jax.Array
    inv_perm: jax.Array       # (P, blk_rows) sorted position of each block row
    send_idx: jax.Array       # (P, 2*halo_w, max_h) int32: local columns this
                              # device gathers for each outgoing ppermute
    recv_idx: jax.Array       # (P, 2*halo_w, max_h) int32: ext-buffer slots
                              # the received compact buffer scatters into
                              # (padding = ext_len sentinel, dropped)
    n_dev: int = dataclasses.field(metadata=dict(static=True))
    n_loc: int = dataclasses.field(metadata=dict(static=True))
    n_blocks: int = dataclasses.field(metadata=dict(static=True))
                              # kernel row blocks = blk_rows // b_r
                              # (blk_rows == gc * n_loc; n_loc // b_r in 1-D)
    b_r: int = dataclasses.field(metadata=dict(static=True))
    chunk_l: int = dataclasses.field(metadata=dict(static=True))
    halo_w: int = dataclasses.field(metadata=dict(static=True))
    halo_lens: tuple = dataclasses.field(metadata=dict(static=True))
                              # per-distance gathered halo sizes (elements),
                              # ordered as halo_distances(halo_w)
    n_rows: int = dataclasses.field(metadata=dict(static=True))  # unpadded
    sigma: int = dataclasses.field(metadata=dict(static=True))   # sort window
    loc_max_chunks: int = dataclasses.field(
        default=None, metadata=dict(static=True))  # prefetched-grid ceilings
    rem_max_chunks: int = dataclasses.field(
        default=None, metadata=dict(static=True))
    rem_chunk_l: int = dataclasses.field(
        default=None, metadata=dict(static=True))
        # tile height of the REMOTE operand when tuned independently of
        # the local one (None -> shares chunk_l); see repro.tune
    # ---- 2-D grid fields (all carry degenerate shapes in 1-D) ----------
    seg_pos: jax.Array = None
        # (P, gc, n_loc) int32: sorted positions of segment (j+s)%gc of
        # this device's row block; row 0 is the device's OWN y slice
        # (== the 1-D inv_perm when gc == 1)
    red_send_pos: jax.Array = None
        # (P, n_red, max_r) int32: positions in SORTED y of the partial
        # rows shipped for reduction distance red_dists[kk] (pad = 0,
        # dropped by the receiver)
    red_recv_idx: jax.Array = None
        # (P, n_red, max_r) int32: own-slice rows the received partials
        # scatter-ADD into (pad = n_loc sentinel, dropped)
    stage_val: jax.Array = None      # (P, S, stage_jds, b_r) per-distance
    stage_col: jax.Array = None      #   remote operands for mode="pipeline"
    stage_chunk_map: jax.Array = None  # (P, S, stage_jds // rem_chunk_l)
    stage_row_block: jax.Array = None  # (P, S, stage_jds)
    grid: tuple = dataclasses.field(
        default=None, metadata=dict(static=True))   # (gr, gc); None = (P, 1)
    red_w: int = dataclasses.field(
        default=0, metadata=dict(static=True))      # reduction ring width
    red_lens: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
        # per-distance gathered reduction sizes, ordered as
        # halo_distances(red_w)
    stage_dists: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
        # the signed ring distance of each pipeline stage operand
    stage_max_chunks: int = dataclasses.field(
        default=1, metadata=dict(static=True))

    @property
    def rem_chunk_l_eff(self) -> int:
        return self.chunk_l if self.rem_chunk_l is None else self.rem_chunk_l

    @property
    def grid_eff(self) -> tuple:
        return (self.n_dev, 1) if self.grid is None else self.grid

    @property
    def blk_rows(self) -> int:
        """Matrix rows of one device block (gc * n_loc)."""
        return self.n_blocks * self.b_r

    @property
    def n_global_pad(self) -> int:
        return self.n_dev * self.n_loc

    @property
    def ext_len(self) -> int:
        return (2 * self.halo_w + 1) * self.n_loc

    def comm_bytes_per_device(self, value_bytes: int = 8, k: int = 1,
                              halo: Halo = "gathered") -> int:
        """Exchange traffic per device per spMVM (send == recv volume),
        x halo plus partial-sum reduction.

        ``"gathered"`` reports the MEASURED per-neighbor set sizes the
        compressed exchange actually ships; ``"full"`` the full-slice /
        full-segment ring shifts of the bulk baseline.  ``k`` scales for
        multi-RHS (``dist_matmat``)."""
        if halo == "full":
            n_red = sum(1 for h in self.red_lens if h)
            return (2 * self.halo_w + n_red) * self.n_loc * value_bytes * k
        if halo != "gathered":
            raise ValueError(halo)
        return (sum(self.halo_lens) + sum(self.red_lens)) * value_bytes * k

    def comm_msgs_per_device(self, halo: Halo = "gathered") -> int:
        """Point-to-point messages per device per spMVM — the quantity
        the calibrated per-message fixed cost multiplies
        (``perf_model.t_link``)."""
        if halo == "full":
            return 2 * self.halo_w + sum(1 for h in self.red_lens if h)
        if halo != "gathered":
            raise ValueError(halo)
        return (sum(1 for h in self.halo_lens if h) +
                sum(1 for h in self.red_lens if h))


def padded_global_size(n_rows: int, n_dev: int, b_r: int = 128) -> int:
    per = b_r * n_dev
    return ((n_rows + per - 1) // per) * per


def _csr_row_slice(m: F.CSRMatrix, lo: int, hi: int, n_loc: int) -> F.CSRMatrix:
    """Rows [lo, hi) of m as a standalone CSR of n_loc rows (zero-padded)."""
    hi = min(hi, m.n_rows)
    counts = np.zeros(n_loc, dtype=np.int64)
    if hi > lo:
        counts[: hi - lo] = np.diff(m.indptr[lo : hi + 1])
    indptr = np.zeros(n_loc + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    s, e = (m.indptr[lo], m.indptr[hi]) if hi > lo else (0, 0)
    return F.CSRMatrix(indptr, m.indices[s:e].copy(), m.data[s:e].copy(),
                       (n_loc, m.shape[1]))


def _split_loc_rem(local: F.CSRMatrix, p: int, n_loc: int, n_dev: int,
                   halo_w: int):
    """1-D helper (used by ``repro.tune``): split a device's row slice
    into local-column and remote-column CSRs, remapping columns to
    slice-local / halo-buffer coordinates."""
    own_lo, own_hi = p * n_loc, (p + 1) * n_loc
    rl = np.diff(local.indptr)
    rows = np.repeat(np.arange(local.n_rows), rl)
    cols = local.indices.astype(np.int64)
    vals = local.data
    is_loc = (cols >= own_lo) & (cols < own_hi)

    loc = F.csr_from_coo(rows[is_loc], cols[is_loc] - own_lo, vals[is_loc],
                         (n_loc, n_loc), sum_duplicates=False)
    rcols = cols[~is_loc]
    owner = rcols // n_loc
    d = (owner - p + n_dev) % n_dev          # ring distance
    d = np.where(d > n_dev // 2, d - n_dev, d)
    ext = (d + halo_w) * n_loc + (rcols % n_loc)
    rem = F.csr_from_coo(rows[~is_loc], ext, vals[~is_loc],
                         (n_loc, (2 * halo_w + 1) * n_loc),
                         sum_duplicates=False)
    return loc, rem


def _pad_lead(a: np.ndarray, longest: int, edge: bool) -> np.ndarray:
    """Pad axis 0 to ``longest``.  Values/columns pad with ZERO (the
    padding sentinel: phantom chunks contribute nothing); chunk/row
    block maps pad with their LAST entry so they stay non-decreasing.
    A degenerate device whose map is EMPTY (it owns no stored entries)
    pads with zeros instead — every phantom chunk then targets block 0
    with all-zero values, a collective-compatible empty program."""
    if a.shape[0] == longest:
        return a
    if edge and a.shape[0] == 0:
        return np.zeros((longest,) + a.shape[1:], a.dtype)
    pad = [(0, longest - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad, mode="edge" if edge else "constant")


def partition_csr(
    m: F.CSRMatrix,
    n_dev: int,
    b_r: int = 128,
    diag_align: int = 8,
    chunk_l: int = 8,
    halo_w: int | None = None,
    sigma: int | None = None,
    index_dtype="auto",
    rem_chunk_l: int | None = None,
    grid: tuple | None = None,
    build_stages: bool = True,
) -> DistPJDS:
    """Partition a global CSR onto an ``n_dev``-device grid as
    :class:`DistPJDS`.

    ``grid=(gr, gc)`` selects the 2-D block layout (``gr * gc == n_dev``,
    row-major device order); ``None`` is the 1-D row partition
    ``(n_dev, 1)``.  Device ``(i, j)`` stores ``A[I_i, J_j]`` — the x
    halo runs along grid columns (ring of ``gr``), the partial-sum y
    reduction along grid rows (ring of ``gc``); both are measured from
    the matrix and recorded as compressed gather/scatter index sets.

    ``halo_w`` is measured when not given; a matrix whose halo window
    reaches the ring radius effectively all-gathers — the pattern the
    paper's model flags as not multi-accelerator-friendly.  A purely
    block-diagonal matrix measures ``halo_w == 0`` (no exchange at all).

    ``sigma`` bounds the per-device row-sort window (SELL-C-sigma style;
    default 8*b_r, clamped to the device block height).

    ``index_dtype="auto"`` compresses the stored column-index streams:
    the local operand addresses only its n_loc-column slice and the
    remote operand only the (2*halo_w+1)*n_loc ext buffer, so the
    partition STRUCTURALLY bounds the index span — int16 indices
    whenever the per-device slice fits, however large the global matrix
    is.  2-D grids tighten the bound further (both spans shrink with
    the grid), which is where the paper's distributed scaling and the
    compressed-stream work compound.

    ``rem_chunk_l`` gives the REMOTE (halo-coupling) operand its own
    tile height; ``None`` shares ``chunk_l``.  ``repro.tune`` measures
    the two independently.

    ``build_stages`` additionally splits the remote operand per ring
    distance into the stage operands ``mode="pipeline"`` consumes
    (costs roughly a second copy of the remote operand; set False to
    drop it when the pipeline mode is never used).
    """
    if m.shape[0] != m.shape[1]:
        raise ValueError("distributed spMVM expects a square matrix")
    if grid is None:
        gr, gc = n_dev, 1
    else:
        gr, gc = (int(grid[0]), int(grid[1]))
        if gr < 1 or gc < 1 or gr * gc != n_dev:
            raise ValueError(f"grid {grid!r} incompatible with n_dev={n_dev}")
    n_pad = padded_global_size(m.n_rows, n_dev, b_r)
    n_loc = n_pad // n_dev
    blk_rows = gc * n_loc

    # COO view of each device block A[I_i, J_j], annotated with the
    # signed grid-column ring distance of every entry's x owner.
    row_slices = [_csr_row_slice(m, i * blk_rows, (i + 1) * blk_rows,
                                 blk_rows) for i in range(gr)]
    dev_rows, dev_cols, dev_vals, dev_d = [], [], [], []
    needs = []
    for p in range(n_dev):
        i, j = divmod(p, gc)
        sl = row_slices[i]
        rl = np.diff(sl.indptr)
        rows = np.repeat(np.arange(blk_rows), rl)
        cols = sl.indices.astype(np.int64)
        vals = sl.data
        owner = cols // n_loc                 # device owning x[col]
        keep = owner % gc == j                # this device's column block
        rows, cols, vals, owner = (rows[keep], cols[keep], vals[keep],
                                   owner[keep])
        d = (owner // gc - i) % gr            # grid-column ring distance
        if gr > 1:
            d = np.where(d > gr // 2, d - gr, d)
        dev_rows.append(rows)
        dev_cols.append(cols)
        dev_vals.append(vals)
        dev_d.append(d)
        nd = {}
        for dd in np.unique(d):
            if dd == 0:
                continue
            nd[int(dd)] = np.unique(cols[d == dd] % n_loc)
        needs.append(nd)

    measured = max((max((abs(d) for d in nd), default=0) for nd in needs),
                   default=0)
    if halo_w is None:
        halo_w = measured
    else:
        halo_w = int(halo_w)
        if halo_w < measured:
            raise ValueError(
                f"halo_w={halo_w} too small: matrix couples devices at ring "
                f"distance {measured}")
    if halo_w > gr // 2 and gr > 1:
        halo_w = gr // 2
    if gr == 1:
        halo_w = 0

    dists = halo_distances(halo_w)
    halo_lens = tuple(
        max((len(nd.get(d, ())) for nd in needs), default=0) for d in dists)
    ext_len = (2 * halo_w + 1) * n_loc
    max_h = max(halo_lens, default=0)
    # send_idx[p, i]: the local columns device p gathers when the exchange
    # for distance dists[i] fires (p serves the grid-column neighbor at
    # ring distance -d, so the gather list is THAT device's need set).
    # recv_idx[p, i]: where the compact buffer received from distance +d
    # lands in p's ext buffer.  Pad gathers with 0 (valid, ignored
    # downstream) and scatters with the ext_len sentinel (dropped).
    send_idx = np.zeros((n_dev, len(dists), max_h), dtype=np.int32)
    recv_idx = np.full((n_dev, len(dists), max_h), ext_len, dtype=np.int32)
    for k, d in enumerate(dists):
        for p in range(n_dev):
            i, j = divmod(p, gc)
            served = ((i - d) % gr) * gc + j
            snd = needs[served].get(d)
            if snd is not None and len(snd):
                send_idx[p, k, : len(snd)] = snd
            rcv = needs[p].get(d)
            if rcv is not None and len(rcv):
                recv_idx[p, k, : len(rcv)] = (d + halo_w) * n_loc + rcv

    # Partial-sum reduction need sets: which rows of each FOREIGN
    # segment of its row block this device actually touches, by signed
    # grid-row ring distance t (the SENDER's structure decides — the
    # receiver scatter-adds exactly what the sender ships).
    red_needs = []
    for p in range(n_dev):
        i, j = divmod(p, gc)
        seg = dev_rows[p] // n_loc
        t = (seg - j) % gc
        if gc > 1:
            t = np.where(t > gc // 2, t - gc, t)
        nd = {}
        for tt in np.unique(t):
            if tt == 0:
                continue
            nd[int(tt)] = np.unique(dev_rows[p][t == tt] % n_loc)
        red_needs.append(nd)
    red_w = max((max((abs(t) for t in nd), default=0) for nd in red_needs),
                default=0)
    red_dists = halo_distances(red_w)
    red_lens = tuple(
        max((len(nd.get(t, ())) for nd in red_needs), default=0)
        for t in red_dists)
    max_r = max(red_lens, default=0)

    sig = min(int(sigma) if sigma is not None else 8 * b_r, blk_rows)
    sig = max(sig, 1)

    rcl = chunk_l if rem_chunk_l is None else int(rem_chunk_l)
    stage_dists = tuple(d for k, d in enumerate(dists)
                        if build_stages and halo_lens[k] > 0)
    locs, rems, invs, seg_pos = [], [], [], []
    stage_ops = []
    for p in range(n_dev):
        i, j = divmod(p, gc)
        rows, cols, vals, d = (dev_rows[p], dev_cols[p], dev_vals[p],
                               dev_d[p])
        is_loc = d == 0
        loc = F.csr_from_coo(rows[is_loc], cols[is_loc] % n_loc,
                             vals[is_loc], (blk_rows, n_loc),
                             sum_duplicates=False)
        ext = (d[~is_loc] + halo_w) * n_loc + (cols[~is_loc] % n_loc)
        rem = F.csr_from_coo(rows[~is_loc], ext, vals[~is_loc],
                             (blk_rows, ext_len), sum_duplicates=False)
        # One shared per-device row sort (by TOTAL row length) so all
        # partial results add in the same permuted order — windowed to
        # sigma rows (SELL-C-sigma) so the inverse permutation stays
        # window-local.  Local and remote operands may carry different
        # tile heights; each pads its own jagged diagonals.
        total_rl = loc.row_lengths() + rem.row_lengths()
        perm = F.windowed_sort_perm(total_rl, sig)
        pj_loc = F._pjds_with_perm(loc, perm, b_r,
                                   max(diag_align, chunk_l), False,
                                   index_dtype)
        pj_rem = F._pjds_with_perm(rem, perm, b_r,
                                   max(diag_align, rcl), False,
                                   index_dtype)
        locs.append(ops.to_device_pjds(pj_loc, chunk_l))
        rems.append(ops.to_device_pjds(pj_rem, rcl))
        stages = []
        for ds in stage_dists:
            ss = ~is_loc & (d == ds)
            st = F.csr_from_coo(rows[ss], cols[ss] % n_loc, vals[ss],
                                (blk_rows, n_loc), sum_duplicates=False)
            pj_st = F._pjds_with_perm(st, perm, b_r,
                                      max(diag_align, rcl), False,
                                      index_dtype)
            stages.append(ops.to_device_pjds(pj_st, rcl))
        stage_ops.append(stages)
        inv = np.empty(blk_rows, dtype=np.int32)
        inv[perm] = np.arange(blk_rows, dtype=np.int32)
        invs.append(inv)
        seg_pos.append(np.stack(
            [inv[((j + s) % gc) * n_loc : ((j + s) % gc + 1) * n_loc]
             for s in range(gc)]))

    # Reduction gather positions (into SORTED y) and scatter-add rows.
    red_send_pos = np.zeros((n_dev, len(red_dists), max_r), dtype=np.int32)
    red_recv_idx = np.full((n_dev, len(red_dists), max_r), n_loc,
                           dtype=np.int32)
    for kk, t in enumerate(red_dists):
        for p in range(n_dev):
            i, j = divmod(p, gc)
            snd = red_needs[p].get(t)
            if snd is not None and len(snd):
                jt = (j + t) % gc
                red_send_pos[p, kk, : len(snd)] = invs[p][jt * n_loc + snd]
            src = i * gc + (j - t) % gc
            rcv = red_needs[src].get(t)
            if rcv is not None and len(rcv):
                red_recv_idx[p, kk, : len(rcv)] = rcv

    def _stack(devs, attr, edge=False):
        # Devices pad to one shared leading extent (see _pad_lead).
        arrs = [np.asarray(getattr(dv, attr)) for dv in devs]
        longest = max(a.shape[0] for a in arrs)
        return jnp.asarray(np.stack(
            [_pad_lead(a, longest, edge) for a in arrs]))

    def _stack_stages(attr, edge=False):
        # (P, S, ...) stack across devices AND stages, one shared extent.
        if not stage_dists:
            like = np.asarray(getattr(locs[0], attr))
            return jnp.zeros((n_dev, 0, 0) + like.shape[1:], like.dtype)
        arrs = [[np.asarray(getattr(st, attr)) for st in stages]
                for stages in stage_ops]
        longest = max(a.shape[0] for row in arrs for a in row)
        return jnp.asarray(np.stack(
            [np.stack([_pad_lead(a, longest, edge) for a in row])
             for row in arrs]))

    n_blocks = blk_rows // b_r

    def _max_chunks(devs) -> int:
        # Static per-output-group chunk ceiling ACROSS devices, including
        # the phantom chunks the shared-extent padding appends to each
        # device's last block.
        longest = max(int(dv.chunk_map.shape[0]) for dv in devs)
        return max(group_max_chunks(_pad_lead(np.asarray(dv.chunk_map),
                                              longest, edge=True))
                   for dv in devs)

    return DistPJDS(
        loc_val=_stack(locs, "val"),
        loc_col=_stack(locs, "col_idx"),
        loc_chunk_map=_stack(locs, "chunk_map", edge=True),
        loc_row_block=_stack(locs, "row_block", edge=True),
        rem_val=_stack(rems, "val"),
        rem_col=_stack(rems, "col_idx"),
        rem_chunk_map=_stack(rems, "chunk_map", edge=True),
        rem_row_block=_stack(rems, "row_block", edge=True),
        inv_perm=jnp.asarray(np.stack(invs)),
        send_idx=jnp.asarray(send_idx),
        recv_idx=jnp.asarray(recv_idx),
        n_dev=n_dev,
        n_loc=n_loc,
        n_blocks=n_blocks,
        b_r=b_r,
        chunk_l=chunk_l,
        halo_w=halo_w,
        halo_lens=halo_lens,
        n_rows=m.n_rows,
        sigma=sig,
        loc_max_chunks=_max_chunks(locs),
        rem_max_chunks=_max_chunks(rems),
        rem_chunk_l=None if rcl == chunk_l else rcl,
        seg_pos=jnp.asarray(np.stack(seg_pos)),
        red_send_pos=jnp.asarray(red_send_pos),
        red_recv_idx=jnp.asarray(red_recv_idx),
        stage_val=_stack_stages("val"),
        stage_col=_stack_stages("col_idx"),
        stage_chunk_map=_stack_stages("chunk_map", edge=True),
        stage_row_block=_stack_stages("row_block", edge=True),
        grid=None if gc == 1 else (gr, gc),
        red_w=red_w,
        red_lens=red_lens,
        stage_dists=stage_dists,
        stage_max_chunks=(max((_max_chunks([st for stages in stage_ops
                                            for st in stages]),), default=1)
                          if stage_dists else 1),
    )


# --------------------------------------------------------------------------
# The shard_map'd operator
# --------------------------------------------------------------------------
def _local_spmv(val, col, chunk_map, row_block, x, n_blocks, b_r, chunk_l,
                backend, max_chunks=None):
    a = ops.PJDSDevice(val=val, col_idx=col, chunk_map=chunk_map,
                       row_block=row_block, n_blocks=n_blocks, b_r=b_r,
                       chunk_l=chunk_l, max_chunks=max_chunks)
    if x.ndim == 2:
        return ops.pjds_matmat(a, x, backend=backend)
    return ops.pjds_matvec(a, x, backend=backend)


def _exchange_halo_full(x_blk, axis: str, n_dev: int, halo_w: int,
                        gc: int = 1):
    """Bulk ring ppermute halo: ext buffer = x slices of the grid-column
    neighbors at ring distances -halo_w .. +halo_w."""
    with jax.named_scope("repro.halo"):
        parts = []
        # from distance -d (send own slice +d)
        for d in range(halo_w, 0, -1):
            parts.append(jax.lax.ppermute(
                x_blk, axis, _col_ring_pairs(n_dev, gc, d)))
        parts.append(x_blk)
        for d in range(1, halo_w + 1):  # from distance +d
            parts.append(jax.lax.ppermute(
                x_blk, axis, _col_ring_pairs(n_dev, gc, -d)))
        return jnp.concatenate(parts)


# Backwards-compatible alias (pre-gathered name).
_exchange_halo = _exchange_halo_full


def _exchange_halo_gathered(x_blk, send_idx, recv_idx, axis: str, n_dev: int,
                            halo_w: int, halo_lens: tuple, gc: int = 1):
    """Compressed halo: gather referenced entries -> ppermute compact
    per-neighbor buffers -> scatter into the dense ext buffer.

    The ext buffer keeps the same (2w+1)*n_loc coordinates as the bulk
    exchange (slot w — this device's own slice — stays zero; remote
    columns never point there), so ``rem_col`` is identical either way.
    Distances whose measured halo is empty ship nothing at all.
    """
    with jax.named_scope("repro.halo"):
        n_loc = x_blk.shape[0]
        ext = jnp.zeros(((2 * halo_w + 1) * n_loc,) + x_blk.shape[1:],
                        x_blk.dtype)
        for i, d in enumerate(halo_distances(halo_w)):
            h = halo_lens[i]
            if h == 0:
                continue
            buf = x_blk[send_idx[i, :h]]
            buf = jax.lax.ppermute(buf, axis,
                                   _col_ring_pairs(n_dev, gc, -d))
            ext = ext.at[recv_idx[i, :h]].set(buf, mode="drop")
        return ext


def _reduce_partials(dist: DistPJDS, y, seg_pos, red_send_pos, red_recv_idx,
                     *, axis: str, halo: Halo):
    """Fold the grid-row partial-sum reduction into the kernel epilogue.

    ``y`` is this device's blk_rows partial result in the SORTED basis;
    the epilogue gathers the device's own y slice and the per-neighbor
    partial rows directly from it (no dense unpermute), ships the
    partials along the grid-row ring, and scatter-adds what arrives.
    """
    with jax.named_scope("repro.halo"):
        gr, gc = dist.grid_eff
        red_dists = halo_distances(dist.red_w)
        if halo == "full":
            # bulk baseline: ship whole partial segments.  Distances
            # whose measured coupling is empty must still be SKIPPED: on
            # an even ring, +gc/2 and -gc/2 are the same partner and the
            # wrap convention parks all coupling on +gc/2 — shipping the
            # empty mirror distance would double-count the shared
            # segment.
            y_own = y[seg_pos[0]]
            for kk, t in enumerate(red_dists):
                if dist.red_lens[kk] == 0:
                    continue
                buf = y[seg_pos[t % gc]]
                buf = jax.lax.ppermute(buf, axis,
                                       _row_ring_pairs(dist.n_dev, gc, t))
                y_own = y_own + buf
            return y_own
        y_own, bufs = R.partial_reduce_epilogue_ref(
            y, seg_pos[0], red_send_pos, dist.red_lens)
        for kk, t in enumerate(red_dists):
            if dist.red_lens[kk] == 0:
                continue
            buf = jax.lax.ppermute(bufs[kk], axis,
                                   _row_ring_pairs(dist.n_dev, gc, t))
            h = dist.red_lens[kk]
            y_own = y_own.at[red_recv_idx[kk, :h]].add(buf, mode="drop")
        return y_own


def dist_matvec_local(dist: DistPJDS, x_blk: jax.Array, *, axis: str,
                      mode: Mode = "overlap",
                      backend: ops.Backend = "ref",
                      halo: Halo = "gathered") -> jax.Array:
    """Per-shard body: x_blk is this device's (n_loc,) or (n_loc, k) slice;
    operand leaves of ``dist`` carry a leading length-1 device axis (from
    shard_map)."""
    sq = lambda a: a[0]
    gr, gc = dist.grid_eff
    n_loc = dist.n_loc
    loc_spmv = functools.partial(_local_spmv, n_blocks=dist.n_blocks,
                                 b_r=dist.b_r, chunk_l=dist.chunk_l,
                                 backend=backend,
                                 max_chunks=dist.loc_max_chunks)
    rem_spmv = functools.partial(_local_spmv, n_blocks=dist.n_blocks,
                                 b_r=dist.b_r, chunk_l=dist.rem_chunk_l_eff,
                                 backend=backend,
                                 max_chunks=dist.rem_max_chunks)
    loc_args = (sq(dist.loc_val), sq(dist.loc_col), sq(dist.loc_chunk_map),
                sq(dist.loc_row_block))
    rem_args = (sq(dist.rem_val), sq(dist.rem_col), sq(dist.rem_chunk_map),
                sq(dist.rem_row_block))

    if halo == "gathered":
        exchange = functools.partial(
            _exchange_halo_gathered, send_idx=sq(dist.send_idx),
            recv_idx=sq(dist.recv_idx), axis=axis, n_dev=dist.n_dev,
            halo_w=dist.halo_w, halo_lens=dist.halo_lens, gc=gc)
        no_halo = sum(dist.halo_lens) == 0
    elif halo == "full":
        exchange = functools.partial(
            _exchange_halo_full, axis=axis, n_dev=dist.n_dev,
            halo_w=dist.halo_w, gc=gc)
        no_halo = dist.halo_w == 0
    else:
        raise ValueError(halo)

    if no_halo:
        # Block-diagonal-in-x partition: no halo crosses the network, so
        # every mode degenerates to the local kernel (the grid-row
        # reduction below may still communicate when gc > 1).
        y = loc_spmv(*loc_args, x_blk)
    elif mode == "vector":
        # comm, then (implicitly fused) full spMVM — bulk synchronous.
        ext = exchange(x_blk)
        ext, x_dep = jax.lax.optimization_barrier((ext, x_blk))
        y = loc_spmv(*loc_args, x_dep) + rem_spmv(*rem_args, ext)
    elif mode == "naive":
        # local kernel first, comm strictly after (no async progress).
        y_loc = loc_spmv(*loc_args, x_blk)
        x_after, _ = jax.lax.optimization_barrier((x_blk, y_loc))
        y = y_loc + rem_spmv(*rem_args, exchange(x_after))
    elif mode == "overlap":
        # task mode: halo and local kernel are independent -> overlapped.
        ext = exchange(x_blk)
        y_loc = loc_spmv(*loc_args, x_blk)
        y = y_loc + rem_spmv(*rem_args, ext)
    elif mode == "pipeline":
        y = _pipeline_body(dist, x_blk, loc_spmv, loc_args, axis=axis,
                           halo=halo, backend=backend, gc=gc)
    else:
        raise ValueError(mode)

    if gc == 1:
        # 1-D: the device owns its whole row block — just undo the sort.
        with jax.named_scope("repro.unpermute"):
            y = y[sq(dist.seg_pos)[0]]
    else:
        y = _reduce_partials(dist, y, sq(dist.seg_pos),
                             sq(dist.red_send_pos), sq(dist.red_recv_idx),
                             axis=axis, halo=halo)
    return y.astype(x_blk.dtype)


def _pipeline_body(dist: DistPJDS, x_blk, loc_spmv, loc_args, *, axis: str,
                   halo: Halo, backend, gc: int):
    """Double-buffered halo pipeline (explicit dependency structure).

    Every stage's compact exchange buffer is gathered up front; the
    ``optimization_barrier`` before stage s's remote spMV ties in stage
    s+1's RECEIVED buffer, so exchange s+1 is materialised no later than
    the start of compute s — the guaranteed one-buffer-ahead schedule of
    the paper's explicit-overlap mode (deeper prefetch remains legal).
    """
    if not dist.stage_dists:
        if sum(dist.halo_lens) > 0:
            raise ValueError(
                "mode='pipeline' needs per-distance stage operands; "
                "repartition with build_stages=True")
        return loc_spmv(*loc_args, x_blk)
    sq = lambda a: a[0]
    n_loc = dist.n_loc
    dists = halo_distances(dist.halo_w)
    send_idx, recv_idx = sq(dist.send_idx), sq(dist.recv_idx)
    stage_spmv = functools.partial(_local_spmv, n_blocks=dist.n_blocks,
                                   b_r=dist.b_r,
                                   chunk_l=dist.rem_chunk_l_eff,
                                   backend=backend,
                                   max_chunks=dist.stage_max_chunks)
    bufs = []
    for d in dist.stage_dists:
        k = dists.index(d)
        pairs = _col_ring_pairs(dist.n_dev, gc, -d)
        with jax.named_scope("repro.halo"):
            if halo == "gathered":
                h = dist.halo_lens[k]
                buf = x_blk[send_idx[k, :h]]
            else:
                buf = x_blk
            bufs.append(jax.lax.ppermute(buf, axis, pairs))

    y = loc_spmv(*loc_args, x_blk)
    for s, d in enumerate(dist.stage_dists):
        k = dists.index(d)
        if s + 1 < len(bufs):
            # double buffer: the NEXT stage's received buffer must exist
            # before this stage's remote compute is allowed to start.
            bufs[s], bufs[s + 1] = jax.lax.optimization_barrier(
                (bufs[s], bufs[s + 1]))
        if halo == "gathered":
            h = dist.halo_lens[k]
            loc_cols = recv_idx[k, :h] - (d + dist.halo_w) * n_loc
            ext_s = jnp.zeros((n_loc,) + x_blk.shape[1:], x_blk.dtype
                              ).at[loc_cols].set(bufs[s], mode="drop")
        else:
            ext_s = bufs[s]
        y = y + stage_spmv(sq(dist.stage_val)[s], sq(dist.stage_col)[s],
                           sq(dist.stage_chunk_map)[s],
                           sq(dist.stage_row_block)[s], ext_s)
    return y


def place_on_mesh(tree, mesh: Mesh, axis: str = "data"):
    """Commit every array of ``tree`` (a ``DistPJDS`` or a global padded
    vector) to ``mesh``, split along its leading axis — one row slab per
    device.  Built on the host, a partition otherwise lives on the
    default device, and every sharded apply would move the other
    devices' slabs across the interconnect again."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sharding),
                                  tree)


def _make_dist_op(dist: DistPJDS, mesh: Mesh, axis: str, mode: Mode,
                  backend: ops.Backend, halo: Halo, multi_rhs: bool):
    n_dev = dist.n_dev
    if mesh.shape[axis] != n_dev:
        raise ValueError(f"mesh axis {axis}={mesh.shape[axis]} != {n_dev}")

    operand_specs = DistPJDS(
        **{f.name: (P(axis) if getattr(dist, f.name) is not None else None)
           for f in dataclasses.fields(DistPJDS)
           if f.metadata.get("static") is not True},
        **{f.name: getattr(dist, f.name)
           for f in dataclasses.fields(DistPJDS)
           if f.metadata.get("static") is True},
    )
    x_spec = P(axis, None) if multi_rhs else P(axis)

    # check_vma=False: a pallas_call's out_shape carries no varying-axes
    # annotation, so the kernel backend cannot run under the VMA check.
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(operand_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    def _mv(d, x_blk):
        return dist_matvec_local(d, x_blk, axis=axis, mode=mode,
                                 backend=backend, halo=halo)

    return functools.partial(_mv, dist)


def dist_matvec(dist: DistPJDS, x: jax.Array, mesh: Mesh, axis: str = "data",
                mode: Mode = "overlap",
                backend: ops.Backend = "ref",
                halo: Halo = "gathered") -> jax.Array:
    return _make_dist_op(dist, mesh, axis, mode, backend, halo,
                         multi_rhs=False)(x)


def dist_matmat(dist: DistPJDS, x: jax.Array, mesh: Mesh, axis: str = "data",
                mode: Mode = "overlap",
                backend: ops.Backend = "ref",
                halo: Halo = "gathered") -> jax.Array:
    if x.ndim != 2:
        raise ValueError(f"dist_matmat expects x of shape (n, k); got "
                         f"{x.shape}")
    return _make_dist_op(dist, mesh, axis, mode, backend, halo,
                         multi_rhs=True)(x)
