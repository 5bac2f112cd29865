"""The paper's performance models (Eq. 1-4), retargeted at TPU v5e.

Paper (Fermi GPU)                    ->  here (TPU v5e target)
  B_GPU   device-memory bandwidth        HBM_BW       = 819 GB/s
  B_PCI   host link bandwidth            ICI_LINK_BW  = 50 GB/s  (per link)
  SP/DP peak                             PEAK_FLOPS   = 197e12 bf16 / chip

Eq. (1): worst-case code balance of the ELLPACK/pJDS kernel,
    B_W^DP = (6 + 4*alpha + 8/N_nzr_max) bytes/flop
with alpha in [1/N_nzr, 1] the RHS cache-reuse parameter.  On TPU the
RHS of most formats is gathered ahead of the kernels, in XLA (the TPU
compiler has no in-kernel gather from a large array): every stored slot
reads x once and the kernel streams the gathered copy, so the program
prices its RHS with :func:`gathered_rhs_bytes` and :func:`spmvm_bytes`
stays the paper's minimum (DESIGN.md §2).  That gather is bound by its
element count, not its bytes: XLA:TPU's scalar gather reads one element
in ``TPUSpec.gather_s`` seconds, so dispatch adds
:func:`gather_seconds` for every slot a format gathers in XLA.  The
windowed SELL-C-sigma format gathers in-window slots inside the kernel
from a VMEM window of x (:func:`window_rhs_bytes`) and only its
out-of-window remainder in XLA.

Eq. (2)-(4): device-vs-link time model.  The paper derives the range of
N_nzr for which accelerator spMVM is worthwhile given the ratio
B_dev/B_link; identical math bounds when a TPU chip's spMVM is worth the
ICI halo traffic.

Also hosts the three-term roofline used by EXPERIMENTS.md §Roofline,
and the CALIBRATION layer: the spec numbers above are data-sheet values,
but ``repro.tune`` fits an effective bandwidth scale and a per-format
fixed overhead from MEASURED spMVM rows (``tune.calibrate``), installs
them here (:func:`set_calibration`), and every
:func:`predicted_spmv_seconds` call — including the dispatch heuristic
``kernels.ops.select_format`` — then prices candidates against the
machine that was actually measured instead of the data sheet.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

__all__ = [
    "TPUSpec",
    "TPU_V5E",
    "Calibration",
    "set_calibration",
    "get_calibration",
    "clear_calibration",
    "code_balance",
    "alpha_range",
    "t_mvm",
    "t_link",
    "t_link_gathered",
    "predicted_dist_spmv_seconds",
    "choose_halo",
    "n_nzr_upper_for_link_penalty",
    "n_nzr_lower_for_link_penalty",
    "spmvm_flops",
    "spmvm_bytes",
    "gathered_rhs_bytes",
    "gather_seconds",
    "window_rhs_bytes",
    "perm_traffic_bytes",
    "SORTED_ROW_FORMATS",
    "CMRS_RIS_BYTES",
    "cmrs_reduce_seconds",
    "predicted_spmv_seconds",
    "SOLVER_SPMV_COUNT",
    "SOLVER_VECTOR_PASSES",
    "solver_iteration_bytes",
    "predicted_iteration_seconds",
    "roofline_terms",
    "RooflineReport",
]


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    name: str
    peak_flops: float        # FLOP/s per chip (bf16 MXU)
    peak_flops_f32: float    # FLOP/s per chip (f32 VPU-bound spMVM path)
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link
    hbm_bytes: int
    gather_s: float          # seconds per element of an XLA gather


TPU_V5E = TPUSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    peak_flops_f32=197e12 / 4,  # f32 through the MXU at quarter rate
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16 * 2 ** 30,
    # ~116M gathered slots/s, the same in every format and for a 1.1 MB
    # as for a 13.6 MB x (PERF.md §5, the RHS gather on one v5e chip)
    gather_s=8.6e-9,
)


# ------------------------------------------------------------- calibration
@dataclasses.dataclass(frozen=True)
class Calibration:
    """Measured correction to the memory-bound time model.

    ``predicted = bytes / (spec.hbm_bw * bw_scale) + overhead_s[fmt]``

    ``bw_scale`` is the ratio of the EFFECTIVE streaming bandwidth the
    measured kernel achieved to the spec's data-sheet number (off-TPU it
    absorbs the CPU-vs-TPU gap wholesale, so the model still ranks
    candidates on the machine that was measured); ``overhead_s`` is a
    per-format fixed launch/epilogue cost in seconds (missing formats
    cost 0).  Fit by ``repro.tune.calibrate.fit_calibration`` from
    measured rows; ``source`` records where the rows came from.
    """

    bw_scale: float
    overhead_s: Mapping[str, float] = dataclasses.field(default_factory=dict)
    source: str = ""
    # ---- link calibration (repro.tune.calibrate.fit_link_calibration) ----
    # Effective ICI/interconnect bandwidth scale, and the per-MESSAGE
    # fixed cost of each halo flavour in seconds — the gather/ppermute/
    # scatter set-up the pure bytes/bandwidth term cannot see.  This is
    # exactly why an uncalibrated model makes the gathered exchange look
    # free at toy scale: 15x fewer bytes, but the same number of
    # messages, each paying pack/unpack latency.  Missing halo keys cost
    # 0 (the uncalibrated data-sheet behaviour).
    link_bw_scale: float = 1.0
    msg_overhead_s: Mapping[str, float] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        if not (self.bw_scale > 0):
            raise ValueError(f"bw_scale must be > 0; got {self.bw_scale}")
        if not (self.link_bw_scale > 0):
            raise ValueError(
                f"link_bw_scale must be > 0; got {self.link_bw_scale}")


_CALIBRATION: Optional[Calibration] = None


def set_calibration(cal: Optional[Calibration]) -> None:
    """Install ``cal`` as the process-wide default calibration: every
    subsequent :func:`predicted_spmv_seconds` call without an explicit
    ``calibration=`` argument uses it (including the ones inside
    ``kernels.ops.select_format``).  ``None`` uninstalls."""
    global _CALIBRATION
    if cal is not None and not isinstance(cal, Calibration):
        raise TypeError(f"expected Calibration or None; got {type(cal)}")
    _CALIBRATION = cal


def get_calibration() -> Optional[Calibration]:
    return _CALIBRATION


def clear_calibration() -> None:
    set_calibration(None)


# ---------------------------------------------------------------- Eq. (1)
def code_balance(alpha: float, n_nzr: float, value_bytes: int = 8,
                 index_bytes: int = 4) -> float:
    """Worst-case code balance in bytes/flop (paper Eq. 1, generalised to
    any value precision).  DP (value_bytes=8):  6 + 4*alpha + 8/N_nzr.
    SP (value_bytes=4):                          4 + 2*alpha + 4/N_nzr.
    """
    # per non-zero: val + col_idx + alpha*RHS element + LHS (read+write) / row,
    # over 2 flops.  DP: (8 + 4 + 8a + 16/N)/2 = 6 + 4a + 8/N  (paper Eq. 1)
    # SP: (4 + 4 + 4a +  8/N)/2 = 4 + 2a + 4/N
    return (
        value_bytes + index_bytes + value_bytes * alpha
        + 2 * value_bytes / n_nzr
    ) / 2.0


def alpha_range(n_nzr: float) -> tuple[float, float]:
    """Admissible RHS reuse parameter: [1/N_nzr (perfect reuse), 1 (none)]."""
    return (1.0 / n_nzr, 1.0)


# ------------------------------------------------------------- Eq. (2)-(4)
def t_mvm(n_rows: float, n_nzr: float, alpha: float, dev_bw: float,
          value_bytes: int = 8) -> float:
    """Paper Eq. (2) left: wallclock of the on-device spMVM.
    T = (value_bytes*N / B_dev) * [N_nzr*(alpha + 3/2) + 2]  (DP form)."""
    return (value_bytes * n_rows / dev_bw) * (n_nzr * (alpha + 1.5) + 2.0)


def t_link(n_rows: float, link_bw: float, value_bytes: int = 8) -> float:
    """Paper Eq. (2) right: moving RHS in and LHS out over the slow link."""
    return 2 * value_bytes * n_rows / link_bw


def t_link_gathered(halo_elems: float, link_bw: float,
                    value_bytes: int = 8, k: int = 1, *,
                    msgs: int = 0, halo: str = "gathered",
                    calibration="default") -> float:
    """Gathered-halo refinement of the Eq. (2) link term: with the
    compressed exchange only the MEASURED per-neighbor halo entries cross
    the link, not the full slice.  ``halo_elems`` is the sum of the
    per-neighbor gathered halo sizes (``DistPJDS.halo_lens`` plus, on a
    2-D grid, ``red_lens``; equals ``comm_bytes_per_device() /
    value_bytes``); ``k`` scales for a multi-RHS block, whose halo
    buffers carry k columns per entry.  With this term the model prices
    what the wire actually carries — a purely block-diagonal partition
    (halo_elems == 0, msgs == 0) costs no link time at all, where the
    slice-proportional Eq. (2) term would still charge
    ``2 * n_loc * value_bytes / B_link``.

    ``msgs`` is the point-to-point message count per device per spMVM
    (``DistPJDS.comm_msgs_per_device``): each message pays the
    calibrated per-message fixed cost ``msg_overhead_s[halo]`` — the
    gather/ppermute/scatter set-up that dominates at toy scale and made
    the UNcalibrated model wrongly prefer the gathered exchange there.
    The link bandwidth is scaled by the calibrated ``link_bw_scale``.
    Without an installed calibration (or with ``msgs=0``, the old
    signature) the term reduces to the pure bytes/bandwidth model."""
    if calibration == "default":
        calibration = _CALIBRATION
    scale = calibration.link_bw_scale if calibration is not None else 1.0
    fixed = (calibration.msg_overhead_s.get(halo, 0.0)
             if calibration is not None else 0.0)
    return value_bytes * k * halo_elems / (link_bw * scale) + msgs * fixed


def predicted_dist_spmv_seconds(dist, halo: str = "gathered",
                                mode: str = "overlap", *, k: int = 1,
                                value_bytes: int = 4, index_bytes: int = 4,
                                spec: TPUSpec = TPU_V5E,
                                calibration="default") -> float:
    """Per-device wall-time estimate of one distributed spMVM over a
    :class:`~repro.core.dist_spmv.DistPJDS` partition (duck-typed to
    avoid a core->core import cycle).

    compute:  local + remote operand streams through the calibrated
              single-device model (Eq. 1/2 left);
    comm:     the calibrated link term — measured bytes over the scaled
              link bandwidth plus the per-message fixed cost
              (:func:`t_link_gathered`).

    Modes ``vector``/``naive`` serialize compute after comm; modes
    ``overlap``/``pipeline`` hide the exchange behind the LOCAL kernel
    (the paper's §3.1 task mode), so only the part of the exchange that
    outlasts it is charged.  This is the decision function behind
    ``dist_operator(halo="auto")`` — see :func:`choose_halo`."""
    if calibration == "default":
        calibration = _CALIBRATION
    blk_rows = dist.n_blocks * dist.b_r

    def _t(val_arr):
        elems = int(val_arr.shape[1]) * int(val_arr.shape[2])
        if elems == 0:
            return 0.0
        return k * predicted_spmv_seconds(
            elems, blk_rows, elems / blk_rows, spec=spec,
            value_bytes=value_bytes, index_bytes=index_bytes,
            fmt="pjds", calibration=calibration)

    t_loc = _t(dist.loc_val)
    t_rem = _t(dist.rem_val)
    elems = dist.comm_bytes_per_device(value_bytes=1, k=k, halo=halo)
    t_comm = t_link_gathered(elems, spec.ici_bw, value_bytes, 1,
                             msgs=dist.comm_msgs_per_device(halo),
                             halo=halo, calibration=calibration)
    if mode in ("overlap", "pipeline"):
        return max(t_loc, t_comm) + t_rem
    return t_loc + t_rem + t_comm


def choose_halo(dist, mode: str = "overlap", *, k: int = 1,
                value_bytes: int = 4, spec: TPUSpec = TPU_V5E,
                calibration="default") -> str:
    """The calibrated gathered-vs-full crossover decision
    (``dist_operator(halo="auto")``): price both exchange flavours with
    :func:`predicted_dist_spmv_seconds` and return the cheaper one.
    Ties (e.g. halo_w == 0: nothing crosses the wire either way) go to
    ``"gathered"``."""
    t_g = predicted_dist_spmv_seconds(dist, "gathered", mode, k=k,
                                      value_bytes=value_bytes, spec=spec,
                                      calibration=calibration)
    t_f = predicted_dist_spmv_seconds(dist, "full", mode, k=k,
                                      value_bytes=value_bytes, spec=spec,
                                      calibration=calibration)
    return "full" if t_f < t_g else "gathered"


def n_nzr_upper_for_link_penalty(dev_bw: float, link_bw: float,
                                 alpha: float) -> float:
    """Paper Eq. (3): below this N_nzr the link transfer costs >= 50% extra
    (T_MVM <= T_link) -> accelerator not worthwhile."""
    return 2.0 * (dev_bw / link_bw - 1.0) / (alpha + 1.5)


def n_nzr_lower_for_link_penalty(dev_bw: float, link_bw: float,
                                 alpha: float) -> float:
    """Paper Eq. (4): above this N_nzr the link penalty is < 10%
    (T_MVM >= 10*T_link)."""
    return (20.0 * dev_bw / link_bw - 2.0) / (alpha + 1.5)


# -------------------------------------------------------------- roofline
def spmvm_flops(nnz: int) -> int:
    """2 flops (multiply + add) per stored non-zero."""
    return 2 * nnz


def spmvm_bytes(stored_elements: int, n_rows: int, alpha: float,
                n_nzr: float, value_bytes: int = 8,
                index_bytes: int = 4,
                vec_bytes: int | None = None) -> float:
    """Minimum HBM traffic of one spMVM in a given format: matrix values +
    indices stream once; RHS traffic scales with alpha; LHS written once.

    ``value_bytes``/``index_bytes`` are the STORED matrix widths, so a
    bf16-value / int16-index build is priced at its compressed stream
    (the whole point of the compressed formats: bytes/nnz drops from
    4+4 to 2+2 before padding).  ``vec_bytes`` is the width of the
    RHS/LHS vectors, which do NOT compress with the matrix — a bf16
    build still reads f32 x and writes the f32 accumulator — and
    defaults to at least f32 (``max(4, value_bytes)``)."""
    if vec_bytes is None:
        vec_bytes = max(4, value_bytes)
    return (
        stored_elements * (value_bytes + index_bytes)
        + alpha * n_nzr * n_rows * vec_bytes
        + 2 * n_rows * vec_bytes
    )


def gathered_rhs_bytes(stored_elements: int, vec_bytes: int = 4) -> float:
    """HBM traffic of the RHS as the program moves it: the XLA gather
    ahead of every kernel (``kernels._backend.gather_rhs``) reads x once
    per STORED slot, padding included (irregular), and writes the
    gathered copy, which the kernel reads back beside the values: three
    vector-width accesses per slot.  It replaces the ``alpha`` RHS term
    of :func:`spmvm_bytes`; padding the kernel skips (ELLPACK-R's
    per-tile early exit) is still gathered."""
    return 3.0 * float(stored_elements) * vec_bytes


def gather_seconds(gathered_elements: int, spec: TPUSpec = TPU_V5E) -> float:
    """Time of an XLA gather of ``gathered_elements`` scalars on
    ``spec``: bound by the element count, not by HBM bytes, at
    ``spec.gather_s`` per element (PERF.md §5)."""
    return float(gathered_elements) * spec.gather_s


def window_rhs_bytes(n_blocks: int, window: int, vec_bytes: int = 4) -> float:
    """HBM traffic of the RHS on the windowed SELL-C-sigma path: the
    kernel fetches one ``window``-entry slice of x per row block into
    VMEM and gathers from it there (``kernels.wsell_spmv``), so no
    gathered copy is written.  Consecutive blocks that share a window
    skip the fetch; this prices one fetch per block, the most."""
    return float(n_blocks) * window * vec_bytes


# Formats whose rows are stored in a sorted order (pJDS globally,
# SELL-C-sigma within sigma windows), priced with an unpermute pass.
SORTED_ROW_FORMATS = ("pjds", "sell", "wsell")


def perm_traffic_bytes(n_rows: int, value_bytes: int = 4,
                       index_bytes: int = 4) -> float:
    """Extra HBM traffic of undoing a row sort OUTSIDE the kernel: the
    permutation index stream plus a read+write pass over y.  Every sorted
    format (:data:`SORTED_ROW_FORMATS`) is charged it; pJDS and SELL
    unpermute in XLA after the kernel, and the windowed SELL's kernel
    restores its rows' order itself where sigma divides an output group
    (``ops.wsell_matvec``), so its charge is an upper bound
    (DESIGN.md §3, §5)."""
    return float(n_rows) * (2 * value_bytes + index_bytes)


# CMRS stores one extra byte per slot: the int8 row-in-strip stream that
# routes each densely-packed slot back to its row (core.formats.CMRSMatrix).
CMRS_RIS_BYTES = 1


def cmrs_reduce_seconds(stored_elements: int, b_r: int,
                        spec: TPUSpec = TPU_V5E) -> float:
    """Compute term of the CMRS in-kernel segment reduction: every
    stored slot feeds a one-hot ``(1, chunk*b_r) @ (chunk*b_r, b_r)``
    matmul, i.e. ``2 * b_r`` f32 MXU flops per slot.  CMRS trades
    ELLPACK/pJDS's padding bytes for these flops, so callers price it
    as ``max(memory_term, this)`` — on TPU the MXU overlaps the HBM
    stream, and whichever term is longer bounds the kernel."""
    return 2.0 * float(stored_elements) * float(b_r) / spec.peak_flops_f32


def predicted_spmv_seconds(stored_elements: int, n_rows: int, n_nzr: float,
                           perm_bytes: float = 0.0,
                           irregular_factor: float = 1.0,
                           spec: TPUSpec = TPU_V5E,
                           value_bytes: int = 4,
                           index_bytes: int = 4,
                           vec_bytes: int | None = None,
                           fmt: str | None = None,
                           calibration="default",
                           gathered: int = 0,
                           rhs_bytes: float | None = None) -> float:
    """Time estimate of one spMVM in a candidate format — the quantity
    ``kernels.ops.select_format`` minimises.  The RHS is priced as the
    program moves it: by default :func:`gathered_rhs_bytes` (read per
    stored slot, written and read back as the gathered stream), or
    ``rhs_bytes`` where the format moves it otherwise
    (:func:`window_rhs_bytes`); ``gathered`` XLA gather elements add
    :func:`gather_seconds`, which bounds a gathered format on the chip
    (0, the default, prices bytes alone);
    ``irregular_factor`` derates formats without a blocked kernel (CSR's
    scalar gather stream cannot saturate HBM).  ``value_bytes`` /
    ``index_bytes`` are the STORED stream widths, ``vec_bytes`` the
    uncompressed RHS/LHS width — see :func:`spmvm_bytes`.

    ``calibration`` applies a measured :class:`Calibration` — effective
    bandwidth scale plus the per-format overhead looked up by ``fmt`` —
    on top of the structural byte model; the default picks up whatever
    :func:`set_calibration` installed (``None`` forces the uncalibrated
    data-sheet estimate)."""
    if vec_bytes is None:
        vec_bytes = max(4, value_bytes)
    if rhs_bytes is None:
        rhs_bytes = gathered_rhs_bytes(stored_elements, vec_bytes)
    # alpha = 0: rhs_bytes carries every RHS read
    b = (spmvm_bytes(stored_elements, n_rows, 0.0, n_nzr,
                     value_bytes, index_bytes, vec_bytes) + rhs_bytes)
    t = ((b * irregular_factor + perm_bytes) / spec.hbm_bw
         + gather_seconds(gathered, spec))
    if calibration == "default":
        calibration = _CALIBRATION
    if calibration is not None:
        t = t / calibration.bw_scale
        if fmt is not None:
            t += calibration.overhead_s.get(fmt, 0.0)
    return max(t, 0.0)


# ------------------------------------------------- solver-iteration model
# spMV applications per Krylov iteration (BiCGStab applies A twice).
SOLVER_SPMV_COUNT: Mapping[str, int] = {
    "cg": 1,
    "bicgstab": 2,
    "block_cg": 1,
}

# Carrier-vector HBM passes per iteration BEYOND the spMV's own rhs/lhs
# traffic (each pass = n_rows * vec_bytes read OR written), counted off
# the solver bodies in ``core.solvers``:
#
#   cg composed:   3 axpys (2 passes each: read+write over x/r/p) +
#                  3 dots re-reading (p, Ap_vs_r, r) = 6 + 3 extra Ap/r
#                  reads -> 12;  fused: the dots ride the spMV epilogue
#                  and only the 3 axpys + Ap read remain -> 7.
#   bicgstab composed: two half-steps, ~2x cg's vector work -> 22;
#                  fused: -> 14.
#   block_cg:      same passes as cg but each is k columns wide; the
#                  caller multiplies by k via ``n_vec``; no fused path.
SOLVER_VECTOR_PASSES: Mapping[str, Mapping[str, int]] = {
    "cg": {"composed": 12, "fused": 7},
    "bicgstab": {"composed": 22, "fused": 14},
    "block_cg": {"composed": 12, "fused": 12},
}


def solver_iteration_bytes(stored_elements: int, n_rows: int, n_nzr: float,
                           *, method: str = "cg",
                           strategy: str = "composed",
                           value_bytes: int = 4, index_bytes: int = 4,
                           vec_bytes: int = 4, n_vec: int = 1) -> float:
    """Minimum HBM traffic of ONE solver iteration: the method's spMV
    streams plus the carrier-vector passes around them.

    This is the honesty fix the fused-iteration work is judged with:
    pricing an iteration as spMV bytes only (the old ``perf_iter`` /
    ``roofline`` habit) hides exactly the traffic the fused kernel
    removes — the axpy/dot passes over x/r/p — and overstates how close
    the composed baseline already was to the roofline.  ``n_vec``
    scales the carrier passes for block solvers (k columns per pass).
    """
    spmv_count = SOLVER_SPMV_COUNT[method]
    passes = SOLVER_VECTOR_PASSES[method][strategy]
    spmv = (spmvm_bytes(stored_elements, n_rows, 0.0, n_nzr,
                        value_bytes, index_bytes, vec_bytes)
            + gathered_rhs_bytes(stored_elements, vec_bytes))
    return spmv_count * spmv + passes * n_vec * float(n_rows) * vec_bytes


def predicted_iteration_seconds(stored_elements: int, n_rows: int,
                                n_nzr: float, *, method: str = "cg",
                                strategy: str = "composed",
                                spec: TPUSpec = TPU_V5E,
                                value_bytes: int = 4, index_bytes: int = 4,
                                vec_bytes: int = 4, n_vec: int = 1,
                                fmt: str | None = None,
                                calibration="default") -> float:
    """Memory-bound time of one solver iteration — the quantity
    ``tune.tune_solver`` measures and ``benchmarks/bench_solve``
    reports predicted-vs-measured for.  Same calibration semantics as
    :func:`predicted_spmv_seconds`, with the per-format overhead
    charged once per spMV application."""
    b = solver_iteration_bytes(
        stored_elements, n_rows, n_nzr, method=method, strategy=strategy,
        value_bytes=value_bytes, index_bytes=index_bytes,
        vec_bytes=vec_bytes, n_vec=n_vec)
    t = b / spec.hbm_bw
    if calibration == "default":
        calibration = _CALIBRATION
    if calibration is not None:
        t = t / calibration.bw_scale
        if fmt is not None:
            t += SOLVER_SPMV_COUNT[method] * calibration.overhead_s.get(
                fmt, 0.0)
    return max(t, 0.0)


@dataclasses.dataclass
class RooflineReport:
    compute_s: float
    memory_s: float
    collective_s: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def fraction_of_roofline(self, achieved_s: float) -> float:
        """How close a measured/estimated step time is to the roofline bound."""
        return self.bound_s / achieved_s if achieved_s > 0 else 0.0


def roofline_terms(hlo_flops: float, hlo_bytes: float,
                   collective_bytes: float, chips: int,
                   spec: TPUSpec = TPU_V5E,
                   flops_rate: float | None = None) -> RooflineReport:
    """EXPERIMENTS.md §Roofline three-term model.

    compute    = HLO_FLOPs / (chips * peak)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

    ``hlo_flops``/``hlo_bytes`` are GLOBAL (whole-program) numbers from
    ``compiled.cost_analysis()``; collective_bytes parsed from the HLO.
    """
    rate = flops_rate if flops_rate is not None else spec.peak_flops
    return RooflineReport(
        compute_s=hlo_flops / (chips * rate),
        memory_s=hlo_bytes / (chips * spec.hbm_bw),
        collective_s=collective_bytes / (chips * spec.ici_bw),
        chips=chips,
    )
