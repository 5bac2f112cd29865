"""Per-kernel microbenchmarks + the chunk_l / b_r trade-off study
+ the compressed-stream (bytes/nnz) accounting.

Wall-times are from the jitted REF path (the Pallas kernels execute in
interpret mode on CPU — Python per grid step — so their wall-time is not
meaningful; their correctness is covered by tests).  What IS meaningful
here and transfers to TPU:
* padding overhead as a function of (b_r, diag_align/chunk_l) — the
  structural cost of bigger VMEM tiles,
* measured stored bytes/nnz per storage variant (f32+int32 baseline,
  int16-compressed indices, bf16+int16 fully compressed) with the
  perf-model's predicted memory-bound spMVM time per variant — the
  roofline rows CI tracks, mirroring the paper's memory-footprint
  comparison at the byte-stream level,
* the arithmetic-intensity jump from spMVM to multi-RHS spMM (the
  SparseFFN case), straight from the byte/flop model.

The compressed-variant rows double as a REGRESSION GUARD: the bench
fails (non-zero exit, so the CI bench-smoke job fails) if the fully
compressed pJDS build stops saving at least 35% of the f32+int32
baseline's stored bytes/nnz, or if any compressed variant drifts from
the f32 reference beyond 1e-2 relative error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F, matrices as M, perf_model as PM
from repro.kernels import ops
from .common import seeded_rng, time_fn, csv_row, write_bench_json

# Compressed-variant guard thresholds (see module docstring).
MAX_COMPRESSED_BYTES_RATIO = 0.65
MAX_COMPRESSED_REL_ERR = 1e-2

_VARIANTS = [
    # (label, value dtype (None = keep f32), index_dtype)
    ("f32+int32", None, np.int32),
    ("f32+int16", None, "auto"),
    ("bf16+int16", jnp.bfloat16, "auto"),
]


def _stored_bytes(sd: ops.SparseDevice) -> int:
    """Measured footprint of the device representation: value stream +
    index stream at their ACTUAL dtypes + per-format metadata arrays."""
    d = sd.dev
    if sd.fmt == "csr":
        return d.data.nbytes + d.indices.nbytes + d.row_ids.nbytes
    n = d.val.nbytes + d.col_idx.nbytes
    if sd.fmt == "ellpack_r":
        return n + d.rowlen.nbytes + d.tile_chunks.nbytes
    n += d.chunk_map.nbytes
    if sd.fmt == "sell":
        n += d.inv_perm.nbytes
    elif sd.inv_perm is not None:
        n += sd.inv_perm.nbytes
    return n


def bytes_per_nnz_rows(m, x, truth, mat: str, fmt: str, rows: list,
                       print_rows: bool) -> dict:
    """One bytes/nnz + predicted-vs-measured roofline row per storage
    variant; returns {variant label: bytes_per_nnz}."""
    out = {}
    n, n_nzr = m.n_rows, m.n_nzr
    scale = max(np.abs(truth).max(), 1.0)
    for label, vdt, idt in _VARIANTS:
        sd = ops.as_device(m, fmt, dtype=vdt, index_dtype=idt)
        bpn = _stored_bytes(sd) / m.nnz
        vb = np.dtype(jnp.bfloat16 if vdt is not None else np.float32).itemsize
        ib = sd.index_dtype.itemsize
        # vectors stay f32 whatever the stored width (vec_bytes default)
        pred_s = PM.predicted_spmv_seconds(
            sd.stored_slots, n, n_nzr,
            perm_bytes=(PM.perm_traffic_bytes(n, 4)
                        if fmt in PM.SORTED_ROW_FORMATS else 0.0),
            value_bytes=vb, index_bytes=ib)
        f = jax.jit(lambda v, sd=sd: sd.matvec(v, backend="ref"))
        xv = jnp.asarray(x)
        t_meas = time_fn(f, xv)
        err = float(np.abs(np.asarray(f(xv), np.float64) - truth).max()
                    / scale)
        if err > MAX_COMPRESSED_REL_ERR:
            raise SystemExit(
                f"REGRESSION: {mat}/{fmt}/{label} drifted from the f32 "
                f"reference: rel err {err:.2e} > {MAX_COMPRESSED_REL_ERR}")
        rows.append(dict(
            kind="bytes_per_nnz", matrix=mat, fmt=fmt, variant=label,
            bytes_per_nnz=bpn, value_bytes=vb, index_bytes=ib,
            predicted_s=pred_s, measured_ref_s=t_meas,
            roofline_fraction=pred_s / t_meas if t_meas > 0 else 0.0,
            rel_err_vs_f32=err,
            gbs_at_roofline=_stored_bytes(sd) / pred_s / 1e9))
        if print_rows:
            print(csv_row(f"bytes_{mat}_{fmt}_{label}", t_meas * 1e6,
                          f"bytes/nnz={bpn:.2f} pred={pred_s*1e6:.1f}us "
                          f"err={err:.1e}"))
        out[label] = bpn
    return out


def run(print_rows=True):
    rows = []
    m = M.uhbr(scale=0.003)
    n = m.shape[0]
    rng = seeded_rng()
    x = rng.standard_normal(n).astype(np.float32)

    # --- b_r x diag_align padding overhead (storage elements vs nnz) ----
    for b_r in (32, 128, 256):
        for diag_align in (8, 64):
            pj = F.csr_to_pjds(m, b_r=b_r, diag_align=diag_align)
            over = F.storage_elements(pj) / m.nnz - 1
            rows.append(dict(kind="padding", b_r=b_r, diag_align=diag_align,
                             overhead=over))
            if print_rows:
                print(csv_row(f"pad_br{b_r}_align{diag_align}", 0.0,
                              f"padding_overhead={100*over:.2f}%"))

    # --- chunk_l sweep: grid steps vs padding (the tile-size default) ---
    # The prefetched kernels stream (chunk_l, b_r) tiles and pad every
    # block to chunk_l jagged diagonals; chunk_l=16 is the dispatch-layer
    # default (ops.as_device) — this row records the measured trade.
    for chunk_l in (8, 16, 32):
        pj = F.csr_to_pjds(m, b_r=128, diag_align=chunk_l,
                           permuted_cols=False)
        over = F.storage_elements(pj) / m.nnz - 1
        steps = int(np.sum(pj.block_len // chunk_l))
        rows.append(dict(kind="chunk_l_sweep", chunk_l=chunk_l,
                         overhead=over, grid_steps=steps,
                         tile_kib=chunk_l * 128 * 4 / 1024))
        if print_rows:
            print(csv_row(f"chunk_l{chunk_l}", 0.0,
                          f"overhead={100*over:.2f}% steps={steps}"))

    # --- bytes/nnz + roofline rows per storage variant + guard ----------
    ms = M.samg(scale=0.004)
    xs = rng.standard_normal(ms.shape[0]).astype(np.float32)
    guard = []
    for mat, mm, xx in (("uhbr", m, x), ("samg", ms, xs)):
        truth = None
        for fmt in ("pjds", "sell"):
            if truth is None:
                truth = F.csr_to_dense(mm).astype(np.float64) @ xx
            bpn = bytes_per_nnz_rows(mm, xx, truth, mat, fmt, rows,
                                     print_rows)
            ratio = bpn["bf16+int16"] / bpn["f32+int32"]
            rows.append(dict(kind="compressed_ratio", matrix=mat, fmt=fmt,
                             ratio=ratio))
            if fmt == "pjds":
                guard.append((mat, ratio))
            if print_rows:
                print(csv_row(f"compress_{mat}_{fmt}", 0.0,
                              f"stored_ratio={ratio:.3f}"))
    for mat, ratio in guard:
        if ratio > MAX_COMPRESSED_BYTES_RATIO:
            raise SystemExit(
                f"REGRESSION: bf16+int16 pJDS on {mat} stores "
                f"{ratio:.2f}x the f32+int32 bytes/nnz "
                f"(> {MAX_COMPRESSED_BYTES_RATIO})")

    # --- spmv vs spmm arithmetic intensity (model) + measured ref time --
    pj = F.csr_to_pjds(m, b_r=128, diag_align=8)
    dev = ops.to_device_pjds(pj)
    xp = jnp.asarray(pj.permute(x))
    f_mv = jax.jit(lambda v: ops.pjds_matvec(dev, v))
    t_mv = time_fn(f_mv, xp)
    rows.append(dict(kind="spmv", t_us=t_mv * 1e6,
                     gfs=2 * m.nnz / t_mv / 1e9))
    if print_rows:
        print(csv_row("pjds_spmv_ref", t_mv * 1e6,
                      f"{rows[-1]['gfs']:.2f}GF/s"))
    for n_rhs in (8, 64):
        xs2 = jnp.asarray(
            rng.standard_normal((pj.n_rows_pad, n_rhs)).astype(np.float32))
        f_mm = jax.jit(lambda v: ops.pjds_matmat(dev, v))
        t_mm = time_fn(f_mm, xs2)
        # intensity: flops / matrix bytes (values+idx), RHS amortised
        inten = 2 * n_rhs / 8.0
        rows.append(dict(kind=f"spmm{n_rhs}", t_us=t_mm * 1e6,
                         gfs=2 * m.nnz * n_rhs / t_mm / 1e9,
                         intensity=inten))
        if print_rows:
            print(csv_row(f"pjds_spmm_rhs{n_rhs}", t_mm * 1e6,
                          f"{rows[-1]['gfs']:.2f}GF/s intensity={inten:.0f}F/B"))

    # --- ELLPACK-R vs pJDS on a high-variance matrix (the paper's win) --
    pj2 = F.csr_to_pjds(ms, b_r=128)
    ell2 = F.csr_to_ell(ms, row_align=128)
    d_p = ops.to_device_pjds(pj2)
    d_e = ops.to_device_ell(ell2)
    x2 = rng.standard_normal(ms.shape[0]).astype(np.float32)
    xp2 = jnp.asarray(pj2.permute(x2))
    xe2 = jnp.asarray(np.resize(x2, ell2.n_rows_pad))
    t_p = time_fn(jax.jit(lambda v: ops.pjds_matvec(d_p, v)), xp2)
    t_e = time_fn(jax.jit(lambda v: ops.ell_matvec(d_e, v)), xe2)
    stored_ratio = F.storage_elements(ell2) / F.storage_elements(pj2)
    rows.append(dict(kind="pjds_vs_ellr", speedup=t_e / t_p,
                     stored_ratio=stored_ratio))
    if print_rows:
        print(csv_row("pjds_vs_ellr_samg", t_p * 1e6,
                      f"speedup={t_e/t_p:.2f}x stored_ratio={stored_ratio:.2f}x"))

    # --- SELL-C-sigma vs pJDS storage on the power-law matrix ----------
    # pJDS is SELL's sigma = n_rows special case, so the best swept SELL
    # overhead is structurally <= pJDS; the interesting number is how
    # small a window already gets close (bench_sell.py has the full sweep).
    mp = M.power_law(4096, seed=7)
    b_r = 128
    pj_p = F.csr_to_pjds(mp, b_r=b_r, permuted_cols=False)
    over_pjds = F.storage_elements(pj_p) / mp.nnz - 1
    n_pad = pj_p.n_rows_pad
    best_sigma, best_over = None, np.inf
    for sigma in (b_r, 4 * b_r, n_pad):
        sl = F.csr_to_sell(mp, c=b_r, sigma=sigma, permuted_cols=False)
        over = F.storage_elements(sl) / mp.nnz - 1
        rows.append(dict(kind="sell_powerlaw_storage", sigma=sigma,
                         overhead=over))
        if over < best_over:
            best_sigma, best_over = sigma, over
    rows.append(dict(kind="sell_vs_pjds_powerlaw", pjds_overhead=over_pjds,
                     sell_overhead_best=best_over, sell_sigma_best=best_sigma,
                     sell_le_pjds=bool(best_over <= over_pjds)))
    if print_rows:
        print(csv_row("sell_vs_pjds_powerlaw", 0.0,
                      f"pjds_overhead={100*over_pjds:.2f}% "
                      f"sell_best={100*best_over:.2f}%@sigma={best_sigma}"))

    write_bench_json("kernels", rows)
    return rows


if __name__ == "__main__":
    run()
