"""Paper Table 1: pJDS data reduction vs ELLPACK + spMVM performance.

For each of the five test-matrix analogues (HMEp, sAMG, DLR1, DLR2,
UHBR):
* data reduction of pJDS vs ELLPACK (the paper's memory column; paper
  measured 19-71%),
* measured spMVM wall-time of the jitted pJDS and ELLPACK-R operators on
  THIS host (CPU, so absolute GF/s are not Fermi numbers; the
  FORMAT-vs-FORMAT ratio is the comparable quantity),
* model-predicted TPU v5e GF/s from the paper's code balance (Eq. 1) at
  both alpha bounds — the number the roofline analysis targets.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import formats as F, matrices as M, perf_model as PM
from repro.kernels import ops
from .common import time_fn, csv_row

SCALES = {"HMEp": 0.004, "sAMG": 0.007, "DLR1": 0.08, "DLR2": 0.04,
          "UHBR": 0.005}


def run(print_rows=True):
    rows = []
    for name, scale in SCALES.items():
        m = M.make_test_matrix(name, scale=scale)
        n = m.shape[0]
        red = F.data_reduction_vs_ellpack(m, b_r=128)

        pj = F.csr_to_pjds(m, b_r=128)
        pdev = ops.to_device_pjds(pj)
        ell = F.csr_to_ell(m, row_align=128)
        edev = ops.to_device_ell(ell)
        rng = np.random.default_rng(0)
        xp = jnp.asarray(pj.permute(rng.standard_normal(n).astype(np.float32)))
        xe = jnp.asarray(np.resize(np.asarray(xp), ell.n_rows_pad))

        import jax
        f_p = jax.jit(lambda x: ops.pjds_matvec(pdev, x))
        f_e = jax.jit(lambda x: ops.ell_matvec(edev, x))
        t_p = time_fn(f_p, xp)
        t_e = time_fn(f_e, xe)
        gf_p = 2 * m.nnz / t_p / 1e9
        gf_e = 2 * m.nnz / t_e / 1e9

        # model-predicted TPU v5e spMVM GF/s (DP) at the two alpha bounds
        lo_a, hi_a = PM.alpha_range(m.n_nzr)
        gf_best = PM.TPU_V5E.hbm_bw / PM.code_balance(lo_a, m.n_nzr) / 1e9
        gf_worst = PM.TPU_V5E.hbm_bw / PM.code_balance(hi_a, m.n_nzr) / 1e9

        rows.append(dict(
            name=name, n=n, nnz=m.nnz, n_nzr=round(m.n_nzr, 1),
            reduction_pct=round(100 * red, 1),
            cpu_pjds_gfs=round(gf_p, 3), cpu_ellr_gfs=round(gf_e, 3),
            pjds_vs_ellr=round(gf_p / gf_e, 2),
            tpu_pred_gfs_best=round(gf_best, 1),
            tpu_pred_gfs_worst=round(gf_worst, 1),
            us_per_call=t_p * 1e6,
        ))
        if print_rows:
            r = rows[-1]
            print(csv_row(
                f"table1_{name}", r["us_per_call"],
                f"reduction={r['reduction_pct']}% "
                f"pjds/ellr={r['pjds_vs_ellr']} "
                f"tpu_pred={r['tpu_pred_gfs_worst']}-{r['tpu_pred_gfs_best']}GF/s"))
    return rows


# ---------------------------------------------------------------------------
# Corpus format sweep -> BENCH_formats.json  (``run.py --only formats``)
# ---------------------------------------------------------------------------

FORMATS = ("csr", "ellpack_r", "pjds", "sell", "cmrs")
MAX_DISPATCH_LOSS = 1.05    # dispatch may never pick a measured >5% loser
MAX_REORDER_LOSS = 1.05     # reorder="auto" may never lose >5% wall time


def _interleaved_times(fns: dict, rounds: int = 5, iters: int = 3,
                       warmup: int = 2) -> dict:
    """Min-of-round-medians for N prepared candidates, all sides
    interleaved inside every round (the ``tune.measure.ab_compare``
    drift story, generalized from 2 sides to N)."""
    import jax
    from repro.tune.measure import median_seconds
    for f in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(f())
    best = {k: float("inf") for k in fns}
    keys = list(fns)
    for r in range(rounds):
        order = keys if r % 2 == 0 else keys[::-1]
        for k in order:
            best[k] = min(best[k], median_seconds(fns[k], warmup=0,
                                                  iters=iters))
    return best


def run_corpus(print_rows=True):
    """Format win-rate table over the on-disk ``.mtx`` corpus, with
    three REGRESSION GUARDS (SystemExit -> the tier-2 CI step fails):

    * the corpus round-trips losslessly through ``io_mm`` (generation
      itself re-reads every file via ``load_mm``);
    * dispatch never picks a measured >5% loser among the alternatives
      it considered: the MEASURED dispatch path (``tune="auto"``, a
      fresh cache) is re-timed inside the same interleaved sweep as the
      static pick it replaces and may not lose >5% to it (the tuner's
      prune keeps the heuristic in the measured set, so this can only
      fail by noise or a real dispatch bug); the full-sweep-best guard
      for the static pick runs only when the measurement backend is the
      compiled kernel (TPU) — the pricing targets that hardware, so on
      the ref backend the per-format times are recorded in the rows
      (the win-rate table) but the model pick is not guarded against
      them;
    * ``reorder="auto"`` never loses wall time to ``reorder="off"`` on
      the shuffled banded matrix.  Single-device the model must DECLINE
      the permutation (guarded), which makes the two builds
      bit-identical — asserted on the stored streams, which implies
      equal wall time without timing two identical jitted programs
      against each other (their measured delta is pure harness noise
      at ~20us/call).  The >5% timed guard runs only when a
      permutation was actually applied (TPU-scale meshes).  The
      RCM-permuted banded partition additionally must ship no more
      halo bytes per device than the unreordered one (deterministic,
      host-side).
    """
    import pathlib
    import tempfile

    import jax
    from benchmarks import corpus
    from repro import tune as T
    from repro.core import dist_spmv as D
    from repro.core.reorder import preprocess
    from repro.tune.measure import measurement_backend
    from .common import write_bench_json

    rows = []
    cache = T.TuneCache(
        pathlib.Path(tempfile.mkdtemp(prefix="bench_formats_")) / "c.json")
    mats = corpus.load()                 # lossless-round-trip guard inside
    for name, m in mats.items():
        orig = corpus.make(name)
        if not (np.array_equal(m.data, orig.data)
                and np.array_equal(m.indices, orig.indices)
                and np.array_equal(m.indptr, orig.indptr)):
            raise SystemExit(
                f"REGRESSION: corpus .mtx round-trip lossy for {name!r}")

        backend = measurement_backend()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(m.shape[1]).astype(np.float32))
        tuned = T.autotune(m, cache=cache, warmup=2, iters=5).best
        fns = {}
        for fmt in FORMATS:
            sd = ops.as_device(m, fmt)
            fns[fmt] = (lambda f, v: (lambda: f(v)))(
                jax.jit(lambda v, s=sd: s.matvec(v, backend=backend)), x)
        sd_t = ops.as_device(m, **tuned.build_kwargs())
        fns["tuned"] = (lambda f, v: (lambda: f(v)))(
            jax.jit(lambda v, s=sd_t: s.matvec(v, backend=backend)), x)
        pick = ops.select_format(m, diag_align=16)
        times = _interleaved_times(fns)
        fmt_times = {k: v for k, v in times.items() if k != "tuned"}
        best_fmt = min(fmt_times, key=fmt_times.get)
        if times["tuned"] > MAX_DISPATCH_LOSS * fmt_times[pick]:
            raise SystemExit(
                f"REGRESSION: measured dispatch (tuned={tuned.label()}) on "
                f"{name!r} is a "
                f"{times['tuned'] / fmt_times[pick]:.2f}x loser vs the "
                f"static pick {pick!r} (guard: {MAX_DISPATCH_LOSS}x)")
        if backend == "kernel" and \
                fmt_times[pick] > MAX_DISPATCH_LOSS * fmt_times[best_fmt]:
            raise SystemExit(
                f"REGRESSION: static dispatch picked {pick!r} on {name!r} "
                f"but {best_fmt!r} measured "
                f"{fmt_times[pick] / fmt_times[best_fmt]:.2f}x faster "
                f"(guard: {MAX_DISPATCH_LOSS}x)")
        row = dict(name=name, n=m.shape[0], nnz=m.nnz, pick=pick,
                   tuned=tuned.label(), measured_best=best_fmt,
                   us_per_call=times["tuned"] * 1e6,
                   **{f"us_{f}": round(t * 1e6, 2) for f, t in times.items()})
        rows.append(row)
        if print_rows:
            print(csv_row(f"formats_{name}", row["us_per_call"],
                          f"pick={pick} measured_best={best_fmt} "
                          f"tuned={tuned.fmt}"))

    # -- reorder guards on the shuffled banded matrix ----------------------
    mb = mats["banded"]
    # Single-device there is no halo to save, only the permute sandwich
    # to pay: the calibrated model must DECLINE (the acceptance
    # criterion that reorder="auto" only applies on a predicted win).
    pp1 = preprocess(mb, reorder="auto", value_bytes=4)
    if pp1.applied:
        raise SystemExit(
            f"REGRESSION: reorder='auto' applied RCM single-device on the "
            f"banded matrix ({pp1.reason}) — no halo exists to pay for "
            f"the permute sandwich")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(mb.shape[1]).astype(np.float32))
    backend = measurement_backend()
    sd_off = ops.as_device(mb, reorder="off")
    sd_auto = ops.as_device(mb, reorder="auto")
    fns = {}
    for tag, sd in (("off", sd_off), ("auto", sd_auto)):
        fns[tag] = (lambda f, v: (lambda: f(v)))(
            jax.jit(lambda v, s=sd: s.matvec(v, backend=backend)), x)
    t = _interleaved_times(fns)
    if sd_auto.pre_perm is None:
        # Declined -> the builds must be bit-identical (equal wall time
        # by construction; timing two identical programs only measures
        # harness noise).
        if sd_auto.fmt != sd_off.fmt or not all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(sd_auto.dev),
                                jax.tree.leaves(sd_off.dev))):
            raise SystemExit(
                "REGRESSION: reorder='auto' declined the permutation but "
                "built a different device operand than reorder='off'")
    elif t["auto"] > MAX_REORDER_LOSS * t["off"]:
        raise SystemExit(
            f"REGRESSION: reorder='auto' lost "
            f"{t['auto'] / t['off']:.2f}x vs 'off' on the banded matrix "
            f"(guard: {MAX_REORDER_LOSS}x) — the pricing model applied a "
            f"losing permutation")

    pp = preprocess(mb, reorder="rcm")
    n_dev = 8
    cb_off = D.partition_csr(mb, n_dev).comm_bytes_per_device(value_bytes=4)
    cb_on = D.partition_csr(pp.matrix, n_dev).comm_bytes_per_device(
        value_bytes=4)
    if cb_on > cb_off:
        raise SystemExit(
            f"REGRESSION: RCM-reordered banded partition ships MORE halo "
            f"bytes ({cb_on} > {cb_off}) at {n_dev} devices")
    rows.append(dict(name="banded_reorder", us_per_call=t["auto"] * 1e6,
                     us_off=round(t["off"] * 1e6, 2),
                     us_auto=round(t["auto"] * 1e6, 2),
                     bw_before=pp.bandwidth_before, bw_after=pp.bandwidth_after,
                     comm_bytes_off=cb_off, comm_bytes_on=cb_on))
    if print_rows:
        print(csv_row("formats_banded_reorder", t["auto"] * 1e6,
                      f"auto/off={t['auto'] / t['off']:.3f} "
                      f"bw={pp.bandwidth_before}->{pp.bandwidth_after} "
                      f"comm={cb_off}->{cb_on}B"))

    write_bench_json("formats", rows)
    return rows


if __name__ == "__main__":
    run()
    run_corpus()
