"""Distributed halo-exchange benchmark: gathered vs full-slice comm,
1-D vs 2-D grids, bulk-synchronous vs overlapped vs pipelined, and the
calibrated ``halo="auto"`` crossover — plus single vs multi-RHS.

Sweeps two banded boundary-coupled test matrices (halo_w = 2 and
halo_w = 1 — both sides of the gathered-vs-full crossover the paper's
Eq. 3-4 link model prices) over communication mode x halo
implementation x device-grid shape on 8 virtual host devices
(subprocess, this process keeps one device), recording per-device wire
statistics (bytes AND messages) next to wall-clock.  Also times k=4
``dist_matmat`` against 4 sequential ``dist_matvec`` calls — the
multi-RHS amortisation of the streamed matrix and the halo set-up.

Host-CPU collectives through shared memory are not an ICI fabric, so
(as with bench_scaling) the gathered-vs-full and mode-vs-mode RATIOS
are the comparable quantities; the comm_bytes/comm_msgs columns are
exact.  That is exactly why the sweep also FITS the link calibration
(``tune.calibrate.fit_link_calibration``) from its own rows: the
per-message fixed cost is a property of whatever fabric ran the
benchmark, and the calibrated model must agree with it.

Two hard guards (SystemExit — CI fails loudly, not quietly):

* ``halo="auto"`` (``perf_model.choose_halo`` under the fitted link
  calibration) must pick the MEASURED gathered-vs-full winner on both
  bench matrices — the calibrated crossover never selects a measured
  loser.
* the best overlapped config (overlap/pipeline, any grid) must beat
  the best bulk-synchronous 1-D config at the largest emulated mesh —
  the explicit dependency structure has to pay for itself.

Writes ``BENCH_dist.json`` (CI artifact), including the strong/weak
scaling-efficiency curves from ``bench_scaling.scaling_curves``.
"""
from __future__ import annotations

import textwrap

from .common import csv_row, run_cpu_child, write_bench_json

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import formats as F, dist_spmv as D
    from repro.core import perf_model as PM
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh
    from repro.tune import fit_link_calibration, link_model_error

    n_dev = 8
    mesh = make_host_mesh(n_dev)
    rng = np.random.default_rng(0)

    def banded(n, reach, stride=8):
        # tridiagonal band + sparse long-range coupling: the gathered
        # halo's winning regime (few scattered remote columns)
        a = np.zeros((n, n), np.float32)
        i = np.arange(n)
        a[i, i] = 4.0
        a[i[:-1], i[:-1] + 1] = -1.0
        a[i[1:], i[1:] - 1] = -1.0
        far = i[::stride]
        for sgn in (+1, -1):
            tgt = far + sgn * reach
            ok = (tgt >= 0) & (tgt < n)
            a[far[ok], tgt[ok]] = -0.5
        return F.csr_from_dense(a)

    def timed(fn, arg, warmup=3, iters=10):
        for _ in range(warmup):
            jax.block_until_ready(fn(arg))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    b_r = 128
    n = 8 * b_r * 2                       # n_loc = 256 on the 1-D grid
    shard = jax.NamedSharding(mesh, P("data"))
    shard2 = jax.NamedSharding(mesh, P("data", None))

    # reach384: n_loc < reach < 2*n_loc  -> halo_w=2, sparse coupling
    # reach96:  reach < n_loc            -> halo_w=1, denser coupling
    mats = [("reach384", banded(n, reach=384)),
            ("reach96", banded(n, reach=96, stride=2))]

    out = {"rows": []}

    def sweep(name, m, grid, halos_modes, k=1):
        dist = D.partition_csr(m, n_dev, b_r=b_r, grid=grid)
        X = rng.standard_normal((dist.n_global_pad, k)).astype(np.float32)
        for halo, mode in halos_modes:
            op = dist_operator(dist, mesh, mode=mode, halo=halo)
            if k == 1:
                f = jax.jit(op.matvec)
                arg = jax.device_put(jnp.asarray(X[:, 0]), shard)
            else:
                f = jax.jit(op.matmat)
                arg = jax.device_put(jnp.asarray(X), shard2)
            t = timed(f, arg)
            out["rows"].append(dict(
                kind="sweep", matrix=name, grid=grid, halo=halo, mode=mode,
                k=k, t_us=t * 1e6,
                halo_w=int(dist.halo_w), red_w=int(dist.red_w),
                comm_bytes=int(dist.comm_bytes_per_device(4, k, halo)),
                comm_msgs=int(dist.comm_msgs_per_device(halo)),
                group=f"{name}/{grid}/{mode}/k{k}",
                gfs=2 * m.nnz * k / t / 1e9))
        return dist

    m1 = mats[0][1]
    d1 = sweep("reach384", m1, None,
               [(h, mo) for h in ("gathered", "full")
                for mo in ("vector", "naive", "overlap")]
               + [("gathered", "pipeline")])
    out["halo_w"] = int(d1.halo_w)
    out["halo_lens"] = list(d1.halo_lens)
    out["n_loc"] = int(d1.n_loc)
    out["nnz"] = int(m1.nnz)
    for grid in ((2, 4), (1, 8)):
        sweep("reach384", m1, grid,
              [(h, mo) for h in ("gathered", "full")
               for mo in ("vector", "overlap")]
              + [("gathered", "pipeline")])
    sweep("reach384", m1, None,
          [(h, mo) for h in ("gathered", "full")
           for mo in ("vector", "overlap")], k=4)
    sweep("reach96", mats[1][1], None,
          [(h, mo) for h in ("gathered", "full")
           for mo in ("vector", "overlap")])

    # -- drift-robust paired timing (tune.measure.ab_compare style):
    # alternate the two sides round by round and keep each side's
    # minimum round median, so slow host drift lands on both sides and
    # the min discards the inflated rounds.  The guards compare PAIRED
    # numbers, never two one-sided sweep rows.
    def paired(f_a, arg_a, f_b, arg_b, rounds=5, iters=5):
        for f, a in ((f_a, arg_a), (f_b, arg_b)):
            for _ in range(2):
                jax.block_until_ready(f(a))
        t_a = t_b = float("inf")
        for r in range(rounds):
            order = (((0, f_a, arg_a), (1, f_b, arg_b)) if r % 2 == 0
                     else ((1, f_b, arg_b), (0, f_a, arg_a)))
            for side, f, a in order:
                ts = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(a))
                    ts.append(time.perf_counter() - t0)
                t = float(np.median(ts))
                if side == 0:
                    t_a = min(t_a, t)
                else:
                    t_b = min(t_b, t)
        return t_a, t_b

    # -- link calibration from PAIRED bulk-synchronous measurements ----
    # Only vector mode: bulk-synchronous time is base + comm (additive),
    # so the wire terms are identifiable; overlapped time hides comm
    # under compute (max), which a fit cannot invert.  Each (matrix,
    # grid) group is measured as an interleaved gathered-vs-full pair,
    # so the two rows a group's base must explain sat under the same
    # host drift — the fit sees the same data the guard judges by.
    sweep_rows = [r for r in out["rows"] if r["kind"] == "sweep"]
    fit_in = []
    pair_t = {}
    for name, m in mats:
        grids = (None, (2, 4), (1, 8)) if name == "reach384" else (None,)
        for grid in grids:
            dist = D.partition_csr(m, n_dev, b_r=b_r, grid=grid)
            x1 = jax.device_put(jnp.asarray(
                rng.standard_normal(dist.n_global_pad).astype(np.float32)),
                shard)
            f_g = jax.jit(dist_operator(dist, mesh, mode="vector",
                                        halo="gathered").matvec)
            f_f = jax.jit(dist_operator(dist, mesh, mode="vector",
                                        halo="full").matvec)
            t_g, t_f = paired(f_g, x1, f_f, x1)
            if grid is None:
                pair_t[name] = (t_g, t_f)
            for halo, t in (("gathered", t_g), ("full", t_f)):
                fit_in.append(dict(
                    group=f"{name}/{grid}", halo=halo,
                    msgs=int(dist.comm_msgs_per_device(halo)),
                    bytes=int(dist.comm_bytes_per_device(4, 1, halo)),
                    measured_s=t))
    cal = fit_link_calibration(fit_in, source="bench_dist")
    out["rows"].append(dict(
        kind="link_calibration",
        msg_overhead_us={h: v * 1e6 for h, v in cal.msg_overhead_s.items()},
        link_bw_scale=cal.link_bw_scale,
        err_uncal=link_model_error(fit_in, None),
        err_cal=link_model_error(fit_in, cal)))

    # -- guard 1: calibrated halo="auto" vs the paired measured winner -
    for name, m in mats:
        dist = D.partition_csr(m, n_dev, b_r=b_r)
        pick = PM.choose_halo(dist, mode="vector", value_bytes=4,
                              calibration=cal)
        t_g, t_f = pair_t[name]
        winner = "gathered" if t_g < t_f else "full"
        # a sub-5% gap is a tie at host-collective noise levels: either
        # pick is defensible, so the guard only fires on a CLEAR loser
        tie = abs(t_g - t_f) <= 0.05 * min(t_g, t_f)
        out["rows"].append(dict(
            kind="halo_auto", matrix=name, picked=pick, measured=winner,
            agree=bool(pick == winner or tie),
            t_gathered_us=t_g * 1e6, t_full_us=t_f * 1e6))

    # -- guard 2: overlapped vs bulk-synchronous at the full mesh ------
    k1 = [r for r in sweep_rows
          if r["matrix"] == "reach384" and r["k"] == 1]
    best_ov = min((r for r in k1 if r["mode"] in ("overlap", "pipeline")),
                  key=lambda r: r["t_us"])
    best_bs = min((r for r in k1 if r["mode"] == "vector"
                   and r["grid"] is None), key=lambda r: r["t_us"])
    d_ov = D.partition_csr(m1, n_dev, b_r=b_r, grid=best_ov["grid"])
    x_ov = jax.device_put(jnp.asarray(
        rng.standard_normal(d_ov.n_global_pad).astype(np.float32)), shard)
    f_ov = jax.jit(dist_operator(d_ov, mesh, mode=best_ov["mode"],
                                 halo=best_ov["halo"]).matvec)
    f_bs = jax.jit(dist_operator(d1, mesh, mode="vector",
                                 halo=best_bs["halo"]).matvec)
    t_ov, t_bs = paired(f_ov, x_ov, f_bs, x_ov)
    out["rows"].append(dict(
        kind="overlap_guard",
        best_overlapped=dict(grid=best_ov["grid"], halo=best_ov["halo"],
                             mode=best_ov["mode"], t_us=t_ov * 1e6),
        best_bulk_1d=dict(halo=best_bs["halo"], t_us=t_bs * 1e6),
        ok=bool(t_ov < t_bs)))

    # k=4 spMM vs 4 sequential spMVMs (overlap mode, gathered halo)
    dist = d1
    X4 = rng.standard_normal((dist.n_global_pad, 4)).astype(np.float32)
    op = dist_operator(dist, mesh, mode="overlap")
    mm = jax.jit(op.matmat)
    arg4 = jax.device_put(jnp.asarray(X4), shard2)
    t_mm = timed(mm, arg4)
    mv = jax.jit(op.matvec)
    cols = [jax.device_put(jnp.asarray(X4[:, j]), shard) for j in range(4)]
    for c in cols:
        jax.block_until_ready(mv(c))
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        for c in cols:
            jax.block_until_ready(mv(c))
        ts.append(time.perf_counter() - t0)
    t_seq = float(np.median(ts))
    out["rows"].append(dict(kind="matmat_vs_seq", t_matmat_us=t_mm * 1e6,
                            t_seq4_us=t_seq * 1e6,
                            speedup=t_seq / t_mm))
    print("RESULTS " + json.dumps(out))
""")


def _measured():
    res, platform = run_cpu_child(_SCRIPT)
    for r in res["rows"]:
        r["platform"] = platform
    return res


def run(print_rows=True):
    from . import bench_scaling

    res = _measured()
    rows = res["rows"]
    meta = dict(kind="meta", halo_w=res["halo_w"],
                halo_lens=res["halo_lens"], n_loc=res["n_loc"],
                nnz=res["nnz"])
    if print_rows:
        for r in rows:
            if r["kind"] == "sweep":
                g = "x".join(map(str, r["grid"])) if r["grid"] else "1d"
                print(csv_row(
                    f"dist_{r['matrix']}_{g}_{r['halo']}_{r['mode']}"
                    f"_k{r['k']}", r["t_us"],
                    f"comm={r['comm_bytes']}B/{r['comm_msgs']}msg/dev "
                    f"{r['gfs']:.2f}GF/s"))
            elif r["kind"] == "link_calibration":
                ov = " ".join(f"{h}={v:.1f}us"
                              for h, v in r["msg_overhead_us"].items())
                print(csv_row("dist_link_calibration", 0.0,
                              f"msg_cost[{ov}] rel_err "
                              f"{r['err_uncal']:.3f}->{r['err_cal']:.3f}"))
            elif r["kind"] == "halo_auto":
                print(csv_row(f"dist_halo_auto_{r['matrix']}", 0.0,
                              f"picked={r['picked']} measured={r['measured']}"
                              f" agree={r['agree']}"))
            elif r["kind"] == "overlap_guard":
                b, s = r["best_overlapped"], r["best_bulk_1d"]
                g = "x".join(map(str, b["grid"])) if b["grid"] else "1d"
                print(csv_row(
                    "dist_overlap_guard", b["t_us"],
                    f"{g}/{b['halo']}/{b['mode']} vs bulk-1d/{s['halo']}="
                    f"{s['t_us']:.1f}us ok={r['ok']}"))
            elif r["kind"] == "matmat_vs_seq":
                print(csv_row("dist_matmat4_vs_4matvec", r["t_matmat_us"],
                              f"seq4={r['t_seq4_us']:.1f}us "
                              f"speedup={r['speedup']:.2f}x"))
        g = next(r for r in rows
                 if r["kind"] == "sweep" and r["halo"] == "gathered")
        f = next(r for r in rows
                 if r["kind"] == "sweep" and r["halo"] == "full")
        print(csv_row("dist_comm_reduction", 0.0,
                      f"{f['comm_bytes'] / max(g['comm_bytes'], 1):.1f}x "
                      f"less halo traffic (halo_w={res['halo_w']})"))

    scaling = bench_scaling.scaling_curves(print_rows=print_rows)
    write_bench_json("dist", [meta] + rows + scaling)

    bad = [r for r in rows if r["kind"] == "halo_auto" and not r["agree"]]
    if bad:
        raise SystemExit(
            "halo='auto' picked a measured loser on "
            + ", ".join(r["matrix"] for r in bad)
            + " — the fitted link calibration disagrees with the "
            "measured gathered-vs-full winner")
    guard = next(r for r in rows if r["kind"] == "overlap_guard")
    if not guard["ok"]:
        raise SystemExit(
            f"no overlapped config beat the bulk-synchronous 1-D baseline "
            f"at the full mesh: best overlapped "
            f"{guard['best_overlapped']} vs {guard['best_bulk_1d']}")
    return rows


if __name__ == "__main__":
    run()
