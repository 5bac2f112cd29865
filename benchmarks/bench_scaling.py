"""Paper Fig. 5: strong scaling of distributed spMVM, three comm modes.

Runs in a subprocess with 8 host devices (this process keeps 1 device)
and measures wall-time per spMVM for DLR1/UHBR analogues on 1/2/4/8
devices x {vector, naive, overlap}.  Host-CPU collectives through shared
memory are not an ICI fabric, so (as in the paper's own CPU-vs-GPU
caveats) the MODE-vs-MODE and scaling TRENDS are the comparable
quantities.  Alongside, the paper's performance model predicts the
strong-scaling curve for the TPU v5e target out to 32 chips: T(P) =
max(T_mvm/P, T_halo) for task mode, sum for vector mode (paper §3.1:
"the possible performance benefit can be at most a factor of two").

:func:`scaling_curves` additionally measures strong AND weak
parallel-efficiency curves across comm configs — bulk-synchronous
full-slice 1-D, gathered/overlap 1-D, and the 2-D grid — whose rows
``bench_dist`` folds into ``BENCH_dist.json`` (the scaling-trajectory
CI artifact)."""
from __future__ import annotations

import textwrap

from repro.core import perf_model as PM
from .common import csv_row, run_cpu_child

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import matrices as M, dist_spmv as D
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh

    out = []
    rng = np.random.default_rng(0)
    for name, scale in [("DLR1", 0.15), ("UHBR", 0.01)]:
        m = M.make_test_matrix(name, scale=scale)
        for n_dev in (1, 2, 4, 8):
            mesh = make_host_mesh(n_dev)
            dist = D.partition_csr(m, n_dev, b_r=128)
            x = np.zeros(dist.n_global_pad, np.float32)
            x[:m.n_rows] = rng.standard_normal(m.n_rows)
            xj = jax.device_put(jnp.asarray(x),
                                jax.NamedSharding(mesh, P("data")))
            for mode in ("vector", "naive", "overlap"):
                mv = jax.jit(dist_operator(dist, mesh, mode=mode).matvec)
                for _ in range(3):
                    jax.block_until_ready(mv(xj))
                ts = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    jax.block_until_ready(mv(xj))
                    ts.append(time.perf_counter() - t0)
                t = float(np.median(ts))
                out.append(dict(matrix=name, n_dev=n_dev, mode=mode,
                                t_us=t * 1e6,
                                gfs=2 * m.nnz / t / 1e9,
                                halo_w=dist.halo_w, nnz=int(m.nnz)))
    print("RESULTS " + json.dumps(out))
""")


def _measured():
    rows, platform = run_cpu_child(_SCRIPT)
    for r in rows:
        r["platform"] = platform
    return rows


_CURVES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import formats as F, dist_spmv as D
    from repro.core.operator import dist_operator
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(0)

    def banded(n, reach, stride=8):
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

    def square_grid(p):
        g = max(d for d in range(1, int(np.sqrt(p)) + 1) if p % d == 0)
        return None if g == 1 else (g, p // g)

    def measure(m, n_dev, grid, halo, mode):
        mesh = make_host_mesh(n_dev)
        dist = D.partition_csr(m, n_dev, b_r=128, grid=grid)
        x = np.zeros(dist.n_global_pad, np.float32)
        x[:m.n_rows] = rng.standard_normal(m.n_rows)
        xj = jax.device_put(jnp.asarray(x),
                            jax.NamedSharding(mesh, P("data")))
        mv = jax.jit(dist_operator(dist, mesh, mode=mode, halo=halo).matvec)
        return timed(mv, xj), dist

    out = []
    b_r = 128
    configs = [("bulk_full_1d", "full", "vector", False),
               ("gathered_overlap_1d", "gathered", "overlap", False),
               ("gathered_overlap_2d", "gathered", "overlap", True)]

    # strong scaling: fixed problem, growing mesh
    n_strong = 8 * b_r * 2
    m_strong = banded(n_strong, reach=384)
    base = {}
    for label, halo, mode, use2d in configs:
        for p in (1, 2, 4, 8):
            grid = square_grid(p) if use2d else None
            if use2d and grid is None and p > 1:
                continue                   # 2-D needs a composite mesh
            t, dist = measure(m_strong, p, grid, halo, mode)
            if p == 1:
                base[label] = t
            out.append(dict(kind="strong_scaling", config=label, n_dev=p,
                            grid=grid, halo=halo, mode=mode, t_us=t * 1e6,
                            halo_w=int(dist.halo_w),
                            efficiency=base[label] / (p * t)))

    # weak scaling: constant rows/device, growing mesh AND problem
    n_base = b_r * 2
    for label, halo, mode, use2d in configs:
        for p in (1, 2, 4, 8):
            grid = square_grid(p) if use2d else None
            if use2d and grid is None and p > 1:
                continue
            m = banded(n_base * p, reach=min(384, n_base * p // 2))
            t, dist = measure(m, p, grid, halo, mode)
            if p == 1:
                base[label] = t
            out.append(dict(kind="weak_scaling", config=label, n_dev=p,
                            grid=grid, halo=halo, mode=mode, t_us=t * 1e6,
                            halo_w=int(dist.halo_w),
                            efficiency=base[label] / t))
    print("RESULTS " + json.dumps(out))
""")


def scaling_curves(print_rows=True):
    """Measured strong/weak parallel-efficiency rows (see module
    docstring); consumed by ``bench_dist`` into ``BENCH_dist.json``."""
    rows, platform = run_cpu_child(_CURVES_SCRIPT)
    for r in rows:
        r["platform"] = platform
    if print_rows:
        for row in rows:
            print(csv_row(
                f"{row['kind']}_{row['config']}_p{row['n_dev']}",
                row["t_us"], f"eff={row['efficiency']:.2f} "
                f"halo_w={row['halo_w']}"))
    return rows


def _model_curve(n_rows, n_nzr, chips=(1, 2, 4, 8, 16, 32)):
    """TPU v5e predicted strong scaling (DP), task vs vector mode."""
    spec = PM.TPU_V5E
    rows = []
    for p in chips:
        t_mvm = PM.t_mvm(n_rows / p, n_nzr, alpha=1 / n_nzr,
                         dev_bw=spec.hbm_bw)
        t_halo = PM.t_link(n_rows / p, spec.ici_bw)  # halo ~ slice-sized
        task = max(t_mvm, t_halo)
        vector = t_mvm + t_halo
        rows.append(dict(chips=p,
                         task_gfs=2 * n_rows * n_nzr / task / 1e9,
                         vector_gfs=2 * n_rows * n_nzr / vector / 1e9))
    return rows


def run(print_rows=True):
    rows = {"measured": _measured(),
            "model_dlr1": _model_curve(280_000, 144),
            "model_uhbr": _model_curve(4_500_000, 123)}
    if print_rows:
        for r in rows["measured"]:
            print(csv_row(
                f"fig5_{r['matrix']}_p{r['n_dev']}_{r['mode']}",
                r["t_us"], f"{r['gfs']:.2f}GF/s halo_w={r['halo_w']}"))
        for key in ("model_dlr1", "model_uhbr"):
            for r in rows[key]:
                print(csv_row(
                    f"fig5_model_{key[6:]}_p{r['chips']}", 0.0,
                    f"task={r['task_gfs']:.0f}GF/s "
                    f"vector={r['vector_gfs']:.0f}GF/s"))
    return rows


if __name__ == "__main__":
    run()
