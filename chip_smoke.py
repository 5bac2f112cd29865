#!/usr/bin/env python3
"""Drive the main path once on a TPU, at the sAMG matrix's published size.

    python chip_smoke.py              # one chip: spMVM in four formats,
                                      # then CG through repro.solve
    python chip_smoke.py --chips 4    # the distributed operator on a
                                      # 4-chip mesh, and nothing else

sAMG (paper §1.3: 3.4M rows, ~7 non-zeros per row) is generated from a
seed.  Every result is checked on the host against a plain numpy float64
CSR product of the same matrix.  The script exits non-zero when JAX
finds no TPU; ``--cpu-rehearsal`` runs the same phases on the CPU at
``REHEARSAL_SCALE`` of that size, with the Pallas kernels interpreted,
to find wrong paths before a chip run.  On a TPU the matrix is always
the published size.  The last line of standard output
is one JSON object naming the device, printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# f32 spMVM against the float64 host product, relative to max |y|: each
# row sums <= 32 f32 products, so the error sits near 1e-7.
MATVEC_TOL = 1e-5
SOLVE_TOL = 1e-6            # repro.solve's default tolerance
FORMATS = ("auto", "sell", "pjds", "ellpack_r")
SEED = 0
KERNEL_FORMATS = ("ellpack_r", "pjds", "sell", "cmrs")
REHEARSAL_SCALE = 0.002     # ~6.8k rows: interpreted kernels stay quick


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


class HostCSR:
    """The numpy float64 reference: y = A x straight off the CSR arrays."""

    def __init__(self, m):
        self.m = m
        self.rows = np.repeat(np.arange(m.n_rows), np.diff(m.indptr))
        self.data = m.data.astype(np.float64)

    def matvec(self, x):
        w = self.data * np.asarray(x, np.float64)[self.m.indices]
        return np.bincount(self.rows, weights=w, minlength=self.m.n_rows)

    def rel_err(self, y, y_ref) -> float:
        y = np.asarray(y, np.float64)[: self.m.n_rows]
        return float(np.abs(y - y_ref).max()
                     / max(np.abs(y_ref).max(), 1e-30))

    def true_residual(self, b, x) -> float:
        b = np.asarray(b, np.float64)
        r = b - self.matvec(np.asarray(x, np.float64)[: self.m.n_rows])
        return float(np.linalg.norm(r) / np.linalg.norm(b))


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def phase_matvec(m, ref, x, backend, on_chip):
    import jax
    import jax.numpy as jnp
    from repro.core.operator import operator
    from repro.kernels import ops

    y_ref = ref.matvec(x)
    xj = jnp.asarray(x)
    for fmt in FORMATS:
        t0 = time.perf_counter()
        op = operator(m, format=fmt, backend=backend)
        jax.block_until_ready(op.dev.dev)
        build_s = time.perf_counter() - t0
        resolved = ops.resolve_backend(op.backend)
        prog = jax.jit(lambda op, v: op @ v)
        in_program = "tpu_custom_call" in prog.lower(op, xj).as_text()
        y, first_s = timed(prog, op, xj)
        _, warm_s = timed(prog, op, xj)
        err = ref.rel_err(y, y_ref)
        slots = op.dev.stored_slots
        log(f"matvec format={fmt:9s} -> {op.fmt:9s} backend={resolved} "
            f"interpret={ops.resolve_interpret(None)} "
            f"kernel_in_program={in_program} build_s={build_s:.3f} "
            f"first_call_s={first_s:.3f} warm_call_s={warm_s:.6f} "
            f"stored_slots_per_nnz={slots / m.nnz:.4f} "
            f"value={op.dev.value_dtype} index={op.dev.index_dtype} "
            f"max_rel_err={err:.3e} (tol {MATVEC_TOL:g})")
        check(err <= MATVEC_TOL, f"{fmt}: matvec error {err:.3e}")
        if on_chip:
            check(resolved == "kernel", f"{fmt}: backend {resolved}")
            check(ops.resolve_interpret(None) is False, "interpret mode on")
            check(in_program, f"{fmt}: no Pallas kernel in the program")
        del op, prog
        ops.clear_device_cache()


def phase_solve(m, ref, b, backend, on_chip):
    import repro
    from repro.kernels import ops

    for label, kw in (("f32", {}), ("bf16-refined", {"dtype": "bfloat16"})):
        runs = []
        for call in ("first", "warm"):
            t0 = time.perf_counter()
            res = repro.solve(m, b, method="cg", fallback="off",
                              backend=backend, **kw)
            x = res.x.block_until_ready()
            wall = time.perf_counter() - t0
            runs.append((call, res, x, wall))
        for call, res, x, wall in runs:
            ladder = res.info.get("ladder", [{"rung": "primary"}])
            rn = ref.true_residual(b, x)
            ph = res.info["phase_s"]
            tune = res.info.get("tune", {})
            log(f"solve {label:12s} call={call:5s} status={res.status} "
                f"iters={int(res.iters)} strategy={res.info['strategy']} "
                f"layout={tune.get('layout')!r} "
                f"tune_cached={tune.get('cached')} "
                f"tune_s={ph.get('tune', 0.0):.3f} "
                f"build_s={ph.get('build', 0.0):.3f} "
                f"solve_s={ph['solve']:.3f} wall_s={wall:.3f} "
                f"rungs={[e['rung'] for e in ladder]} "
                f"certified={res.diagnostics.get('true_residual')} "
                f"host_true_residual={rn:.3e} (tol {SOLVE_TOL:g})")
            check(res.status == "converged", f"{label}: {res.status}")
            check(all(e["rung"] == "primary" for e in ladder),
                  f"{label}: ladder {ladder}")
            check(rn <= SOLVE_TOL, f"{label}: true residual {rn:.3e}")
            # Every tuned layout but csr runs a Pallas kernel; csr is
            # the XLA reference path alone.
            fmt = tune.get("layout", "").split(" ", 1)[0]
            check(fmt in KERNEL_FORMATS, f"{label}: layout {fmt!r} "
                  f"has no kernel")
            if on_chip:
                check(ops.resolve_backend(backend) == "kernel",
                      f"{label}: backend {ops.resolve_backend(backend)}")
                check(ops.resolve_interpret(None) is False,
                      "interpret mode on")
        first, warm = runs[0][1], runs[1][1]
        log(f"solve {label:12s} first-call compile ~= "
            f"{first.info['phase_s']['solve'] - warm.info['phase_s']['solve']:.3f}"
            f" s (first solve_s minus warm solve_s)")
        ops.clear_device_cache()


def phase_dist(m, ref, x, b, backend, n_chips, on_chip):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro
    from repro.core.operator import dist_operator, operator
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh

    y_ref = ref.matvec(x)
    mesh = make_host_mesh(n_chips)
    dev0 = jax.devices()[0]
    with jax.default_device(dev0):
        op1 = operator(m, backend=backend)
        y1 = np.asarray((op1 @ jnp.asarray(x)).block_until_ready(),
                        np.float64)
    log(f"single-device operator on {dev0}: format={op1.fmt} "
        f"max_rel_err_vs_numpy={ref.rel_err(y1, y_ref):.3e}")
    # dist_operator's default exchange mode is "overlap"; "vector" is the
    # bulk-synchronous baseline the paper compares it with; it reuses the
    # default operator's partition.
    op = None
    for label, kw in (("default", {}), ("vector", {"mode": "vector"})):
        t0 = time.perf_counter()
        op = dist_operator(m if op is None else op.dist, mesh,
                           backend=backend, **kw)
        build_s = time.perf_counter() - t0
        shards = op.dist.loc_val.addressable_shards
        placement = sorted((s.device.id, s.data.shape[0]) for s in shards)
        log(f"dist {label}: mode={op.mode} build_s={build_s:.3f} "
            f"n_loc={op.dist.n_loc} halo_w={op.dist.halo_w} "
            f"shards(device id, row slabs)={placement}")
        check(len({d for d, _ in placement}) == n_chips
              and all(k == 1 for _, k in placement),
              f"{label}: partition is not one row slab per chip")
        n_pad = op.shape[0]
        sh = NamedSharding(mesh, P("data"))
        xs = jax.device_put(jnp.asarray(np.pad(x, (0, n_pad - len(x)))), sh)
        prog = jax.jit(lambda op, v: op @ v)
        resolved = ops.resolve_backend(op.backend)
        in_program = "tpu_custom_call" in prog.lower(op, xs).as_text()
        log(f"dist {label}: backend={resolved} "
            f"interpret={ops.resolve_interpret(None)} "
            f"kernel_in_program={in_program}")
        if on_chip:
            check(resolved == "kernel", f"dist {label}: backend {resolved}")
            check(ops.resolve_interpret(None) is False, "interpret mode on")
            check(in_program, f"dist {label}: no Pallas kernel in the program")
        y, first_s = timed(prog, op, xs)
        _, warm_s = timed(prog, op, xs)
        y_devs = sorted(s.device.id for s in y.addressable_shards)
        err_np = ref.rel_err(y, y_ref)
        err_1 = ref.rel_err(y, y1)
        log(f"dist {label}: y shards on devices {y_devs} "
            f"first_call_s={first_s:.3f} warm_call_s={warm_s:.6f} "
            f"max_rel_err_vs_numpy={err_np:.3e} "
            f"max_rel_err_vs_single_device={err_1:.3e} (tol {MATVEC_TOL:g})")
        check(err_np <= MATVEC_TOL and err_1 <= MATVEC_TOL,
              f"dist {label}: matvec error")
        bs = jax.device_put(jnp.asarray(np.pad(b, (0, n_pad - len(b)))), sh)
        t0 = time.perf_counter()
        res = repro.solve(op, bs, method="cg", fallback="off")
        xr = res.x.block_until_ready()
        wall = time.perf_counter() - t0
        rn = ref.true_residual(b, xr)
        log(f"dist {label}: cg status={res.status} iters={int(res.iters)} "
            f"strategy={res.info['strategy']} wall_s={wall:.3f} "
            f"host_true_residual={rn:.3e} (tol {SOLVE_TOL:g})")
        check(res.status == "converged", f"dist {label}: {res.status}")
        check(rn <= SOLVE_TOL, f"dist {label}: true residual {rn:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed-operator phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a non-TPU backend, at REHEARSAL_SCALE "
                         "of the published size")
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.compile_cache import enable_compile_cache
        from repro.core import matrices as M
    except ImportError as e:
        return fail(f"cannot import the program next to this script: {e}")

    cache_dir = enable_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count_cache_event(event, **_):
        kind = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and kind in cache_events:
            cache_events[kind] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    devices = jax.devices()
    d0 = devices[0]
    on_chip = d0.platform == "tpu"
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if not on_chip and not args.cpu_rehearsal:
        return fail(f"no TPU: JAX found {d0.platform!r}")
    if len(devices) < args.chips:
        return fail(f"--chips {args.chips} needs {args.chips} devices, "
                    f"found {len(devices)}")
    n_cached = len(list(pathlib.Path(cache_dir).glob("*"))) \
        if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({n_cached} entries at start)")
    # Tuning measures afresh in every run: its cache goes to a scratch
    # file that dies with the run.
    tmp = tempfile.TemporaryDirectory()
    os.environ.setdefault("REPRO_TUNE_CACHE",
                          os.path.join(tmp.name, "tune_cache.json"))
    backend = "auto" if on_chip else "kernel"

    scale = 1.0 if on_chip else REHEARSAL_SCALE
    t0 = time.perf_counter()
    m = M.samg(scale=scale)
    gen_s = time.perf_counter() - t0
    log(f"sAMG scale={scale}: rows={m.n_rows} nnz={m.nnz} "
        f"n_nzr={m.n_nzr:.3f} generate_s={gen_s:.3f}")
    ref = HostCSR(m)
    rng = np.random.default_rng(SEED)
    x = rng.standard_normal(m.n_rows).astype(np.float32)
    b = rng.standard_normal(m.n_rows).astype(np.float32)

    try:
        if args.chips == 4:
            phase_dist(m, ref, x, b, backend, args.chips, on_chip)
        else:
            phase_matvec(m, ref, x, backend, on_chip)
            phase_solve(m, ref, b, backend, on_chip)
    except AssertionError as e:
        return fail(str(e))
    finally:
        tmp.cleanup()
    n_cached = len(list(pathlib.Path(cache_dir).glob("*"))) \
        if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {n_cached} entries at end; this run read "
        f"{cache_events['cache_hits']} programs from it and compiled "
        f"{cache_events['cache_misses']} that were not there")
    if not on_chip:
        log("cpu rehearsal passed; no device result is printed")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
