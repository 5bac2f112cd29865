"""One closed-loop client calling ``repro.solve`` with a fresh ``b``
from the seed for every call.

Without ``prebuilt`` in the traffic the client hands ``repro.solve`` the
host matrix, as the README documents, and the front door tunes (once,
cached) and builds the layout.  With ``"prebuilt": {<operator keywords>}``
set-up builds one ``repro.core.operator.operator`` and every call solves
on it, which bypasses the tuner.
"""
from __future__ import annotations

import time

from chipbench import loops as L
from chipbench import reference as R

# The keywords of the traffic file that ``repro.solve`` takes as they are.
SOLVE_KEYS = ("method", "tol", "refine", "tune", "fallback", "maxiter")


def _on_ref_rung(res) -> bool:
    """Whether the program's degradation ladder left the kernel backend
    for the jnp reference path (``kernel->ref`` and every rung after it)."""
    return any(e.get("rung") == "kernel->ref"
               for e in res.info.get("ladder", ()))


def _off_primary(res) -> bool:
    return any(e.get("rung") != "primary" for e in res.info.get("ladder", ()))


def run(run: L.Run) -> L.Outcome:
    import jax
    import jax.numpy as jnp
    import repro

    t = run.traffic
    m = run.matrix
    n = m.n_rows
    kw = {k: t[k] for k in SOLVE_KEYS if k in t}
    if L.value_dtype(t) is not None:
        kw["dtype"] = L.value_dtype(t)
    make_b = jax.jit(lambda key, i: jax.random.normal(
        jax.random.fold_in(key, i), (n,), jnp.float32))
    key = run.jax_key(1)
    warm = 1 << 30                     # the warm-up's b lies outside the window's

    def operand():
        if "prebuilt" not in t:
            return run.csr()
        from repro.core.operator import operator
        kw.pop("dtype", None)
        return operator(run.csr(), dtype=L.value_dtype(t),
                        index_dtype=run.cfg["index_dtype"], **t["prebuilt"])

    t0 = time.perf_counter()
    a = operand()
    res = repro.solve(a, make_b(key, warm), **kw)
    run.phases["first_solve_s"] = time.perf_counter() - t0
    run.phases.update({f"first_solve_{k}_s": v
                       for k, v in res.info["phase_s"].items()})
    if not res.info.get("tune", {}).get("cached", True):
        # This run tuned: the candidates it measured, and their programs,
        # stay loaded with the host matrix object.  A fresh object and
        # fresh caches keep only the winner.
        a = operand()
        L.free_program_state()
        jax.clear_caches()
        t0 = time.perf_counter()
        res = repro.solve(a, make_b(key, warm), **kw)
        run.phases["rebuild_after_tuning_s"] = time.perf_counter() - t0
    jax.block_until_ready(res.x)
    setup_s = time.perf_counter() - run.t_start

    sample = L.Reservoir(int(t["check_samples"]), run.rng(2))
    lat, iters, tune_s, failed, on_ref, off_primary = [], [], [], 0, 0, 0
    x = None
    with run.tracer.window():
        w0 = time.perf_counter()
        i = 0
        while True:
            b = make_b(key, i)
            t0 = time.perf_counter()
            with run.tracer.span("chipbench.solve"):
                try:
                    res = repro.solve(a, b, **kw)
                    x = jax.block_until_ready(res.x)
                    ok = res.status == "converged"
                except repro.SolveFailure:
                    res, ok = None, False
            now = time.perf_counter()
            i += 1
            if res is not None:
                on_ref += _on_ref_rung(res)
                off_primary += _off_primary(res)
            if ok:
                lat.append(now - t0)
                iters.append(int(res.iters))
                tune_s.append(res.info["phase_s"].get("tune", 0.0))
                sample.offer(b, x)
            else:
                failed += 1
            if now - w0 >= run.seconds:
                break
        window_s = now - w0
    sample.to_host()
    in_use, peak = L.memory(run.devices())
    layout = (res.info.get("tune", {}).get("layout")
              if res is not None else None)
    del res, x, b, a
    L.free_program_state()

    ref = R.HostCSR(m)
    resid = [ref.true_residual(b_h, x_h) for b_h, x_h in sample.items]
    return L.Outcome(
        setup_s=setup_s, window_s=window_s, attempted=i, failed=failed,
        end_to_end={"solves_per_s": len(lat) / window_s},
        counters={"solves": len(lat), "iters": iters, "tune_s": tune_s,
                  "solve_s": L.timing(lat), "layout": layout,
                  "iters_range": {"min": min(iters, default=None),
                                  "max": max(iters, default=None)},
                  "off_primary_solves": off_primary},
        checks={"max_true_residual": (max(resid, default=float("inf")),
                                      run.limits["max_true_residual"]),
                "failed_solves": (failed, run.limits["failed_solves"]),
                "ref_rung_solves": (on_ref, 0)},
        hbm_bytes_in_use=in_use, memory_peak_bytes=peak)
