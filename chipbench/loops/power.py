"""Power iteration ``x <- A x / ||A x||`` through ``op @ x``,
``steps_per_dispatch`` steps per jitted call, calls back to back with
``queue_ahead_s`` seconds of them enqueued.

The traffic's ``operator`` names the program's builder and its keywords:
``{"build": "operator", "format": "auto"}`` builds one
``repro.core.operator.operator`` on one chip; ``{"build":
"dist_operator", "mode": ...}`` partitions the matrix with
``dist_operator`` over a 1-D mesh of the cell's chips.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from chipbench import loops as L
from chipbench import reference as R

# The most dispatches enqueued, whatever ``queue_ahead_s`` asks: a step
# of microseconds (a test on the CPU) would otherwise queue thousands.
MAX_DEPTH = 32


def build(run: L.Run, spec: dict):
    from repro.core import operator as O
    kw = {k: v for k, v in spec.items() if k != "build"}
    if spec["build"] == "dist_operator":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(run.chips)
        return O.dist_operator(run.csr(), mesh, axis="data", **kw)
    if spec["build"] != "operator":
        raise ValueError(f"unknown operator build {spec['build']!r}")
    return O.operator(run.csr(), dtype=L.value_dtype(run.traffic),
                      index_dtype=run.cfg["index_dtype"], **kw)


def _steps(op, x, k: int, n: int):
    """``k`` power steps; ``x`` carries the operator's padded columns
    (a partitioned operator pads its rows), whose entries stay 0."""
    import jax
    import jax.numpy as jnp

    def body(v, _):
        y = (op @ v)[:n]
        y = y / jnp.maximum(jnp.linalg.norm(y), 1e-30)
        return jnp.pad(y, (0, v.shape[0] - n)), None

    return jax.lax.scan(body, x, None, length=k)[0]


def run(run: L.Run) -> L.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    t = run.traffic
    m = run.matrix
    k = int(t["steps_per_dispatch"])
    t0 = time.perf_counter()
    op = build(run, t["operator"])
    jax.block_until_ready(op)
    run.phases["operator_build_s"] = time.perf_counter() - t0
    backend = ops.resolve_backend(op.backend)
    step = jax.jit(_steps, static_argnums=(2, 3))
    n = m.n_rows
    x = run.rng(1).standard_normal(n)
    x = np.pad(x / np.linalg.norm(x), (0, op.shape[1] - n))
    x = jnp.asarray(x.astype(np.float32))
    t0 = time.perf_counter()
    jax.block_until_ready(step(op, x, k, n))     # compiles
    run.phases["first_dispatch_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(step(op, x, k, n))
    warm_s = time.perf_counter() - t0
    run.phases["warm_dispatch_s"] = warm_s
    depth = min(max(2, math.ceil(float(t["queue_ahead_s"]) / warm_s)),
                MAX_DEPTH)
    setup_s = time.perf_counter() - run.t_start

    # ``depth`` dispatches, ``queue_ahead_s`` seconds of device work, stay
    # enqueued while the host waits on the oldest, as a user's loop that
    # does not sync every step keeps them: a stall of the host or the
    # runtime shorter than the queue leaves the device busy.  Each step's
    # input is the one before's output.  When the time is up nothing more
    # is sent, all that was sent is waited for, and the clock is read
    # after that wait: every step sent counts, over all that time.
    sample = L.Reservoir(int(t["check_samples"]), run.rng(2))
    pending = collections.deque()
    call_s = []
    x_last = x
    with run.tracer.window():
        w0 = time.perf_counter()
        now = w0
        while True:
            while now - w0 < run.seconds and len(pending) < depth:
                pending.append((x_last, step(op, x_last, k, n)))
                x_last = pending[-1][1]
            if not pending:
                break
            with run.tracer.span("chipbench.dispatch"):
                x_in, x_out = pending.popleft()
                jax.block_until_ready(x_out)
            t_prev, now = now, time.perf_counter()
            call_s.append(now - t_prev)
            sample.offer(x_in, x_out)
        window_s = now - w0
    del pending, x_in, x_out, x_last
    sample.to_host()
    in_use, peak = L.memory(run.devices())
    fmt = getattr(op, "fmt", type(op).__name__)
    del op, step, x
    L.free_program_state()

    ref = R.HostCSR(m)
    errs = [R.max_rel_err(x_out, ref.power_steps(x_in, k))
            for x_in, x_out in sample.items]
    applies = len(call_s) * k
    nonfinite = sum(not np.isfinite(x_out).all() for _, x_out in sample.items)
    return L.Outcome(
        setup_s=setup_s, window_s=window_s, attempted=applies,
        failed=int(nonfinite),
        end_to_end={"spmvm_gflops": 2.0 * m.nnz * applies / window_s / 1e9},
        counters={"applies": applies, "format": fmt, "backend": backend,
                  "queue_depth": depth, "dispatch_s": L.timing(call_s)},
        checks={"power_max_rel_err": (max(errs),
                                      run.limits["power_max_rel_err"]),
                "off_backend": (int(backend != run.expected_backend), 0)},
        hbm_bytes_in_use=in_use, memory_peak_bytes=peak)
