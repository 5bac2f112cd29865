"""LDBC Graphalytics PageRank (arXiv:2011.15028) through the program's
``repro.core.graph``: the transition operator ``P = A D^-1`` built once
through ``operator()`` (the traffic's ``operator`` keywords, format
"auto"), then ``steps_per_dispatch`` iterations of
``pagerank_steps`` per jitted call, calls back to back with
``queue_ahead_s`` seconds of them enqueued, each call's input the one
before's output.  ``applies`` counts iterations, one ``P @ x`` each.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from chipbench import loops as L
from chipbench import pagerank_reference as R
from repro.core import graph as G     # a program without it exits at once

MAX_DEPTH = 32


def run(run: L.Run) -> L.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    t = run.traffic
    m = run.matrix
    k = int(t["steps_per_dispatch"])
    damping = float(t["damping"])
    t0 = time.perf_counter()
    pr = G.pagerank_operator(run.csr(), dtype=L.value_dtype(t),
                             index_dtype=run.cfg["index_dtype"],
                             **t["operator"])
    jax.block_until_ready(pr)
    run.phases["operator_build_s"] = time.perf_counter() - t0
    backend = ops.resolve_backend(pr.op.backend)
    step = jax.jit(G.pagerank_steps, static_argnums=(2,))
    x = run.rng(1).uniform(0.5, 1.5, m.n_rows)
    x = jnp.asarray((x / x.sum()).astype(np.float32))
    t0 = time.perf_counter()
    jax.block_until_ready(step(pr, x, k, damping))     # compiles
    run.phases["first_dispatch_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(step(pr, x, k, damping))
    warm_s = time.perf_counter() - t0
    run.phases["warm_dispatch_s"] = warm_s
    depth = min(max(2, math.ceil(float(t["queue_ahead_s"]) / warm_s)),
                MAX_DEPTH)
    setup_s = time.perf_counter() - run.t_start

    # As the power loop: ``depth`` dispatches stay enqueued while the
    # host waits on the oldest; at the window's end nothing more is
    # sent, all that was sent is waited for and counted, then the clock
    # is read.
    sample = L.Reservoir(int(t["check_samples"]), run.rng(2))
    pending = collections.deque()
    call_s = []
    x_last = x
    with run.tracer.window():
        w0 = time.perf_counter()
        now = w0
        while True:
            while now - w0 < run.seconds and len(pending) < depth:
                pending.append((x_last, step(pr, x_last, k, damping)))
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
    fmt = getattr(pr.op, "fmt", type(pr.op).__name__)
    del pr, step, x
    L.free_program_state()

    ref = R.HostPageRank(m)
    errs = [R.max_rel_err(x_out, ref.steps(x_in, k, damping))
            for x_in, x_out in sample.items]
    applies = len(call_s) * k
    nonfinite = sum(not np.isfinite(x_out).all() for _, x_out in sample.items)
    return L.Outcome(
        setup_s=setup_s, window_s=window_s, attempted=applies,
        failed=int(nonfinite),
        end_to_end={"spmvm_gflops": 2.0 * m.nnz * applies / window_s / 1e9},
        counters={"applies": applies, "format": fmt, "backend": backend,
                  "queue_depth": depth, "dispatch_s": L.timing(call_s)},
        checks={"pagerank_max_rel_err": (max(errs),
                                         run.limits["pagerank_max_rel_err"]),
                "off_backend": (int(backend != run.expected_backend), 0)},
        hbm_bytes_in_use=in_use, memory_peak_bytes=peak)
