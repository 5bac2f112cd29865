"""``repro.solve`` — the one front door to every linear solve.

The rest of the package is layered exactly like the paper's software
stack: storage formats (``core.formats``), device kernels
(``kernels``), the operator protocol (``core.operator``), Krylov
methods (``core.solvers``), the autotuner (``tune``).  ``solve`` is the
seam that composes them for the common case::

    import repro
    res = repro.solve(m, b)                      # host CSR, CG, tuned
    res = repro.solve(op, b, method="bicgstab")  # existing operator
    res.x, res.residual, res.iters, res.converged, res.info

It owns the three decisions a caller would otherwise wire by hand:

* STRATEGY — the fused spMV+dots iteration (``kernels.fused_iter`` +
  ``solvers.fused_cg``/``fused_bicgstab``) whenever the operand
  supports it (single-device SELL, resident RHS, square, no
  preconditioner), the composed operator bodies otherwise (Dist
  operators, block solves, preconditioned solves, bare closures);
* TUNING — for host matrices, ``tune.tune_solver`` measures layout
  candidates under the solver's own iteration (the config that wins
  per ITERATION, not per matvec) and caches the winner under the
  structural-fingerprint key;
* PRECISION — ``refine`` wraps the solve in mixed-precision iterative
  refinement (``solvers.iterative_refinement``): inner iterations
  against a bf16(+int16) operand at 0.50x bytes/nnz, residual
  corrections against the full-precision operator, final accuracy at
  the f32 target.

``refine="auto"`` turns refinement on exactly when a host matrix is
requested with a sub-f32 ``dtype`` (the outer operator is then built at
native f32 and the INNER one at the requested dtype); ``refine=True``
forces it — for an existing f32 operator the inner operand is a bf16
cast of it (Device and Dist operators both).  Refining a bare closure
or a block solve raises (there is nothing to cast / no block
refinement path).

Every call returns :class:`repro.core.solvers.SolveResult`; ``info``
carries ``strategy``, per-phase wall-clock ``phase_s`` (tune / build /
solve: the durations of the host spans ``repro.solve.tune``,
``repro.solve.build`` and ``repro.solve.iterate``), the tuner's
decision under ``tune`` and per-round refinement diagnostics under
``refine``.
"""
from __future__ import annotations

import math
import warnings

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import graph as G
from repro.core import solvers as S
from repro.core.solvers import SolveResult

__all__ = ["solve", "SolveFailure", "pagerank"]

_METHODS = ("cg", "bicgstab", "block_cg")
_DEFAULT_MAXITER = {"cg": 500, "bicgstab": 1000, "block_cg": 500}


class SolveFailure(RuntimeError):
    """Raised by :func:`solve` when the degradation ladder is exhausted:
    every rung either raised or ended in a failure status (breakdown /
    diverged / non_finite / decertified).  Carries the evidence —
    ``ladder`` is the per-rung record (label, status or error, certified
    residual) and ``result`` the last :class:`SolveResult` produced (its
    ``status``/``diagnostics`` describe the final failure), or ``None``
    if every rung raised before producing one."""

    def __init__(self, message: str, *, result=None, ladder=None):
        super().__init__(message)
        self.result = result
        self.ladder = list(ladder or [])


def _is_host_matrix(a) -> bool:
    from repro.core import formats as F
    return isinstance(a, F.CSRMatrix)


def _is_sub_f32(dtype) -> bool:
    if dtype is None:
        return False
    dt = jnp.dtype(dtype)
    return jnp.issubdtype(dt, jnp.floating) and dt.itemsize < 4


def _fused_eligible(op, method: str, precond, b: jax.Array) -> bool:
    """The fused iteration needs: a single-device SELL operand, square,
    1-D RHS, no preconditioner (the fused step reduces plain dots), and
    a cg/bicgstab recurrence."""
    from repro.core.operator import DeviceOperator
    return (method in ("cg", "bicgstab") and precond is None
            and b.ndim == 1 and isinstance(op, DeviceOperator)
            and op.fmt == "sell"
            and op.shape[0] == op.shape[1])


def _fused_dots_of(op):
    """The fused-pass callable over ``op``'s SELL operand."""
    from repro.kernels import ops as K
    from repro.kernels.fused_iter import make_matvec_dots
    return make_matvec_dots(op.dev.dev, backend=K.resolve_backend(op.backend))


def _cast_low_precision(op):
    """A bf16 clone of an existing f32 operator for refinement's inner
    solves: every floating leaf of the device/distributed operand drops
    to bf16 (0.25x value bytes); a single-device SELL operand whose
    column space fits additionally compresses ``col_idx`` to int16,
    landing on the PR-4 0.50x bytes/nnz layout.  Structure-only fields
    (index maps, permutations, halo tables) are untouched, so the clone
    shares the original's partition/layout exactly."""
    import dataclasses as _dc

    from repro.core.operator import DeviceOperator, DistOperator

    def _lo(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(jnp.bfloat16)
        return leaf

    if isinstance(op, DeviceOperator):
        dev = jax.tree_util.tree_map(_lo, op.dev)
        inner = dev.dev
        if (op.fmt == "sell" and hasattr(inner, "col_idx")
                and op.shape[1] <= jnp.iinfo(jnp.int16).max):
            inner = _dc.replace(inner,
                                col_idx=inner.col_idx.astype(jnp.int16))
            dev = _dc.replace(dev, dev=inner)
        return DeviceOperator(dev, backend=op.backend)
    if isinstance(op, DistOperator):
        dist = jax.tree_util.tree_map(_lo, op.dist)
        return DistOperator(dist, op.mesh, axis=op.axis, mode=op.mode,
                            backend=op.backend, halo=op.halo,
                            diag=op.diag)
    raise ValueError(
        "refine=True needs a Device/Dist operator (or a host matrix) to "
        f"cast to bf16; got {type(op).__name__}")


def _pad_to(v: jax.Array, n_pad: int) -> jax.Array:
    return v if v.shape[0] == n_pad else jnp.pad(v, (0, n_pad - v.shape[0]))


def _one_solve(op, b, *, method, strategy, maxiter, tol, precond,
               x0=None) -> SolveResult:
    if strategy == "fused":
        mvd = _fused_dots_of(op)
        n, n_pad = op.shape[0], op.dev.dev.n_rows_pad
        bp = _pad_to(b, n_pad)
        x0p = None if x0 is None else _pad_to(x0, n_pad)
        fn = S.fused_cg if method == "cg" else S.fused_bicgstab
        res = fn(mvd, bp, x0=x0p, maxiter=maxiter, tol=tol)
        res.x = res.x[:n]
        return res
    if method == "cg":
        return S.cg(op, b, x0=x0, maxiter=maxiter, tol=tol, M=precond)
    if method == "bicgstab":
        return S.bicgstab(op, b, x0=x0, maxiter=maxiter, tol=tol, M=precond)
    return S.block_cg(op, b, x0=x0, maxiter=maxiter, tol=tol)


def _refined_solve(op, op_lo, b, *, method, strategy, maxiter, tol,
                   precond, x0=None) -> SolveResult:
    """Mixed-precision refinement: inner ``method`` solves on the
    low-precision operand, residual corrections on the full-precision
    one.  The inner tolerance is floored at 1e-3 — bf16 storage cannot
    resolve much further, and the outer loop closes the rest."""
    apply_full = S._matvec_of(op)
    inner_tol = max(tol, 1e-3)
    inner_strategy = ("fused" if _fused_eligible(op_lo, method, precond, b)
                      else "composed")

    def residual_of(x):
        return b - apply_full(x)

    def inner(r):
        rr = _one_solve(op_lo, r.astype(b.dtype), method=method,
                        strategy=inner_strategy, maxiter=maxiter,
                        tol=inner_tol, precond=precond)
        return rr.x.astype(b.dtype), rr.iters, rr.residual

    x, rn, rounds, reason = S.iterative_refinement(residual_of, inner, b,
                                                   x0=x0, tol=tol)
    # The divergence guard: a stalled or poisoned refinement is a typed
    # failure (the ladder escalates to the f32 rung), not maxiter worth
    # of useless corrections.
    flag = {"stalled": S.STATUS_DIVERGED,
            "non_finite": S.STATUS_NON_FINITE}.get(reason, 0)
    total = sum(r["inner_iters"] for r in rounds)
    res = S._result(method, x, total, rn, tol, flag=flag,
                    diagnostics={"refine_reason": reason,
                                 "true_residual": rn,
                                 "certified": reason == "converged"},
                    strategy=f"{inner_strategy}+refined")
    res.info["refine"] = {
        "rounds": rounds,
        "reason": reason,
        "inner_dtype": str(op_lo.dtype),
        "inner_tol": inner_tol,
    }
    return res


def _true_rel_residual(op, b, x) -> float:
    """Certified relative true residual ||b - A x|| / ||b|| through the
    operator (max over columns for block RHS) — the arbiter behind
    ``status == "converged"``."""
    r = b - S._matvec_of(op)(x)
    if b.ndim == 1:
        return float(jnp.linalg.norm(r)
                     / jnp.maximum(jnp.linalg.norm(b), 1e-30))
    num = jnp.linalg.norm(r, axis=0)
    den = jnp.maximum(jnp.linalg.norm(b, axis=0), 1e-30)
    return float(jnp.max(num / den))


def _certify(res: SolveResult, op, b, tol: float) -> SolveResult:
    """Demote a "converged" claim whose certified true residual misses
    tol (recurrence drift, a broken kernel, a garbled exchange):
    certification is the arbiter, not the recurrence.  Skipped when the
    solver already certified (fused drive / refinement measure the true
    residual themselves — ``diagnostics["true_residual"]`` present)."""
    if tol <= 0:
        return res
    if "true_residual" not in res.diagnostics:
        try:
            rn = _true_rel_residual(op, b, res.x)
        except Exception as e:                      # certification broke
            res.diagnostics["certify_error"] = f"{type(e).__name__}: {e}"
            rn = float("nan")
        res.diagnostics["true_residual"] = rn
        res.diagnostics["certified"] = rn == rn and rn <= tol
    if res.status == "converged" and not res.diagnostics.get("certified"):
        res.status_code = S.STATUS_DIVERGED
        res.converged = jnp.asarray(False)
        res.diagnostics["demoted"] = True
    return res


def solve(a, b, *, method: str = "cg", precond=None, tol: float = 1e-6,
          maxiter: int | None = None, x0=None, tune="auto",
          refine="auto", fallback="auto", format: str = "auto", dtype=None,
          index_dtype="auto", backend="auto",
          **convert_kwargs) -> SolveResult:
    """Solve ``A x = b``; see the module docstring for the decisions
    this front door makes.

    ``a``: a host ``CSRMatrix`` (an operator is built — ``format`` /
    ``dtype`` / ``index_dtype`` / ``backend`` and any further
    ``as_device`` keywords apply, unless the tuner picks the layout), an
    existing ``SparseOperator`` (used as-is), or a bare matvec closure
    (composed strategy only).  ``method``: ``"cg"`` (SPD),
    ``"bicgstab"`` (general), ``"block_cg"`` (SPD, b of shape (n, k)).
    ``precond``: ``None``, ``"jacobi"`` or a callable ``z = M(r)``.
    ``tune``: ``"auto"`` measures solver-level layout candidates for
    host matrices (cached; ``"force"`` re-measures), ``"off"`` builds
    the heuristic layout.  ``refine``: ``"auto"`` / ``True`` / ``False``
    mixed-precision refinement, see module docstring.

    ``fallback="auto"`` (default) arms the degradation ladder: a rung
    that raises or ends in a failure status (breakdown / diverged /
    non_finite / a "converged" claim demoted by the true-residual
    certification) falls through fused->composed, bf16-refined->f32,
    kernel backend->ref and a final escalation retry (fresh x0 + jacobi)
    — the rungs taken are recorded in ``result.info["ladder"]`` and
    exhaustion raises a typed :class:`SolveFailure`.  ``fallback="off"``
    runs only the preferred configuration and returns its typed result
    (``result.status``) without retrying or raising.  Either way a
    result with ``status == "converged"`` has a certified true residual
    ``<= tol`` (see ``result.diagnostics["true_residual"]``).
    """
    if method not in _METHODS:
        raise ValueError(f"method must be one of {_METHODS}; got {method!r}")
    if not isinstance(b, jax.Array):
        b = jnp.asarray(b)
    if method == "block_cg" and b.ndim != 2:
        raise ValueError(f"block_cg expects b of shape (n, k); got {b.shape}")
    if method != "block_cg" and b.ndim != 1:
        raise ValueError(f"{method} expects a 1-D b; got shape {b.shape}")
    if refine is True and method == "block_cg":
        raise ValueError("refine is not available for block_cg "
                         "(no block refinement path)")
    if refine is True and callable(precond):
        raise ValueError("refine=True cannot re-derive a callable precond "
                         "for the low-precision operand; use precond="
                         "'jacobi' or None")
    maxiter = _DEFAULT_MAXITER[method] if maxiter is None else maxiter
    with obs.span("repro.solve"):
        phase_s: dict = {}
        info_tune = None
        strategy_pref = None
        op_lo = None

        if _is_host_matrix(a):
            m = a
            do_refine = (refine is True
                         or (refine == "auto" and _is_sub_f32(dtype)
                             and method != "block_cg"))
            inner_dtype = dtype if _is_sub_f32(dtype) else jnp.bfloat16
            build_kwargs = dict(convert_kwargs)
            with obs.span("repro.solve.tune") as sp:
                if tune not in ("off", False, None) and method != "block_cg":
                    from repro import tune as T
                    st = T.tune_solver(m, method=method,
                                       dtype=None if do_refine else dtype,
                                       index_dtype=index_dtype,
                                       force=(tune == "force"))
                    strategy_pref = st.strategy
                    build_kwargs = st.layout.build_kwargs()
                    if "validate" in convert_kwargs:  # admission gate survives
                        build_kwargs["validate"] = convert_kwargs["validate"]
                    info_tune = {"cached": st.cached, "strategy": st.strategy,
                                 "layout": st.layout.label()}
                else:
                    build_kwargs.setdefault("format", format)
                    if (build_kwargs["format"] == "auto"
                            and method in ("cg", "bicgstab")
                            and precond is None):
                        build_kwargs["format"] = "sell"  # fused-eligible build
            phase_s["tune"] = sp.seconds

            from repro.core.operator import operator
            with obs.span("repro.solve.build") as sp:
                op = operator(m, dtype=None if do_refine else dtype,
                              index_dtype=index_dtype, backend=backend,
                              **build_kwargs)
                if do_refine:
                    op_lo = operator(m, dtype=inner_dtype,
                                     index_dtype=index_dtype, backend=backend,
                                     **build_kwargs)
            phase_s["build"] = sp.seconds
        else:
            op = a
            is_operator = hasattr(op, "matvec")
            do_refine = refine is True
            if do_refine and not is_operator:
                raise ValueError("refine=True needs an operator or host "
                                 "matrix; got a bare closure")
            if do_refine and _is_sub_f32(getattr(op, "dtype", None)):
                raise ValueError("refine=True expects a full-precision "
                                 "operator to refine against; this one is "
                                 f"already {op.dtype} — pass the host "
                                 "matrix instead")
            with obs.span("repro.solve.build") as sp:
                if do_refine:
                    op_lo = _cast_low_precision(op)
            phase_s["build"] = sp.seconds

        strategy = ("fused"
                    if (_fused_eligible(op, method, precond, b)
                        and strategy_pref != "composed")
                    else "composed")

        with obs.span("repro.solve.iterate") as sp:
            res, ladder = _ladder_solve(op, op_lo, b, method=method,
                                        strategy=strategy, maxiter=maxiter,
                                        tol=tol, precond=precond, x0=x0,
                                        fallback=fallback)
        phase_s["solve"] = sp.seconds

        res.info["phase_s"] = phase_s
        if info_tune is not None:
            res.info["tune"] = info_tune
        if len(ladder) > 1 or fallback not in ("off", False, None):
            res.info["ladder"] = ladder
        return res


def _build_rungs(op, op_lo, *, method, strategy, precond, fallback):
    """The degradation ladder, most- to least-aggressive: the preferred
    configuration, then fused->composed, bf16-refined->f32, kernel
    backend->ref, and finally a bounded escalation retry (fresh x0 +
    jacobi where the method and operator support it).  Rungs that would
    repeat the previous configuration are skipped.

    A GENERATOR on purpose: the happy path consumes only the primary
    rung, so the fallback rungs' construction cost (imports, backend
    resolution, diagonal probing) is paid only after a failure — the
    ladder's happy-path overhead budget is enforced by
    ``benchmarks.bench_solve.MAX_LADDER_OVERHEAD``."""
    yield {"label": "primary", "op": op, "op_lo": op_lo,
           "strategy": strategy, "precond": precond, "fresh_x0": False}
    if fallback in ("off", False, None):
        return
    from repro.core.operator import DeviceOperator

    if strategy == "fused":
        yield {"label": "fused->composed", "op": op, "op_lo": op_lo,
               "strategy": "composed", "precond": precond,
               "fresh_x0": False}
    if op_lo is not None:
        yield {"label": "bf16->f32", "op": op, "op_lo": None,
               "strategy": "composed", "precond": precond,
               "fresh_x0": False}
    esc_op = op
    if isinstance(op, DeviceOperator):
        from repro.kernels import ops as K
        if K.resolve_backend(op.backend) == "kernel":
            esc_op = DeviceOperator(op.dev, backend="ref")
            yield {"label": "kernel->ref", "op": esc_op,
                   "op_lo": None, "strategy": "composed",
                   "precond": precond, "fresh_x0": False}
    esc_precond = precond
    if (precond is None and method in ("cg", "bicgstab")
            and getattr(esc_op, "diagonal", None) is not None):
        esc_precond = "jacobi"
    yield {"label": "escalate:fresh-x0"
           + ("+jacobi" if esc_precond == "jacobi"
              and precond is None else ""),
           "op": esc_op, "op_lo": None, "strategy": "composed",
           "precond": esc_precond, "fresh_x0": True}


_FAILURE_STATUSES = ("breakdown", "diverged", "non_finite")


def _ladder_solve(op, op_lo, b, *, method, strategy, maxiter, tol, precond,
                  x0, fallback):
    """Walk the degradation ladder.  Each rung runs, is certified
    (:func:`_certify` — the true-residual arbiter), and is recorded;
    success returns immediately.  ``maxiter`` (status "maxiter") is an
    honest typed outcome, not a fault — it returns without escalating
    (except for refined rungs, whose round cap should escalate to the
    f32 rung, not mask it).  When every rung fails, ``fallback="auto"``
    surfaces a typed :class:`SolveFailure`; ``fallback="off"`` returns
    the single rung's typed result as-is."""
    fallback_on = fallback not in ("off", False, None)
    if fallback not in ("auto", True, "off", False, None):
        raise ValueError(f"fallback must be 'auto' or 'off'; got "
                         f"{fallback!r}")
    rungs = _build_rungs(op, op_lo, method=method, strategy=strategy,
                         precond=precond, fallback=fallback)
    ladder, res, warm = [], None, None
    for rung in rungs:
        if rung["label"] == "kernel->ref":
            # The ref path is a different, much slower program: never
            # take it without a trace of why the kernels did not serve.
            last = ladder[-1] if ladder else {}
            warnings.warn(
                "repro.solve: the Pallas kernel path failed ("
                + last.get("error", f"status {last.get('status')}")
                + "); retrying on the XLA reference path",
                RuntimeWarning, stacklevel=3)
        rung_x0 = None if rung["fresh_x0"] else (x0 if warm is None else warm)
        try:
            rn_prev, restarts = float("inf"), 0
            iters_acc = None
            while True:
                if rung["op_lo"] is not None:
                    res = _refined_solve(rung["op"], rung["op_lo"], b,
                                         method=method,
                                         strategy=rung["strategy"],
                                         maxiter=maxiter, tol=tol,
                                         precond=rung["precond"], x0=rung_x0)
                else:
                    res = _one_solve(rung["op"], b, method=method,
                                     strategy=rung["strategy"],
                                     maxiter=maxiter, tol=tol,
                                     precond=rung["precond"], x0=rung_x0)
                res = _certify(res, rung["op"], b, tol)
                status = res.status    # forces the device sync in-try
                # a warm restart is a continuation of the same solve:
                # report the rung's cumulative iteration count, not the
                # (often single-digit) final polish segment's
                iters_acc = (res.iters if iters_acc is None
                             else iters_acc + res.iters)
                res.iters = iters_acc
                rn = res.diagnostics.get("true_residual")
                # Certification miss from recurrence drift: warm-restart
                # the SAME rung — re-seeding from x resets the recurrence
                # to the true residual (the composed analogue of
                # _fused_drive's restart) — while it still improves.
                if (res.diagnostics.get("demoted") and restarts < 2
                        and rn is not None and math.isfinite(rn)
                        and rn < rn_prev):
                    rung_x0, rn_prev, restarts = res.x, rn, restarts + 1
                    continue
                break
        except Exception as e:
            if not fallback_on:
                raise                  # single rung: surface the original
            ladder.append({"rung": rung["label"],
                           "error": f"{type(e).__name__}: {e}"})
            continue
        entry = {"rung": rung["label"], "status": status}
        if restarts:
            entry["restarts"] = restarts
        if rn is not None:
            entry["true_residual"] = rn
        ladder.append(entry)
        if status == "converged":
            break
        if status == "maxiter" and rung["op_lo"] is None:
            break                      # honest out-of-budget — not a fault
        if not fallback_on:
            break
        # warm-start the next rung from any finite partial progress
        if rn is not None and math.isfinite(rn) and rn < 1.0:
            warm = res.x
    else:
        last = ladder[-1] if ladder else {}
        raise SolveFailure(
            f"solve({method}) failed on every ladder rung "
            f"(last: {last}); see .ladder / .result for diagnostics",
            result=res, ladder=ladder)
    return res, ladder


def pagerank(adj, damping: float = 0.85, iterations: int = 10, x0=None):
    """PageRank of the graph ``adj`` as LDBC Graphalytics defines it:
    ``iterations`` fixed iterations from ``x0`` (``1/|V|`` each by
    default) with damping ``damping``, the dangling vertices' rank
    spread over every vertex.  ``adj`` is a host CSR whose stored entry
    ``(u, v)`` is the edge ``u -> v`` (:mod:`repro.core.graph`); the
    transition matrix goes through ``operator()`` with format "auto".
    Returns the ranks, float32, one per row of ``adj``."""
    pr = G.pagerank_operator(adj)
    if x0 is None:
        x = jnp.full(pr.n, 1.0 / pr.n, jnp.float32)
    else:
        x = jnp.asarray(x0, jnp.float32)
        if x.shape != (pr.n,):
            raise ValueError(f"x0 has shape {x.shape}; the graph has "
                             f"{pr.n} vertices")
    return _pagerank_steps(pr, x, int(iterations), float(damping))


_pagerank_steps = jax.jit(G.pagerank_steps, static_argnums=(2,))
