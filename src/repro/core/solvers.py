"""Krylov solvers on top of (distributed) spMVM.

The paper's motivation (§1.1): spMVM dominates sparse eigensolvers and
linear solvers, and "for most iterative spMVM algorithms such as Krylov
subspace methods, permutation of the indices needs to be done only before
the start and after the end of the algorithm".  Every solver here takes
``a`` as either a :class:`repro.core.operator.SparseOperator` or a bare
``matvec`` closure (``_matvec_of`` normalizes), so ONE solver source runs
unchanged on:

* a single-device operator (``operator(m)`` — any storage format, in the
  original basis), a hand-built matvec closure (e.g. the permuted-basis
  pJDS closures the older tests use), or
* the distributed operator (``dist_operator(m, mesh)``) over a mesh,
  with all vector arithmetic staying sharded (jnp elementwise ops and
  ``jnp.vdot`` lower to per-shard compute + all-reduce under pjit).

Every linear solver returns one :class:`SolveResult`; all options are
keyword-only.  ``cg``/``bicgstab`` take an optional preconditioner ``M``
(a callable ``z = M(r)`` or ``"jacobi"``, which reads ``a.diagonal()``
— see :func:`jacobi`).  Non-symmetric DUAL systems (``A^T y = c``) need
no new code at all: pass ``op.T`` — the operator protocol's lazy
transpose view — to any solver.  The user-facing front door is
``repro.solve`` (``repro.api``), which also owns operator construction,
solver-level tuning and mixed-precision refinement.

Two iteration strategies share each method's math:

* the COMPOSED bodies (``cg``/``bicgstab``) apply the operator and then
  reduce the dot products as separate HLO ops — correct everywhere, but
  each reduction is another pass over vectors the spMV just wrote;
* the FUSED bodies (``fused_cg``/``fused_bicgstab``) take a
  ``matvec_dots(v, w1, w2)`` closure (``kernels.fused_iter``) returning
  ``(Av, <Av,w1>, <Av,w2>, <Av,Av>, <w2,w2>, <w1,w2>)`` — the dots
  reduced beside the spMV in one jitted step — and carry
  every remaining scalar (BiCGStab's rho, the exit test's look-ahead
  norm) by algebraic recurrence, so the loop body contains NO
  standalone vector reduction.  Carriers live at the operand's padded
  length; ``x0`` is donated back to the solver.

:func:`iterative_refinement` layers mixed precision on top: an inner
solve against a bf16(+int16) operand, with the residual correction
``x += solve(A_lo, b - A_f32 x)`` computed against the full-precision
operator — storage at 0.50x bytes/nnz, accuracy at the f32 target.

All loops are ``jax.lax.while_loop`` / ``fori_loop`` so the whole solve
is one compiled program (no host round-trips per iteration).

The BLOCK variants (``block_cg``, ``block_lanczos``) carry ``k`` vectors
at once through a multi-RHS operator (the protocol's ``matmat``): the
matrix is streamed from memory once per iteration for all k systems, and
in the distributed case the halo exchange set-up cost is amortised the
same way — the two levers the SELL-C-sigma follow-up (arXiv:1307.6209)
identifies for escaping the spMVM memory roofline.  All k-by-k
reductions (X^T Y) lower to per-shard matmuls + all-reduce under pjit,
so the block solvers stay fully sharded.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.tree_util import Partial

__all__ = ["SolveResult", "STATUS_NAMES", "cg", "bicgstab", "block_cg",
           "fused_cg", "fused_bicgstab", "iterative_refinement",
           "jacobi", "lanczos", "power_iteration", "tridiag_eigvals",
           "block_lanczos", "block_tridiag_eigvals"]

MatVec = Callable[[jax.Array], jax.Array]
Operator = "SparseOperator | MatVec"     # accepted by every solver

# (Av, <Av,w1>, <Av,w2>, <Av,Av>, <w2,w2>, <w1,w2>) — kernels.fused_iter
MatVecDots = Callable[[jax.Array, jax.Array, jax.Array], tuple]


# Terminal status codes.  Inside the compiled loops the same integers
# serve as the failure FLAG carried through the while_loop state, with 0
# meaning "no failure observed yet"; ``_result`` resolves the final code
# (a flag of 0 becomes converged or maxiter depending on the residual).
STATUS_CONVERGED = 0
STATUS_MAXITER = 1
STATUS_BREAKDOWN = 2
STATUS_DIVERGED = 3
STATUS_NON_FINITE = 4
STATUS_NAMES = ("converged", "maxiter", "breakdown", "diverged",
                "non_finite")

# Failure-detection thresholds (active only when tol > 0 — the tuner's
# and benchmark's tol <= 0 fixed-length probes must run to maxiter
# untouched).  DIVERGED when the squared RELATIVE residual exceeds
# _DIVERGE_REL2 (relative residual 1e6 from a start of ~1).
# Stagnation — two consecutive _STAG_WINDOW checkpoints without a
# _STAG_RTOL relative improvement (see _health) — reports as BREAKDOWN
# (the recurrence has stopped making progress, e.g. a singular
# operator's residual floor).  Checkpointed progress, NOT a
# running-minimum window: ill-conditioned f32 CG is non-monotone
# enough to spend >1500 iterations above its starting residual while
# genuinely converging.
_DIVERGE_REL2 = 1e12
_STAG_WINDOW = 500
_STAG_RTOL = 0.01


@dataclasses.dataclass
class SolveResult:
    """The one result type every linear solver returns.

    ``x``/``iters``/``residual`` stay lazy jax arrays (no forced device
    sync); ``residual`` is the relative residual ||r||/||b|| the solver
    terminated on (per column, shape (k,), for ``block_cg``) and
    ``converged`` is ``all(residual <= tol)``.  ``status_code`` is the
    device-side termination code (see ``STATUS_NAMES``); reading the
    ``status`` string forces the sync.  ``diagnostics`` carries
    failure-path detail (certified true residual, restart counts,
    refinement stall reasons, degradation-ladder rungs).  ``info``
    carries strategy / per-phase timing / refinement diagnostics —
    populated by the solver (``strategy``) and extended by
    ``repro.solve`` (``phase_s``, ``tune``, ``refine``, ``ladder``).
    """

    x: jax.Array
    iters: jax.Array
    residual: jax.Array
    converged: jax.Array
    method: str = ""
    info: dict = dataclasses.field(default_factory=dict)
    status_code: jax.Array | int = 0
    diagnostics: dict = dataclasses.field(default_factory=dict)

    @property
    def status(self) -> str:
        """Termination status string — one of ``STATUS_NAMES``.  This
        forces the device sync (the code is a lazy array)."""
        return STATUS_NAMES[int(self.status_code)]


def _result(method: str, x, iters, residual, tol: float, *,
            flag=0, diagnostics=None, **info) -> SolveResult:
    res = jnp.asarray(residual)
    flag = jnp.asarray(flag, jnp.int32)
    ok = jnp.all(res <= tol)
    code = jnp.where(ok, STATUS_CONVERGED,
                     jnp.where(flag != 0, flag, STATUS_MAXITER))
    return SolveResult(x=x, iters=iters, residual=residual,
                       converged=ok, method=method, info=dict(info),
                       status_code=code,
                       diagnostics=dict(diagnostics or {}))


def _apply_operator(op, x: jax.Array) -> jax.Array:
    return op.matvec(x) if x.ndim == 1 else op.matmat(x)


def _as_partial(f) -> Partial:
    """A callable as a jit ARGUMENT: a ``Partial``'s function is its
    static part (hashed by identity, so one function means one compile)
    and its bound arguments are traced leaves."""
    return f if isinstance(f, Partial) else Partial(f)


def _matvec_of(a) -> MatVec:
    """Normalize ``SparseOperator | MatVec`` to one apply callable.

    Operators dispatch 1-D carriers to ``matvec`` and 2-D blocks to
    ``matmat`` (the distributed operator shards the two differently);
    bare closures pass through — the pre-protocol call sites keep
    working as shims.

    The result is a ``Partial`` that the jitted solvers take as an
    argument: an operator's arrays enter the program as inputs.  Closed
    over instead, they would be baked into every compiled solver as
    constants — at published matrix sizes, hundreds of MB of executable
    per program — and every operator would compile its own programs.
    """
    if getattr(a, "matvec", None) is None:
        return _as_partial(a)
    return Partial(_apply_operator, a)


def jacobi(a) -> MatVec:
    """Jacobi (diagonal) preconditioner ``z = D^{-1} r`` from an
    operator's ``diagonal()``.  Zero diagonal entries (e.g. the padded
    tail of a distributed operator) pass through unscaled."""
    d = getattr(a, "diagonal", None)
    if d is None:
        raise TypeError(
            "jacobi needs a SparseOperator with .diagonal(); got "
            f"{type(a).__name__} — pass M as an explicit callable instead")
    cached = getattr(a, "_jacobi_precond", None)
    if cached is not None:
        return cached
    diag = d()
    inv = jnp.where(diag != 0, 1.0 / jnp.where(diag != 0, diag, 1), 1.0)
    precond = Partial(_scale_rows, inv.astype(diag.dtype))
    try:
        a._jacobi_precond = precond
    except (AttributeError, TypeError):
        pass
    return precond


def _scale_rows(inv: jax.Array, r: jax.Array) -> jax.Array:
    return r * (inv if r.ndim == 1 else inv[:, None])


def _identity(r: jax.Array) -> jax.Array:
    return r


# The no-op preconditioner: one stable object, so one compile.
_IDENTITY = Partial(_identity)


def _not_done(res2, tol):
    """Loop-exit test on the squared relative residual.  ``tol <= 0``
    means "run to maxiter" — the tuner's and benchmark's fixed-length
    probes rely on this, since a converged f32 residual (or the fused
    look-ahead's clamp) can reach EXACTLY zero and would otherwise end
    the probe early.  A NON-FINITE ``res2`` exits the loop (for tol > 0)
    — but as a detected failure, not as convergence: the loop bodies
    flag it via :func:`_health` and the result reports
    ``status == "non_finite"``.  (``res2 > tol*tol`` alone is False for
    NaN, which used to end the loop with the failure masked.)"""
    return (tol <= 0.0) | (jnp.isfinite(res2) & (res2 > tol * tol))


def _health(flag, rel2, best, since, *, breakdown, check):
    """One failure-detection step shared by every solver loop body.

    ``rel2`` is the squared relative residual the body just produced;
    ``breakdown`` the body's method-specific breakdown predicate (CG
    ``p·Ap <= 0``, BiCGStab ``rho -> 0``, block-CG a non-finite /
    indefinite Gram step); ``check`` gates everything off for tol <= 0
    probe runs.  Returns the updated ``(flag, best, since)`` — ``flag``
    latches the FIRST failure observed (0 = healthy).

    Stagnation is judged at CHECKPOINTS, not against a running minimum:
    ``best`` holds the residual at the last checkpoint and ``since``
    the iterations since the last checkpoint that showed progress.
    Every ``_STAG_WINDOW`` iterations the current residual is compared
    against the previous checkpoint's; a relative improvement of at
    least ``_STAG_RTOL`` resets the clock, and only TWO consecutive
    no-progress checkpoints fire BREAKDOWN.  A running-minimum window
    false-positives on ill-conditioned CG, whose residual is
    non-monotone: measured on a cond~1e6 SPD system, the residual
    climbs to 7.6x its starting value and sets no new minimum for the
    first ~1500 of the 15000 iterations it genuinely needs — yet it
    IMPROVES between any two adjacent checkpoints on its way back
    down, which is exactly what this predicate measures.  A singular
    operator's residual floor is flat across checkpoints and still
    fires, one window later."""
    finite = jnp.isfinite(rel2)
    since = since + 1
    at_ckpt = (since % _STAG_WINDOW) == 0
    progressed = finite & (rel2 <= best * (1.0 - _STAG_RTOL))
    stalled = at_ckpt & ~progressed & (since >= 2 * _STAG_WINDOW)
    new = jnp.where(~finite, STATUS_NON_FINITE,
          jnp.where(breakdown, STATUS_BREAKDOWN,
          jnp.where(rel2 > _DIVERGE_REL2, STATUS_DIVERGED,
          jnp.where(stalled, STATUS_BREAKDOWN, 0))))
    new = jnp.where(check, new, 0).astype(jnp.int32)
    best = jnp.where(at_ckpt, rel2, best)
    since = jnp.where(at_ckpt & progressed, 0, since)
    return jnp.where(flag != 0, flag, new), best, since


def _nz(d):
    """Replace an exactly-zero denominator with a tiny value — keeps
    probe-mode (tol <= 0) carriers finite after a residual hits 0.0
    instead of spreading NaN through the remaining timed iterations."""
    return jnp.where(d == 0, jnp.asarray(1e-30, d.dtype), d)


def _precond_of(M, a) -> MatVec | None:
    if M is None:
        return None
    if M == "jacobi":
        return jacobi(a)
    if callable(M):
        return _as_partial(M)
    raise TypeError(f"M must be None, 'jacobi' or a callable; got {M!r}")


def cg(a: Operator, b: jax.Array, *, x0: jax.Array | None = None,
       maxiter: int = 500, tol: float = 1e-6, M=None) -> SolveResult:
    """(Preconditioned) conjugate gradients for SPD A.

    ``a``: SparseOperator or matvec closure.  ``M``: optional
    preconditioner — ``"jacobi"`` (diagonal, from ``a.diagonal()``) or a
    callable ``z = M(r)`` approximating ``A^{-1} r``.  Convergence is
    checked on the TRUE residual ||r|| / ||b||, so results with and
    without M are directly comparable.
    """
    matvec = _matvec_of(a)
    pre = _precond_of(M, a)
    x0 = jnp.zeros_like(b) if x0 is None else x0
    if pre is None:
        x, k, res, flag = _cg(matvec, b, x0, maxiter, tol)
    else:
        x, k, res, flag = _pcg(matvec, pre, b, x0, maxiter, tol)
    return _result("cg", x, k, res, tol, flag=flag, strategy="composed")


def _health_init(rel2, tol):
    """Initial (flag, best, since) carriers: a non-finite INITIAL
    residual (poisoned b / x0 / values) is flagged before the loop
    ever runs a body."""
    check = tol > 0.0
    flag = jnp.where(check & ~jnp.isfinite(rel2),
                     STATUS_NON_FINITE, 0).astype(jnp.int32)
    best = jnp.where(jnp.isfinite(rel2), rel2, jnp.inf)
    return flag, jnp.asarray(best, jnp.asarray(rel2).dtype), jnp.int32(0)


@functools.partial(jax.jit, static_argnums=(3,))
def _cg(matvec: MatVec, b: jax.Array, x0: jax.Array,
        maxiter: int = 500, tol: float = 1e-6):
    x = x0
    r = b - matvec(x)
    p = r
    rs = jnp.vdot(r, r)
    b2 = jnp.maximum(jnp.vdot(b, b), 1e-30)
    check = tol > 0.0
    flag, best, since = _health_init(rs / b2, tol)

    def cond(state):
        _, _, _, rs, k, flag, _, _ = state
        return (flag == 0) & _not_done(rs / b2, tol) & (k < maxiter)

    def body(state):
        x, r, p, rs, k, flag, best, since = state
        ap = matvec(p)
        pap = jnp.vdot(p, ap)
        # p·Ap <= 0 => A is not SPD along p: CG breakdown.  Zero the
        # step so x/r stay at the last healthy iterate (the select
        # fuses into the axpy — no extra memory pass).
        bad = check & ((pap <= 0.0) | ~jnp.isfinite(pap))
        alpha = jnp.where(bad, 0.0, rs / _nz(pap))
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.vdot(r, r)
        flag, best, since = _health(flag, rs_new / b2, best, since,
                                    breakdown=bad, check=check)
        p = r + (rs_new / _nz(rs)) * p
        return x, r, p, rs_new, k + 1, flag, best, since

    x, r, p, rs, k, flag, best, since = jax.lax.while_loop(
        cond, body, (x, r, p, rs, jnp.int32(0), flag, best, since))
    return x, k, jnp.sqrt(rs / b2), flag


@functools.partial(jax.jit, static_argnums=(4,))
def _pcg(matvec: MatVec, precond: MatVec, b: jax.Array, x0: jax.Array,
         maxiter: int = 500, tol: float = 1e-6):
    """Preconditioned CG: same recurrence with z = M r directions."""
    x = x0
    r = b - matvec(x)
    z = precond(r)
    p = z
    rz = jnp.vdot(r, z)
    rs = jnp.vdot(r, r)
    b2 = jnp.maximum(jnp.vdot(b, b), 1e-30)
    check = tol > 0.0
    flag, best, since = _health_init(rs / b2, tol)

    def cond(state):
        _, _, _, _, rs, k, flag, _, _ = state
        return (flag == 0) & _not_done(rs / b2, tol) & (k < maxiter)

    def body(state):
        x, r, p, rz, rs, k, flag, best, since = state
        ap = matvec(p)
        pap = jnp.vdot(p, ap)
        bad = check & ((pap <= 0.0) | ~jnp.isfinite(pap))
        alpha = jnp.where(bad, 0.0, rz / _nz(pap))
        x = x + alpha * p
        r = r - alpha * ap
        z = precond(r)
        rz_new = jnp.vdot(r, z)
        rs_new = jnp.vdot(r, r)
        flag, best, since = _health(flag, rs_new / b2, best, since,
                                    breakdown=bad, check=check)
        p = z + (rz_new / _nz(rz)) * p
        return x, r, p, rz_new, rs_new, k + 1, flag, best, since

    x, r, p, rz, rs, k, flag, best, since = jax.lax.while_loop(
        cond, body, (x, r, p, rz, rs, jnp.int32(0), flag, best, since))
    return x, k, jnp.sqrt(rs / b2), flag


def bicgstab(a: Operator, b: jax.Array, *, x0: jax.Array | None = None,
             maxiter: int = 1000, tol: float = 1e-6,
             M=None) -> SolveResult:
    """BiCGStab (van der Vorst 1992) for general (non-symmetric) A.

    Transpose-free: the recurrence itself never applies ``A^T`` — but
    the DUAL system ``A^T y = c`` is solved by simply passing ``op.T``
    (the protocol's lazy transpose view) as ``a``.  ``M`` as in
    :func:`cg` (right preconditioning: A M z-directions).
    """
    matvec = _matvec_of(a)
    pre = _precond_of(M, a) or _IDENTITY
    x0 = jnp.zeros_like(b) if x0 is None else x0
    x, k, res, flag = _bicgstab(matvec, pre, b, x0, maxiter, tol)
    return _result("bicgstab", x, k, res, tol, flag=flag,
                   strategy="composed")


@functools.partial(jax.jit, static_argnums=(4,))
def _bicgstab(matvec: MatVec, precond: MatVec, b: jax.Array, x0: jax.Array,
              maxiter: int = 1000, tol: float = 1e-6):
    dt = b.dtype
    tiny = jnp.asarray(1e-30, dt)

    def _safe(d):
        return jnp.where(jnp.abs(d) > tiny, d, tiny)

    x = x0
    r = b - matvec(x)
    rhat = r                       # shadow residual, fixed
    one = jnp.asarray(1.0, dt)
    b2 = jnp.maximum(jnp.vdot(b, b), 1e-30)
    check = tol > 0.0
    flag, best, since = _health_init(jnp.vdot(r, r) / b2, tol)
    state = (x, r, jnp.zeros_like(b), jnp.zeros_like(b),
             one, one, one, jnp.vdot(r, r), jnp.int32(0),
             flag, best, since)

    def cond(state):
        rs, k, flag = state[7], state[8], state[9]
        return (flag == 0) & _not_done(rs / b2, tol) & (k < maxiter)

    def body(state):
        x, r, p, v, rho, alpha, omega, _rs, k, flag, best, since = state
        rho_new = jnp.vdot(rhat, r)
        beta = (rho_new / _safe(rho)) * (alpha / _safe(omega))
        p = r + beta * (p - omega * v)
        p_hat = precond(p)
        v = matvec(p_hat)
        rhat_v = jnp.vdot(rhat, v)
        alpha = rho_new / _safe(rhat_v)
        s = r - alpha * v
        s_hat = precond(s)
        t = matvec(s_hat)
        tt = jnp.vdot(t, t)
        omega = jnp.vdot(t, s) / _safe(tt)
        x = x + alpha * p_hat + omega * s_hat
        r = s - omega * t
        rs_new = jnp.vdot(r, r)
        # rho -> 0 (serious breakdown: r orthogonal to the shadow
        # residual) or a vanishing <rhat, Ap> / <t, t> — the _safe
        # clamps keep the carriers finite, the flag makes it a typed
        # failure instead of silent garbage.
        bad = ((jnp.abs(rho_new) <= tiny) | (jnp.abs(rhat_v) <= tiny)
               | (jnp.abs(tt) <= tiny))
        flag, best, since = _health(flag, rs_new / b2, best, since,
                                    breakdown=bad, check=check)
        return (x, r, p, v, rho_new, alpha, omega, rs_new, k + 1,
                flag, best, since)

    out = jax.lax.while_loop(cond, body, state)
    x, rs, k, flag = out[0], out[7], out[8], out[9]
    return x, k, jnp.sqrt(rs / b2), flag


# --------------------------------------------------------------------------
# Fused-iteration solvers (spMV + dots in one kernel pass)
# --------------------------------------------------------------------------
def fused_cg(matvec_dots: MatVecDots, b: jax.Array, *,
             x0: jax.Array | None = None, maxiter: int = 500,
             tol: float = 1e-6) -> SolveResult:
    """CG whose loop body is ONE fused spMV+dots pass and three axpys.

    ``matvec_dots`` is the callable ``kernels.fused_iter.make_matvec_dots``
    builds over a SELL operand.  Each pass ``matvec_dots(p, p, r)`` returns Ap together with
    <Ap,p>, <Ap,r>, <Ap,Ap> and the EXACT <r,r> (the free self-dot
    of w2), so alpha and beta use an exact residual
    norm every iteration; only the exit test's one-step look-ahead

        <r',r'> = <r,r> - 2 alpha <Ap,r> + alpha^2 <Ap,Ap>

    is a recurrence (clamped at 0).  The host driver then certifies the
    TRUE residual ``||b - Ax||/||b||`` with one composed pass and warm-
    restarts if the look-ahead exited optimistically — the reported
    residual/converged are always honest.  Carriers live at the
    operand's padded length (pad rows stay exactly zero through every
    recurrence); ``x0`` is donated to the solve.  Unpreconditioned (the
    fused step reduces plain dots; ``repro.solve`` falls back to the
    composed body when a preconditioner is requested).
    """
    return _fused_drive(_fused_cg, "cg", matvec_dots, b, x0, maxiter, tol)


def fused_bicgstab(matvec_dots: MatVecDots, b: jax.Array, *,
                   x0: jax.Array | None = None, maxiter: int = 1000,
                   tol: float = 1e-6) -> SolveResult:
    """BiCGStab over the fused spMV+dots pass (two per iteration).

    Every scalar the composed body reduces separately arrives fused:
    pass one, ``matvec_dots(p, rhat, r)``, yields v = Ap with <v,rhat>,
    <v,r>, <v,v> and the exact ||r||^2; pass two,
    ``matvec_dots(s, rhat, s)``, yields t = As with <t,rhat>, <t,s>,
    <t,t>, the exact ||s||^2 AND the exact <rhat,s> (the epilogue's
    w1·w2 cross-dot).  The two scalars with no direct dot follow:

        rho_{k+1} = <rhat, r'> = <rhat,s> - omega <t, rhat>,
        ||r'||^2  = ||s||^2 - 2 omega <t,s> + omega^2 <t,t>,

    the latter only as the exit test's one-step look-ahead.  rho uses
    the measured <rhat,s>, NOT the textbook simplification <rhat,s> = 0
    — exact in exact arithmetic, but its f32 drift stalls the rho
    recurrence on matrices where composed BiCGStab converges fine.
    Same host restart driver and carrier/donation contract as
    :func:`fused_cg`.
    """
    return _fused_drive(_fused_bicgstab, "bicgstab", matvec_dots, b, x0,
                        maxiter, tol)


def _fused_drive(loop_fn, method: str, matvec_dots: MatVecDots,
                 b: jax.Array, x0, maxiter: int, tol: float) -> SolveResult:
    """Host driver shared by the fused solvers: run the compiled loop,
    certify the true residual with one composed pass, warm-restart while
    it still improves.  At most a handful of host syncs per SOLVE —
    versus one per iteration for a scipy-style stepped loop.

    The certification is the ARBITER: a loop that exits claiming
    convergence (its look-ahead recurrence under tol) whose certified
    TRUE residual stays above tol is demoted to ``status="diverged"``
    with the evidence in ``diagnostics`` — never returned as converged.
    """
    x = jnp.zeros_like(b) if x0 is None else x0
    matvec_dots = _as_partial(matvec_dots)
    total, restarts = 0, 0
    rn_prev = float("inf")
    flag, demoted = 0, False
    while True:
        x, k, _, lflag = loop_fn(matvec_dots, b, x, maxiter - total, tol)
        total += int(k)
        flag = int(lflag)
        rn = float(_true_residual(matvec_dots, b, x))
        if not math.isfinite(rn):
            flag = flag or STATUS_NON_FINITE
            break
        if (tol > 0 and rn <= tol) or flag != 0 or total >= maxiter:
            break
        if int(k) == 0 or rn >= rn_prev:
            # the look-ahead claimed convergence (or a restart made no
            # progress) but the certified residual disagrees — demote
            demoted = tol > 0
            break
        rn_prev = rn
        restarts += 1
    if demoted and flag == 0:
        flag = STATUS_DIVERGED
    diagnostics = {"true_residual": rn, "restarts": restarts,
                   "certified": bool(math.isfinite(rn) and tol > 0
                                     and rn <= tol)}
    if demoted:
        diagnostics["demoted"] = True
    return _result(method, x, total, rn, tol, flag=flag,
                   diagnostics=diagnostics,
                   strategy="fused", restarts=restarts)


@jax.jit
def _true_residual(matvec_dots: MatVecDots, b: jax.Array, x: jax.Array):
    r = b - matvec_dots(x, x, x)[0]
    return jnp.sqrt(jnp.vdot(r, r) / jnp.maximum(jnp.vdot(b, b), 1e-30))


@functools.partial(jax.jit, donate_argnums=(2,))
def _fused_cg(matvec_dots: MatVecDots, b: jax.Array, x0: jax.Array,
              maxiter, tol):
    r = b - matvec_dots(x0, x0, b)[0]
    rs = jnp.vdot(r, r)            # exact, once per (re)start
    b2 = jnp.maximum(jnp.vdot(b, b), 1e-30)
    check = tol > 0.0
    flag, best, since = _health_init(rs / b2, tol)

    def cond(state):
        _, _, _, rs, k, flag, _, _ = state
        return (flag == 0) & _not_done(rs / b2, tol) & (k < maxiter)

    def body(state):
        x, r, p, _rs, k, flag, best, since = state
        ap, pap, r_ap, apap, rr, _ = matvec_dots(p, p, r)  # rr exact
        bad = check & ((pap <= 0.0) | ~jnp.isfinite(pap))
        alpha = jnp.where(bad, 0.0, rr / _nz(pap))
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.maximum(rr - 2.0 * alpha * r_ap + alpha * alpha * apap,
                             0.0)
        flag, best, since = _health(flag, rs_new / b2, best, since,
                                    breakdown=bad, check=check)
        p = r + (rs_new / jnp.maximum(rr, 1e-30)) * p
        return x, r, p, rs_new, k + 1, flag, best, since

    x, r, p, rs, k, flag, best, since = jax.lax.while_loop(
        cond, body, (x0, r, r, rs, jnp.int32(0), flag, best, since))
    return x, k, jnp.sqrt(rs / b2), flag


@functools.partial(jax.jit, donate_argnums=(2,))
def _fused_bicgstab(matvec_dots: MatVecDots, b: jax.Array, x0: jax.Array,
                    maxiter, tol):
    dt = b.dtype
    tiny = jnp.asarray(1e-30, dt)

    def _safe(d):
        return jnp.where(jnp.abs(d) > tiny, d, tiny)

    r = b - matvec_dots(x0, x0, b)[0]
    rhat = r                       # shadow residual, fixed
    rs0 = jnp.vdot(r, r)           # exact, once per (re)start
    b2 = jnp.maximum(jnp.vdot(b, b), 1e-30)
    one = jnp.asarray(1.0, dt)
    check = tol > 0.0
    flag, best, since = _health_init(rs0 / b2, tol)
    # state: (x, r, p, v, rho, rho_prev, alpha, omega, rs, k, health);
    # rho_1 = <rhat, r0> = ||r0||^2 and rho_0 := rho_1 so the first
    # beta is (rho_1/rho_0)(alpha/omega) = 1 and p_1 = r0 (v = p = 0).
    state = (x0, r, jnp.zeros_like(b), jnp.zeros_like(b),
             rs0, rs0, one, one, rs0, jnp.int32(0), flag, best, since)

    def cond(state):
        rs, k, flag = state[8], state[9], state[10]
        return (flag == 0) & _not_done(rs / b2, tol) & (k < maxiter)

    def body(state):
        (x, r, p, v, rho, rho_prev, alpha, omega, rs, k,
         flag, best, since) = state
        beta = (rho / _safe(rho_prev)) * (alpha / _safe(omega))
        p = r + beta * (p - omega * v)
        v, rhat_v, _r_v, _vv, _rr, _ = matvec_dots(p, rhat, r)
        alpha = rho / _safe(rhat_v)
        s = r - alpha * v
        # rhat_s = <rhat, s> EXACT from the epilogue cross-dot — the
        # textbook pipelined recurrence assumes it zero, and its f32
        # drift stalls the rho recurrence (stagnation at ~1e-5)
        t, t_rhat, t_s, tt, ss, rhat_s = matvec_dots(s, rhat, s)
        omega = t_s / _safe(tt)
        x = x + alpha * p + omega * s
        r = s - omega * t
        rs_new = jnp.maximum(ss - 2.0 * omega * t_s + omega * omega * tt, 0.0)
        rho_next = rhat_s - omega * t_rhat
        bad = ((jnp.abs(rho) <= tiny) | (jnp.abs(rhat_v) <= tiny)
               | (jnp.abs(tt) <= tiny))
        flag, best, since = _health(flag, rs_new / b2, best, since,
                                    breakdown=bad, check=check)
        return (x, r, p, v, rho_next, rho, alpha, omega, rs_new, k + 1,
                flag, best, since)

    out = jax.lax.while_loop(cond, body, state)
    x, rs, k, flag = out[0], out[8], out[9], out[10]
    return x, k, jnp.sqrt(rs / b2), flag


# --------------------------------------------------------------------------
# Mixed-precision iterative refinement
# --------------------------------------------------------------------------
def iterative_refinement(residual_of: MatVec, inner_solve, b: jax.Array, *,
                         x0: jax.Array | None = None, tol: float = 1e-6,
                         max_rounds: int = 10):
    """Outer f32 correction loop over a low-precision inner solve.

    ``residual_of(x) -> b - A x`` MUST apply the FULL-precision
    operator; ``inner_solve(r) -> (dx, iters, inner_residual)`` solves
    ``A dx = r`` against the low-precision (bf16+int16) operand to its
    own looser tolerance.  Classic iterative refinement: each round the
    true f32 residual is re-measured and the correction added, so the
    bf16 storage only ever limits CONVERGENCE RATE, never the final
    accuracy — rounds stop at ``tol`` on the true relative residual, on
    ``max_rounds``, or when a round fails to reduce the residual
    (divergent inner operand — e.g. a matrix too ill-conditioned for
    bf16 values).

    Host-driven by design: a handful of rounds, each a full compiled
    inner solve, with per-round diagnostics the caller can report.
    Returns ``(x, rel_residual, rounds, reason)`` where ``rounds`` is
    one dict per correction (inner iteration count, residual entering
    the round) and ``reason`` names why the outer loop stopped:
    ``"converged"``, ``"max_rounds"``, ``"stalled"`` (a round failed to
    reduce the true residual — the divergence guard; the caller should
    escalate to a full-precision solve instead of burning more rounds)
    or ``"non_finite"`` (a poisoned operand/correction).
    """
    bn = max(float(jnp.linalg.norm(b)), 1e-30)
    x = jnp.zeros_like(b) if x0 is None else x0
    rounds = []
    rn_prev = float("inf")
    while True:
        r = residual_of(x)
        rn = float(jnp.linalg.norm(r)) / bn
        if not math.isfinite(rn):
            reason = "non_finite"
            break
        if rn <= tol:
            reason = "converged"
            break
        if len(rounds) >= max_rounds:
            reason = "max_rounds"
            break
        if rn >= rn_prev:
            reason = "stalled"
            break
        dx, iters, inner_res = inner_solve(r)
        x = x + dx.astype(x.dtype)
        rounds.append({"residual_in": rn, "inner_iters": int(iters),
                       "inner_residual": float(inner_res)})
        rn_prev = rn
    return x, rn, rounds, reason


def lanczos(a: Operator, v0: jax.Array, m: int = 50):
    """m-step Lanczos: returns (alphas, betas) of the tridiagonal T_m.
    Eigenvalues of T_m approximate extremal eigenvalues of symmetric A —
    the Holstein-Hubbard (HMEp) use case of the paper's group."""
    return _lanczos(_matvec_of(a), v0, m)


@functools.partial(jax.jit, static_argnums=(2,))
def _lanczos(matvec: MatVec, v0: jax.Array, m: int = 50):
    v = v0 / jnp.linalg.norm(v0)

    def body(carry, _):
        v_prev, v, beta = carry
        w = matvec(v) - beta * v_prev
        alpha = jnp.vdot(w, v)
        w = w - alpha * v
        # one step of full reorthogonalisation against the two known vectors
        w = w - jnp.vdot(w, v) * v
        beta_new = jnp.linalg.norm(w)
        v_new = w / jnp.maximum(beta_new, 1e-30)
        return (v, v_new, beta_new), (alpha, beta_new)

    (_, _, _), (alphas, betas) = jax.lax.scan(
        body, (jnp.zeros_like(v), v, jnp.asarray(0.0, v.dtype)), None, length=m
    )
    return alphas, betas


def _ridge(a: jax.Array) -> jax.Array:
    """Tiny trace-relative ridge for the k-by-k Gram systems — shared by
    block-CG and CholeskyQR so the two regularize identically."""
    k = a.shape[0]
    eps = jnp.asarray(jnp.finfo(a.dtype).eps, a.dtype)
    scale = eps * (jnp.trace(a) / k) + jnp.asarray(1e-30, a.dtype)
    return scale * jnp.eye(k, dtype=a.dtype)


def _ridge_solve(a: jax.Array, b: jax.Array) -> jax.Array:
    """Solve the k-by-k system with a tiny trace-relative ridge so the
    block recurrences survive a column converging early (the Gram
    matrices go singular exactly when a residual column hits zero)."""
    return jnp.linalg.solve(a + _ridge(a), b)


def block_cg(a: Operator, b: jax.Array, *, x0: jax.Array | None = None,
             maxiter: int = 500, tol: float = 1e-6) -> SolveResult:
    """Block conjugate gradients (O'Leary 1980) for SPD A, k RHS at once.

    b: (n, k).  ``a``: SparseOperator (its ``matmat`` runs the k systems
    per matrix stream) or a closure accepting (n, k).  Stops when EVERY
    column's relative residual is below ``tol``; ``result.residual`` is
    the per-column vector, ``result.converged`` requires all columns.
    """
    x, k_it, res, flag = _block_cg(_matvec_of(a), b,
                                   jnp.zeros_like(b) if x0 is None else x0,
                                   maxiter, tol)
    return _result("block_cg", x, k_it, res, tol, flag=flag,
                   strategy="composed")


@functools.partial(jax.jit, static_argnums=(3,))
def _block_cg(matvec: MatVec, b: jax.Array, x0: jax.Array,
              maxiter: int = 500, tol: float = 1e-6):
    x = x0
    r = b - matvec(x)
    p = r
    rtr = r.T @ r                                     # (k, k)
    b2 = jnp.maximum(jnp.sum(b * b, axis=0), 1e-30)   # (k,)
    check = tol > 0.0
    flag, best, since = _health_init(
        jnp.max(jnp.diagonal(rtr) / b2), tol)

    def cond(state):
        _, _, _, rtr, k_it, flag, _, _ = state
        res2 = jnp.diagonal(rtr) / b2
        return ((flag == 0) & jnp.any(_not_done(res2, tol))
                & (k_it < maxiter))

    def body(state):
        x, r, p, rtr, k_it, flag, best, since = state
        ap = matvec(p)
        ptap = p.T @ ap
        alpha = _ridge_solve(ptap, rtr)               # (k, k)
        # A direction with p_j·Ap_j <= 0 (indefinite A) or a Gram solve
        # gone non-finite (the k-by-k factorization failing on a
        # poisoned/singular block) is a block breakdown: zero the step
        # so x/r hold the last healthy iterate.  Columns already under
        # tol are exempt — their directions legitimately shrink to 0.
        live = jnp.diagonal(rtr) / b2 > tol * tol
        bad = check & (jnp.any(live & (jnp.diagonal(ptap) <= 0.0))
                       | ~jnp.all(jnp.isfinite(alpha)))
        alpha = jnp.where(bad, jnp.zeros_like(alpha), alpha)
        x = x + p @ alpha
        r = r - ap @ alpha
        rtr_new = r.T @ r
        flag, best, since = _health(
            flag, jnp.max(jnp.diagonal(rtr_new) / b2), best, since,
            breakdown=bad, check=check)
        beta = _ridge_solve(rtr, rtr_new)
        p = r + p @ beta
        return x, r, p, rtr_new, k_it + 1, flag, best, since

    x, r, p, rtr, k_it, flag, best, since = jax.lax.while_loop(
        cond, body, (x, r, p, rtr, jnp.int32(0), flag, best, since))
    return x, k_it, jnp.sqrt(jnp.diagonal(rtr) / b2), flag


def _chol_qr(w: jax.Array):
    """CholeskyQR: W = Q R with Q^T Q = I via the k-by-k Gram matrix —
    only matmuls and a k-by-k factorization, so it stays sharded along n
    (a tall-skinny QR would gather W).  Returns (Q, R upper)."""
    g = w.T @ w
    g = g + _ridge(g)
    l = jnp.linalg.cholesky(g)                        # G = L L^T
    # Q = W L^{-T}:  solve L Y = W^T, Q = Y^T
    q = jax.scipy.linalg.solve_triangular(l, w.T, lower=True).T
    return q, l.T


def block_lanczos(a: Operator, v0: jax.Array, m: int = 25):
    """m-step block Lanczos for symmetric A with block size k = v0.shape[1].

    Returns (A_blocks (m, k, k), B_blocks (m, k, k)) of the block
    tridiagonal T_m:  A V_j = V_{j-1} B_{j-1}^T + V_j A_j + V_{j+1} B_j.
    Eigenvalues of T_m approximate extremal eigenvalues of A, converging
    faster per matrix pass than scalar Lanczos because every pass streams
    the matrix once for k directions (``block_tridiag_eigvals`` builds
    and solves T_m host-side)."""
    return _block_lanczos(_matvec_of(a), v0, m)


@functools.partial(jax.jit, static_argnums=(2,))
def _block_lanczos(matvec: MatVec, v0: jax.Array, m: int = 25):
    v, _ = _chol_qr(v0)
    k = v.shape[1]

    def body(carry, _):
        v_prev, v, b_prev = carry
        w = matvec(v) - v_prev @ b_prev.T
        a = v.T @ w
        w = w - v @ a
        # one full reorthogonalisation pass against the two known blocks
        w = w - v @ (v.T @ w) - v_prev @ (v_prev.T @ w)
        v_new, b = _chol_qr(w)
        return (v, v_new, b), (a, b)

    init = (jnp.zeros_like(v), v, jnp.zeros((k, k), v.dtype))
    _, (alphas, betas) = jax.lax.scan(body, init, None, length=m)
    return alphas, betas


def block_tridiag_eigvals(a_blocks, b_blocks):
    """Eigenvalues of the block-Lanczos block tridiagonal (host, numpy)."""
    import numpy as np
    a = np.asarray(a_blocks, dtype=np.float64)
    b = np.asarray(b_blocks, dtype=np.float64)
    m, k, _ = a.shape
    t = np.zeros((m * k, m * k))
    for j in range(m):
        s = slice(j * k, (j + 1) * k)
        t[s, s] = (a[j] + a[j].T) / 2
        if j + 1 < m:
            s1 = slice((j + 1) * k, (j + 2) * k)
            t[s1, s] = b[j]
            t[s, s1] = b[j].T
    return np.linalg.eigvalsh(t)


def tridiag_eigvals(alphas, betas):
    """Eigenvalues of the Lanczos tridiagonal (host-side, numpy)."""
    import numpy as np
    a = np.asarray(alphas, dtype=np.float64)
    b = np.asarray(betas, dtype=np.float64)[:-1]
    t = np.diag(a) + np.diag(b, 1) + np.diag(b, -1)
    return np.linalg.eigvalsh(t)


def power_iteration(a: Operator, v0: jax.Array, iters: int = 100):
    """Dominant eigenpair via power iteration."""
    return _power_iteration(_matvec_of(a), v0, iters)


@functools.partial(jax.jit, static_argnums=(2,))
def _power_iteration(matvec: MatVec, v0: jax.Array, iters: int = 100):
    def body(v, _):
        w = matvec(v)
        lam = jnp.vdot(v, w)
        v_new = w / jnp.maximum(jnp.linalg.norm(w), 1e-30)
        return v_new, lam

    v, lams = jax.lax.scan(body, v0 / jnp.linalg.norm(v0), None, length=iters)
    return v, lams[-1]
