"""The fused Krylov iteration's operator step: SELL-C-sigma spMV plus the
iteration's dot products, as one call per iteration.

The paper's roofline (§4) prices an spMVM-bound solver entirely by HBM
traffic per iteration, and the SELL-C-sigma follow-up (arXiv:1307.6209)
points at amortising that traffic across the whole iteration.  One call
returns

    y = A x,  d1 = <y, w1>   d2 = <y, w2>   dy = <y, y>
              dw = <w2, w2>  dz = <w1, w2>

so a fully-recurrent CG/BiCGStab body (``core.solvers.fused_cg`` /
``fused_bicgstab``) needs NO other per-iteration vector reduction: every
alpha/beta/omega/residual-norm scalar follows algebraically from these
dots.  The solvers route their residual-type carrier through ``w2``, so
every iteration gets an EXACT ||r||^2 (or ||s||^2) — the scalar that,
carried purely by recurrence, cancels catastrophically once convergence
is fast — and BiCGStab reads the exact <rhat, s> from ``dz``.

On the kernel backend y comes from the SELL kernel (``ops.sell_matvec``:
the RHS gather and the window unpermute run in XLA around the Pallas
pass, see ``kernels._backend``) and the five dots are XLA reductions
over the unpermuted y and the carriers, which XLA fuses inside the
solver's ``while_loop``.  The dots cannot ride the kernel's output
epilogue: the kernel's y is in window-sorted row order and the carriers
are not, and the TPU compiler has no in-kernel gather to bring them
together.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.tree_util import Partial

from . import ops

__all__ = ["fused_matvec_dots", "make_matvec_dots"]


def fused_matvec_dots(a, x, w1, w2, *, backend: str = "ref"):
    """(y, <y,w1>, <y,w2>, <y,y>, <w2,w2>, <w1,w2>) over a ``SELLDevice``,
    y = A x in ORIGINAL row order.

    ``backend`` is the RESOLVED backend string ("kernel" on TPU, "ref"
    elsewhere — callers go through ``ops.resolve_backend``).  Carriers
    live at the padded length ``a.n_rows_pad``; padded rows store zero
    values, so y is zero there and the y-dots are exact.
    """
    y = ops.sell_matvec(a, x, backend)
    dt = y.dtype
    w1c = w1.astype(dt)
    w2c = w2.astype(dt)
    return (y, jnp.vdot(y, w1c), jnp.vdot(y, w2c),
            jnp.vdot(y, y), jnp.vdot(w2c, w2c), jnp.vdot(w1c, w2c))


# One function per backend: the static part of every fused solver's jit
# key, so operands of one structure share their compiled programs.
_MATVEC_DOTS = {b: functools.partial(fused_matvec_dots, backend=b)
                for b in ("kernel", "ref")}


def make_matvec_dots(a, *, backend: str = "ref"):
    """``(v, w1, w2) -> fused_matvec_dots(a, v, w1, w2)`` over one
    ``SELLDevice``, as a ``Partial`` the fused solvers
    (``core.solvers.fused_cg``/``fused_bicgstab``) take as a jit
    argument: the operand's arrays are traced inputs, not constants."""
    return Partial(_MATVEC_DOTS[backend], a)
