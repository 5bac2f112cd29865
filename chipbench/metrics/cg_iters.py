"""Mean Krylov iterations per solve (``SolveResult.iters``) over the
window's converged solves."""


def read(ctx):
    iters = ctx["counters"].get("iters")
    if not iters:
        return None
    return sum(iters) / len(iters)
