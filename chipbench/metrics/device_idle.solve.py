"""Share of the traced window in which no operation ran on the device,
in the cells that drive whole solves."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or "solves" not in ctx["counters"]:
        return None
    return 100.0 * trace.idle_share
