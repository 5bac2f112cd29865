"""Share of the spMV kernel grid's steps that compute a chunk, the rest
being steps past an output group's extent that fetch nothing and skip
their compute: the program's gauge ``repro.grid_fill`` (``repro.obs``),
noted when the run built its operator; None from a program without it."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:          # a program that notes no gauges
        return None
    fill = obs.gauges().get("repro.grid_fill")
    return None if fill is None else float(fill)
