"""Share of the matrix's non-zeros that the operator's apply serves from
a window of x inside the kernel, the rest being gathered in XLA: the
program's gauge ``repro.window_share`` (``repro.obs``), noted when the
run built its operator; 0 for an operator without windows."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:          # a program that notes no gauges
        return None
    share = obs.gauges().get("repro.window_share")
    return None if share is None else float(share)
