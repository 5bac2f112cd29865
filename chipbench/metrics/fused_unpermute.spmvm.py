"""Share of the operator's rows whose unpermute runs inside the kernel,
the rest being unpermuted by an XLA gather of y: the program's gauge
``repro.fused_unpermute`` (``repro.obs``), noted when the run built its
operator; 0 for an operator that unpermutes in XLA or has no row order
to restore."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:          # a program that notes no gauges
        return None
    share = obs.gauges().get("repro.fused_unpermute")
    return None if share is None else float(share)
