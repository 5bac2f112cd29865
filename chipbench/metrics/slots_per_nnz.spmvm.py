"""Value slots the operator streams per apply, padding included and
summed over devices, per non-zero of the matrix: the program's gauge
``repro.stored_slots`` (``repro.obs``), noted when the run built its
operator."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:          # a program that notes no gauges
        return None
    slots = obs.gauges().get("repro.stored_slots")
    if not slots:
        return None
    return slots / ctx["nnz"]
