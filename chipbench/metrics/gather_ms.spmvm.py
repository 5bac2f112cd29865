"""Device milliseconds per apply under the program scope
``repro.gather_rhs``: the RHS gather ``x[col_idx]``, its index cast
included, in the traced window, averaged over the devices."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_apply(ctx, "repro.gather_rhs")
