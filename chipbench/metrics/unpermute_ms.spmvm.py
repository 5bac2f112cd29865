"""Device milliseconds per apply under the program scope
``repro.unpermute``: the row unpermute ``y[inv_perm]`` and any reorder
sandwich, in the traced window, averaged over the devices."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_apply(ctx, "repro.unpermute")
