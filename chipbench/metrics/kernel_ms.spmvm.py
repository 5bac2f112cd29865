"""Device milliseconds per apply under the program scope
``repro.kernel``: the Pallas spMV kernels (the ``pallas_call`` and its
output slice), in the traced window, averaged over the devices."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_apply(ctx, "repro.kernel")
