"""Device milliseconds per PageRank iteration under the program scope
``repro.pagerank``: the dangling sum, the teleport and the damping
around the apply, in the traced window, averaged over the devices."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_apply(ctx, "repro.pagerank")
