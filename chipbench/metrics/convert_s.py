"""Seconds the program spent converting the host CSR to its stored
format, format choice included: the total of its ``repro.convert`` host
spans (``repro.obs``) in this run's process, all of them in set-up."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:          # a program without host spans
        return None
    return obs.totals().get("repro.convert")
