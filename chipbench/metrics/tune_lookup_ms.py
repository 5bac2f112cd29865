"""Milliseconds per solve that ``repro.solve`` spends in its tuner
(``info["phase_s"]["tune"]``: fingerprint and cache lookup once tuned)."""


def read(ctx):
    tune_s = ctx["counters"].get("tune_s")
    if not tune_s:
        return None
    return 1000.0 * sum(tune_s) / len(tune_s)
