"""Test-support shims: ``hypothesis_fallback`` stands in for the
``hypothesis`` package where it is not installed."""
