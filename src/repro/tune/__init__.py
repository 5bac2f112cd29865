"""Empirical autotuner + perf-model calibration (DESIGN.md §9).

The paper picks formats with "a suitable performance model"; the
SELL-C-sigma follow-up (arXiv:1307.6209) shows the winning kernel
statics are hardware- AND matrix-dependent.  This package closes the
loop: enumerate the legal static space (``space``), prune it with the
model, MEASURE the survivors (``measure``), remember the decision in a
persistent cache keyed by structural fingerprint x device x dtype
policy (``cache``), and feed the measured rows back into the model as
an effective-bandwidth + per-format-overhead calibration
(``calibrate`` -> ``core.perf_model.set_calibration``).

Entry points most callers want are one level up —
``ops.as_device(a, tune="auto")`` / ``operator(a, tune="auto")`` /
``dist_operator(m, mesh, tune="auto")`` — which route here.
"""
from .space import (Candidate, enumerate_candidates, heuristic_candidate,
                    price_candidate, prune_candidates, solver_candidates,
                    dist_candidates)
from .measure import (measure_candidate, measure_solver_candidate,
                      measure_dist_candidate, prepare_candidate, ab_compare,
                      median_seconds, device_kind, measurement_backend)
from .cache import (TuneCache, default_cache, cache_key,
                    dtype_policy, RECORD_SCHEMA)
from .calibrate import (fit_calibration, model_error,
                        fit_link_calibration, link_model_error,
                        rows_from_bench_kernels, fit_from_bench_kernels)
from .autotune import (TuneResult, TunePartition, SolverTuneResult,
                       autotune, tune_partition, tune_solver)

__all__ = [
    "Candidate",
    "enumerate_candidates",
    "heuristic_candidate",
    "price_candidate",
    "prune_candidates",
    "measure_candidate",
    "prepare_candidate",
    "ab_compare",
    "median_seconds",
    "device_kind",
    "measurement_backend",
    "TuneCache",
    "RECORD_SCHEMA",
    "default_cache",
    "cache_key",
    "dtype_policy",
    "fit_calibration",
    "model_error",
    "fit_link_calibration",
    "link_model_error",
    "rows_from_bench_kernels",
    "fit_from_bench_kernels",
    "solver_candidates",
    "dist_candidates",
    "measure_solver_candidate",
    "measure_dist_candidate",
    "TuneResult",
    "TunePartition",
    "SolverTuneResult",
    "autotune",
    "tune_partition",
    "tune_solver",
]
