"""The tuning drivers: enumerate -> prune -> measure -> cache.

:func:`autotune` is the single-device driver behind
``ops.as_device(..., tune=...)`` /
``operator(..., tune=...)``; :func:`tune_partition` is the distributed
driver behind ``dist_operator(..., tune=...)``, which chooses the
``chunk_l`` of the LOCAL and REMOTE operands independently — their
row-length statistics differ structurally (the remote part holds only
the halo coupling, typically far sparser rows), so one shared tile
height wastes padding on one of them.

Both drivers go through the persistent :class:`cache.TuneCache`; a hit
returns the stored decision without building or measuring anything.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import perf_model as PM
from repro.kernels import ops
from . import cache as C
from . import measure as ME
from .space import (Candidate, enumerate_candidates, heuristic_candidate,
                    price_candidate, prune_candidates, solver_candidates)

__all__ = ["TuneResult", "TunePartition", "SolverTuneResult",
           "autotune", "tune_partition", "tune_solver"]

_DEFAULT_TOP_K = 6


@dataclasses.dataclass
class TuneResult:
    """Outcome of one :func:`autotune` call.  ``rows`` carries one dict
    per measured candidate (statics + uncalibrated ``model_s`` +
    ``measured_s``) — the input ``calibrate.fit_calibration`` wants —
    and ``cached`` says whether measurement was skipped entirely."""

    best: Candidate
    rows: list
    cached: bool
    key: str

    @property
    def heuristic_row(self) -> Optional[dict]:
        for r in self.rows:
            if r.get("heuristic"):
                return r
        return None


def autotune(
    m: F.CSRMatrix,
    *,
    format: str = "auto",
    dtype=None,
    index_dtype="auto",
    top_k: int = _DEFAULT_TOP_K,
    warmup: int = 1,
    iters: int = 5,
    cache: Optional[C.TuneCache] = None,
    force: bool = False,
    measure_fn: Optional[Callable] = None,
    spec: PM.TPUSpec = PM.TPU_V5E,
) -> TuneResult:
    """Pick measured-best kernel statics for ``m`` under the given
    format restriction and dtype policy.

    Cache semantics: the key is (structural fingerprint, device kind,
    dtype policy, format restriction).  ``force=False`` returns a hit
    verbatim — zero builds, zero measurements; ``force=True``
    re-measures and overwrites.  ``measure_fn`` (same signature as
    ``measure.measure_candidate``) exists for tests and custom
    harnesses.

    A winner other than the heuristic default is CONFIRMED by a
    drift-robust paired comparison (``measure.ab_compare``) before it
    is cached; if it cannot beat the heuristic head-to-head the
    heuristic is kept — so a cached tuned decision is never a one-sided
    timing artifact.  (Skipped under an injected ``measure_fn``: custom
    harnesses own their noise model.)"""
    if cache is None:
        cache = C.default_cache()
    key = C.cache_key(F.structural_fingerprint(m), ME.device_kind(),
                      C.dtype_policy(dtype, index_dtype),
                      extra=f"fmt={format}" if format != "auto" else "")
    if not force:
        hit = cache.get(key, require=("best",))
        if hit is not None:
            try:
                return TuneResult(best=Candidate.from_dict(hit["best"]),
                                  rows=list(hit.get("rows", [])),
                                  cached=True, key=key)
            except (AttributeError, KeyError, TypeError, ValueError):
                cache.quarantined[key] = "malformed 'best' candidate"


    heur = heuristic_candidate(m, format, dtype, index_dtype)
    cands = prune_candidates(
        m, enumerate_candidates(m, format, dtype, index_dtype),
        top_k=top_k, dtype=dtype, index_dtype=index_dtype, spec=spec,
        heuristic=heur)
    confirm = measure_fn is None
    if measure_fn is None:
        measure_fn = ME.measure_candidate
    rows = []
    for c in cands:
        t = measure_fn(m, c, dtype=dtype, index_dtype=index_dtype,
                       warmup=warmup, iters=iters)
        rows.append({
            **c.as_dict(),
            "label": c.label(),
            "heuristic": c == heur,
            "model_s": price_candidate(m, c, dtype=dtype,
                                       index_dtype=index_dtype, spec=spec,
                                       calibration=None),
            "measured_s": float(t),
        })
    best = cands[int(np.argmin([r["measured_s"] for r in rows]))]
    if confirm and best != heur:
        t_h, t_b = ME.ab_compare(m, heur, best, dtype=dtype,
                                 index_dtype=index_dtype,
                                 rounds=5, iters=max(iters // 2, 2),
                                 warmup=warmup)
        if t_b >= t_h:
            best = heur
    cache.put(key, {"best": best.as_dict(), "rows": rows})
    return TuneResult(best=best, rows=rows, cached=False, key=key)


# --------------------------------------------------------------------------
# Solver-level tuning
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SolverTuneResult:
    """Outcome of one :func:`tune_solver` call: the iteration STRATEGY
    (``"fused"`` / ``"composed"``) and the layout to build it on, plus
    one row per measured (strategy, layout) probe."""

    strategy: str
    layout: Candidate
    rows: list
    cached: bool
    key: str


def tune_solver(
    m: F.CSRMatrix,
    *,
    method: str = "cg",
    dtype=None,
    index_dtype="auto",
    probe_iters: int = 20,
    warmup: int = 1,
    iters: int = 3,
    cache: Optional[C.TuneCache] = None,
    force: bool = False,
    measure_fn: Optional[Callable] = None,
) -> SolverTuneResult:
    """Pick the measured-best (strategy, layout) for running ``method``
    on ``m`` — the config that wins per solver ITERATION, not per
    matvec: the fused spMV+dots pass amortizes differently than a bare
    matvec (no separate reduction passes, but an extra weight-slab read
    per window), so the per-matvec winner is not automatically the
    per-iteration winner.

    Same cache discipline as :func:`autotune` (persistent, keyed on the
    structural fingerprint + device kind + dtype policy, with the
    method as the ``extra`` component so cg and bicgstab tune
    independently); ``measure_fn`` (signature of
    ``measure.measure_solver_candidate``) exists for tests."""
    if cache is None:
        cache = C.default_cache()
    key = C.cache_key(F.structural_fingerprint(m), ME.device_kind(),
                      C.dtype_policy(dtype, index_dtype),
                      extra=f"solver:method={method}")
    if not force:
        hit = cache.get(key, require=("strategy", "layout"))
        if hit is not None:
            try:
                return SolverTuneResult(
                    strategy=str(hit["strategy"]),
                    layout=Candidate.from_dict(hit["layout"]),
                    rows=list(hit.get("rows", [])), cached=True, key=key)
            except (AttributeError, KeyError, TypeError, ValueError):
                cache.quarantined[key] = "malformed 'layout' candidate"


    if measure_fn is None:
        measure_fn = ME.measure_solver_candidate
    cands = solver_candidates(m, method=method, dtype=dtype,
                              index_dtype=index_dtype)
    rows = []
    for strategy, c in cands:
        t = measure_fn(m, strategy, c, method=method, dtype=dtype,
                       index_dtype=index_dtype, probe_iters=probe_iters,
                       warmup=warmup, iters=iters)
        rows.append({"strategy": strategy, "layout": c.as_dict(),
                     "label": f"{strategy}: {c.label()}",
                     "seconds_per_iter": float(t)})
    best = rows[int(np.argmin([r["seconds_per_iter"] for r in rows]))]
    cache.put(key, {"strategy": best["strategy"], "layout": best["layout"],
                    "rows": rows})
    return SolverTuneResult(strategy=best["strategy"],
                            layout=Candidate.from_dict(best["layout"]),
                            rows=rows, cached=False, key=key)


# --------------------------------------------------------------------------
# Distributed-partition tuning
# --------------------------------------------------------------------------
@dataclasses.dataclass
class TunePartition:
    """Independently chosen tile heights for a row partition's local
    (block-diagonal) and remote (halo-coupling) operands, plus — when
    the sweep ran over a mesh — the measured-best communication config
    (``halo`` flavour, execution ``mode``, 2-D ``grid`` shape; ``None``
    each when no mesh was given, ``grid=None`` also meaning the 1-D
    ``(n_dev, 1)`` winner)."""

    chunk_l: int
    rem_chunk_l: int
    rows: list
    cached: bool
    key: str
    halo: Optional[str] = None
    mode: Optional[str] = None
    grid: Optional[tuple] = None


def _measure_operand(sub: F.CSRMatrix, perm: np.ndarray, b_r: int,
                     diag_align: int, chunk_l: int, index_dtype,
                     warmup: int, iters: int) -> float:
    """Median seconds of one spMVM over a single device's operand built
    the exact way ``partition_csr`` builds it (shared windowed perm,
    then pJDS blocking at this chunk_l)."""
    pj = F._pjds_with_perm(sub, perm, b_r, max(diag_align, chunk_l),
                           False, index_dtype)
    dev = ops.to_device_pjds(pj, chunk_l=chunk_l)
    backend = ME.measurement_backend()
    rng = np.random.default_rng(ME.MEASURE_SEED)
    x = jnp.asarray(rng.standard_normal(sub.shape[1]).astype(np.float32))
    f = jax.jit(lambda v: ops.pjds_matvec(dev, v, backend=backend))
    return ME.median_seconds(f, x, warmup=warmup, iters=iters)


def tune_partition(
    m: F.CSRMatrix,
    n_dev: int,
    *,
    b_r: int = 128,
    diag_align: int = 8,
    sigma: Optional[int] = None,
    index_dtype="auto",
    chunk_l_options: Sequence[int] = (8, 16, 32),
    warmup: int = 1,
    iters: int = 3,
    cache: Optional[C.TuneCache] = None,
    force: bool = False,
    mesh=None,
    axis: str = "data",
    comm_candidates: Optional[Sequence[dict]] = None,
) -> TunePartition:
    """Measure the best ``chunk_l`` for the local and remote operands of
    an ``n_dev``-way row partition of ``m``, independently.

    The straggler device decides distributed step time, so measurement
    runs on the device whose operand stores the most (separately for
    local and remote — they need not be the same device), with the SAME
    shared total-row-length windowed sort ``partition_csr`` will use.
    The result feeds ``partition_csr(..., chunk_l=, rem_chunk_l=)``
    through ``core.operator.dist_operator(tune=...)``.

    With a ``mesh`` the tuner additionally sweeps the COMMUNICATION
    config — halo flavour x execution mode x 2-D grid shape
    (``space.dist_candidates``, or an explicit ``comm_candidates``
    list) — by timing one full sharded spMVM per candidate with the
    chunk winners baked in, and returns the measured-best triple in
    ``.halo`` / ``.mode`` / ``.grid``.  The sweep rows double as
    ``calibrate.fit_link_calibration`` input (each carries the
    candidate's ``msgs`` / ``bytes`` wire statistics), so one tuning
    pass also yields the calibrated gathered-vs-full crossover model.
    """
    from repro.core import dist_spmv as D   # deferred: dist_spmv imports ops
    from .space import dist_candidates as _dist_cands

    if cache is None:
        cache = C.default_cache()
    sweep = mesh is not None
    if sweep and comm_candidates is None:
        comm_candidates = _dist_cands(n_dev)
    comm_sig = ""
    if sweep:
        comm_sig = ":comm=" + ";".join(
            f"{c.get('grid')}/{c['halo']}/{c['mode']}/{c.get('halo_w')}"
            for c in comm_candidates)
    key = C.cache_key(
        F.structural_fingerprint(m), ME.device_kind(),
        C.dtype_policy(None, index_dtype),
        extra=(f"partition:n_dev={n_dev}:b_r={b_r}:sigma={sigma}"
               f":da={diag_align}"
               f":cl={','.join(map(str, chunk_l_options))}" + comm_sig))
    require = (("chunk_l", "rem_chunk_l", "halo", "mode")
               if sweep else ("chunk_l", "rem_chunk_l"))
    if not force:
        hit = cache.get(key, require=require)
        if hit is not None:
            try:
                return TunePartition(
                    chunk_l=int(hit["chunk_l"]),
                    rem_chunk_l=int(hit["rem_chunk_l"]),
                    rows=list(hit.get("rows", [])), cached=True, key=key,
                    halo=hit.get("halo"), mode=hit.get("mode"),
                    grid=(tuple(hit["grid"]) if hit.get("grid") else None))
            except (TypeError, ValueError):
                cache.quarantined[key] = "malformed chunk_l record"


    n_pad = D.padded_global_size(m.n_rows, n_dev, b_r)
    n_loc = n_pad // n_dev
    slices = [D._csr_row_slice(m, p * n_loc, (p + 1) * n_loc, n_loc)
              for p in range(n_dev)]
    needs = [F.csr_remote_columns_by_distance(sl, p, n_loc, n_dev)
             for p, sl in enumerate(slices)]
    halo_w = min(max((max((abs(d) for d in nd), default=0) for nd in needs),
                     default=0), n_dev // 2)
    sig = max(min(int(sigma) if sigma is not None else 8 * b_r, n_loc), 1)

    splits = [D._split_loc_rem(sl, p, n_loc, n_dev, halo_w)
              for p, sl in enumerate(slices)]
    perms = [F.windowed_sort_perm(loc.row_lengths() + rem.row_lengths(), sig)
             for loc, rem in splits]
    p_loc = int(np.argmax([loc.nnz for loc, _ in splits]))
    p_rem = int(np.argmax([rem.nnz for _, rem in splits]))

    rows, best = [], {}
    for which, p in (("loc", p_loc), ("rem", p_rem)):
        sub = splits[p][0 if which == "loc" else 1]
        for cl in chunk_l_options:
            t = _measure_operand(sub, perms[p], b_r, diag_align, cl,
                                 index_dtype, warmup, iters)
            rows.append(dict(operand=which, device=p, chunk_l=cl,
                             measured_s=float(t)))
            if t < best.get(which, (np.inf,))[0]:
                best[which] = (t, cl)
    chunk_l, rem_chunk_l = best["loc"][1], best["rem"][1]

    halo = mode = grid = None
    if sweep:
        comm_rows = []
        for cand in comm_candidates:
            r = ME.measure_dist_candidate(
                m, mesh, cand, axis=axis, b_r=b_r, diag_align=diag_align,
                chunk_l=chunk_l, rem_chunk_l=rem_chunk_l, sigma=sigma,
                index_dtype=index_dtype, warmup=warmup, iters=iters)
            r["operand"] = "comm"
            r["group"] = F.structural_fingerprint(m)
            comm_rows.append(r)
        w = comm_rows[int(np.argmin([r["measured_s"] for r in comm_rows]))]
        halo, mode = w["halo"], w["mode"]
        grid = tuple(w["grid"]) if w.get("grid") else None
        rows += comm_rows

    cache.put(key, {"chunk_l": chunk_l, "rem_chunk_l": rem_chunk_l,
                    "halo": halo, "mode": mode,
                    "grid": list(grid) if grid else None, "rows": rows})
    return TunePartition(chunk_l=chunk_l, rem_chunk_l=rem_chunk_l,
                         rows=rows, cached=False, key=key,
                         halo=halo, mode=mode, grid=grid)
