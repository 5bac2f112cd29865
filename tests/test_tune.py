"""Autotuner unit tests: cache semantics, fingerprint stability, prune
guarantees, calibration, and the tuned end-to-end paths."""
import dataclasses
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import formats as F, matrices as M, perf_model as PM
from repro.kernels import ops
from repro import tune as T


def _mat(n=320, density=0.05, seed=0):
    rng = np.random.default_rng(seed)
    a = ((rng.random((n, n)) < density)
         * rng.standard_normal((n, n))).astype(np.float32)
    return a, F.csr_from_dense(a)


def _model_measure(calls):
    """Deterministic stand-in for the measurement harness: 'measured'
    time = uncalibrated model price (plus a structural epsilon so ties
    break stably), recording every invocation."""
    def fn(m, c, **kw):
        calls.append(c)
        return T.price_candidate(m, c, calibration=None) \
            + (hash(c) % 7) * 1e-9
    return fn


@pytest.fixture
def cache(tmp_path):
    return T.TuneCache(tmp_path / "tune_cache.json")


@pytest.fixture(autouse=True)
def _no_global_calibration():
    yield
    PM.clear_calibration()


# --------------------------------------------------------------- cache
def test_cache_hit_skips_measurement(cache):
    _, m = _mat()
    calls = []
    r1 = T.autotune(m, cache=cache, measure_fn=_model_measure(calls))
    assert not r1.cached and len(calls) > 0
    n_first = len(calls)

    r2 = T.autotune(m, cache=cache, measure_fn=_model_measure(calls))
    assert r2.cached and len(calls) == n_first      # nothing re-measured
    assert r2.best == r1.best and r2.key == r1.key

    r3 = T.autotune(m, cache=cache, measure_fn=_model_measure(calls),
                    force=True)
    assert not r3.cached and len(calls) == 2 * n_first   # force re-measures


def test_cache_survives_reload_and_corruption(cache, tmp_path):
    _, m = _mat()
    r1 = T.autotune(m, cache=cache, measure_fn=_model_measure([]))
    # a fresh instance on the same file sees the entry
    again = T.TuneCache(cache.path)
    assert T.autotune(m, cache=again, measure_fn=_model_measure([])).cached
    # a corrupt file is an empty cache, not an error
    cache.path.write_text("{ not json")
    broken = T.TuneCache(cache.path)
    assert broken.get(r1.key) is None


def test_record_schema_quarantine_round_trip(cache):
    """Individual-record versioning: unknown schema stamps, non-dict
    records and missing required keys QUARANTINE (miss + reason, no
    crash, no silent reuse) and a re-measure ``put`` heals the entry."""
    _, m = _mat()
    r1 = T.autotune(m, cache=cache, measure_fn=_model_measure([]))
    rec = cache.get(r1.key, require=("best",))
    assert rec is not None and rec["schema"] == T.RECORD_SCHEMA

    # hand-mangle the file three ways; a fresh loader quarantines each
    payload = json.loads(cache.path.read_text())
    entries = payload["entries"]
    good = entries[r1.key]
    entries[r1.key] = {**good, "schema": 999}      # future version
    entries["k_str"] = "not a dict"
    entries["k_bare"] = {"schema": T.RECORD_SCHEMA}
    cache.path.write_text(json.dumps(payload))

    fresh = T.TuneCache(cache.path)
    assert fresh.get(r1.key) is None
    assert "schema" in fresh.quarantined[r1.key]
    assert fresh.get("k_str") is None
    assert "dict" in fresh.quarantined["k_str"]
    assert fresh.get("k_bare", require=("best",)) is None
    assert "missing" in fresh.quarantined["k_bare"]
    assert fresh.get("k_bare") is not None         # stamp alone is valid

    # the autotuner degrades to a re-measure, then the put heals it
    calls = []
    r2 = T.autotune(m, cache=fresh, measure_fn=_model_measure(calls))
    assert not r2.cached and calls
    assert r1.key not in fresh.quarantined
    assert fresh.get(r1.key, require=("best",)) is not None


def test_malformed_nested_record_quarantines(cache):
    """A record with a valid stamp but garbage INSIDE the required key
    (deserialization blows up) also degrades to a re-measure."""
    _, m = _mat()
    r1 = T.autotune(m, cache=cache, measure_fn=_model_measure([]))
    payload = json.loads(cache.path.read_text())
    payload["entries"][r1.key]["best"] = 42      # breaks from_dict
    cache.path.write_text(json.dumps(payload))

    fresh = T.TuneCache(cache.path)
    calls = []
    r2 = T.autotune(m, cache=fresh, measure_fn=_model_measure(calls))
    assert not r2.cached and calls                 # degraded to re-measure
    # ... and the re-measure's put healed the record in place
    assert r1.key not in fresh.quarantined
    healed = fresh.get(r1.key, require=("best",))
    assert isinstance(healed["best"], dict)


def test_cache_key_separates_policy_device_format():
    fp = "f" * 40
    keys = {
        T.cache_key(fp, "cpu:x", T.dtype_policy(None, "auto")),
        T.cache_key(fp, "tpu:v5e", T.dtype_policy(None, "auto")),
        T.cache_key(fp, "cpu:x", T.dtype_policy(jnp.bfloat16, "auto")),
        T.cache_key(fp, "cpu:x", T.dtype_policy(None, np.int32)),
        T.cache_key(fp, "cpu:x", T.dtype_policy(None, "auto"), "fmt=sell"),
    }
    assert len(keys) == 5


# --------------------------------------------------------- fingerprint
def test_fingerprint_stable_under_value_changes():
    _, m = _mat(seed=3)
    m2 = F.CSRMatrix(m.indptr.copy(), m.indices.copy(),
                     m.data * 7.5 + 1.0, m.shape)
    assert F.structural_fingerprint(m) == F.structural_fingerprint(m2)


def test_fingerprint_invalidates_under_structure_changes():
    _, m = _mat(seed=4)
    fp = F.structural_fingerprint(m)
    # add one entry (same values elsewhere)
    rows = np.repeat(np.arange(m.n_rows), m.row_lengths())
    m_plus = F.csr_from_coo(np.concatenate([rows, [0]]),
                            np.concatenate([m.indices, [m.n_cols - 1]]),
                            np.concatenate([m.data, [1.0]]), m.shape)
    assert F.structural_fingerprint(m_plus) != fp
    # same pattern, different shape
    m_wide = F.CSRMatrix(m.indptr, m.indices, m.data,
                         (m.shape[0], m.shape[1] + 1))
    assert F.structural_fingerprint(m_wide) != fp


# ----------------------------------------------------- space / pruning
@pytest.mark.parametrize("mk", [
    lambda: _mat(256, 0.03, 1)[1],
    lambda: M.samg(scale=0.002),
    lambda: M.power_law(1024, seed=7),
    lambda: M.poisson_2d(16, 16),
])
def test_pruning_never_drops_heuristic(mk):
    m = mk()
    heur = T.heuristic_candidate(m)
    pruned = T.prune_candidates(m, T.enumerate_candidates(m), top_k=3)
    assert heur in pruned
    assert len(pruned) <= 4      # top_k + (possibly) the appended heuristic


def test_enumerate_respects_format_restriction():
    _, m = _mat()
    cands = T.enumerate_candidates(m, format="pjds")
    assert {c.fmt for c in cands} <= {"pjds"}
    assert T.heuristic_candidate(m, format="pjds") in cands


def test_ellpack_r_tiles_are_whole_lane_widths():
    # ELLPACK-R row tiles are lane slices: the TPU compiler refuses a
    # tile of fewer than 128 lanes, so the tuner never offers one
    m = M.samg(scale=0.002)
    ell = [c for c in T.enumerate_candidates(m) if c.fmt == "ellpack_r"]
    assert ell and all(c.b_r % 128 == 0 for c in ell)
    assert any(c.fmt == "pjds" and c.b_r == 32
               for c in T.enumerate_candidates(m))


def test_degenerate_matrix_collapses_to_csr():
    a = np.zeros((8, 8), np.float32)
    m = F.csr_from_dense(a)
    cands = T.enumerate_candidates(m)
    assert all(c.fmt == "csr" for c in cands)


def test_candidate_json_roundtrip():
    c = T.Candidate(fmt="sell", b_r=64, chunk_l=8, sigma=512)
    assert T.Candidate.from_dict(json.loads(json.dumps(c.as_dict()))) == c


# ---------------------------------------------------------- calibration
def test_calibration_strictly_improves_synthetic():
    rng = np.random.default_rng(5)
    rows = []
    for i in range(30):
        fmt = ("pjds", "sell", "ellpack_r")[i % 3]
        # model times spanning 3 decades so both the scale (large rows)
        # and the per-format offset (small rows) are identifiable
        model = float(10 ** rng.uniform(-7, -4))
        true = model / 0.002 + {"pjds": 2e-4, "sell": 5e-5,
                                "ellpack_r": 0.0}[fmt]
        rows.append(dict(fmt=fmt, model_s=model,
                         measured_s=true * float(rng.uniform(0.99, 1.01))))
    err0 = T.model_error(rows)
    cal = T.fit_calibration(rows, source="synthetic")
    err1 = T.model_error(rows, cal)
    assert err1 < err0               # strict improvement
    assert err1 < 0.1                # and actually a good fit
    assert cal.bw_scale == pytest.approx(0.002, rel=0.5)
    assert cal.overhead_s.get("pjds", 0) == pytest.approx(2e-4, rel=0.5)
    assert cal.overhead_s.get("pjds", 0) > cal.overhead_s.get("sell", 0)


def test_calibration_installs_into_predicted_seconds():
    t0 = PM.predicted_spmv_seconds(10_000, 1_000, 10.0, fmt="pjds")
    cal = PM.Calibration(bw_scale=0.5, overhead_s={"pjds": 1e-3})
    PM.set_calibration(cal)
    t1 = PM.predicted_spmv_seconds(10_000, 1_000, 10.0, fmt="pjds")
    assert t1 == pytest.approx(2 * t0 + 1e-3)
    # explicit calibration=None bypasses the installed one
    assert PM.predicted_spmv_seconds(10_000, 1_000, 10.0, fmt="pjds",
                                     calibration=None) == pytest.approx(t0)
    PM.clear_calibration()
    assert PM.predicted_spmv_seconds(10_000, 1_000, 10.0,
                                     fmt="pjds") == pytest.approx(t0)


def test_calibration_improves_on_measured_rows(cache):
    """End-to-end: fit on real measured autotune rows -> the calibrated
    model error on those rows is strictly below the uncalibrated one."""
    _, m = _mat(256, 0.08, 6)
    res = T.autotune(m, cache=cache, warmup=1, iters=3)
    err0 = T.model_error(res.rows)
    cal = T.fit_calibration(res.rows)
    assert T.model_error(res.rows, cal) < err0


def test_rows_from_bench_kernels(tmp_path):
    payload = {"suite": "kernels", "rows": [
        {"kind": "bytes_per_nnz", "fmt": "pjds", "predicted_s": 1e-5,
         "measured_ref_s": 3e-4},
        {"kind": "padding", "b_r": 32},
        {"kind": "bytes_per_nnz", "fmt": "sell", "predicted_s": 2e-5,
         "measured_ref_s": 5e-4},
    ]}
    p = tmp_path / "BENCH_kernels.json"
    p.write_text(json.dumps(payload))
    rows = T.rows_from_bench_kernels(p)
    assert [r["fmt"] for r in rows] == ["pjds", "sell"]
    cal = T.fit_from_bench_kernels(p)
    assert T.model_error(rows, cal) < T.model_error(rows)


def test_link_calibration_recovers_synthetic():
    """fit_link_calibration identifies the per-message fixed cost and
    the effective link bandwidth from bulk-synchronous rows built with
    known ground truth, and strictly improves link_model_error."""
    rng = np.random.default_rng(11)
    base = {"g1": 120e-6, "g2": 210e-6}
    cost = {"gathered": 25e-6, "full": 5e-6}
    inv_bw = 1.0 / (PM.TPU_V5E.ici_bw * 0.5)       # link_bw_scale = 0.5
    rows = []
    for group in base:
        for halo in cost:
            for msgs, byts in ((2, 4e5), (4, 1.6e6), (6, 6.4e6)):
                t = base[group] + msgs * cost[halo] + byts * inv_bw
                rows.append(dict(group=group, halo=halo, msgs=msgs,
                                 bytes=byts,
                                 measured_s=t * rng.uniform(0.99, 1.01)))
    err0 = T.link_model_error(rows)
    cal = T.fit_link_calibration(rows, source="synthetic")
    err1 = T.link_model_error(rows, cal)
    assert err1 < err0 and err1 < 0.05
    assert cal.msg_overhead_s["gathered"] == pytest.approx(25e-6, rel=0.5)
    assert cal.msg_overhead_s["gathered"] > cal.msg_overhead_s.get("full", 0)
    assert cal.link_bw_scale == pytest.approx(0.5, rel=0.5)
    assert cal.source == "synthetic"


def test_link_calibration_rejects_bad_rows():
    with pytest.raises(ValueError):
        T.fit_link_calibration([])
    with pytest.raises(ValueError):
        T.fit_link_calibration([dict(group="g", halo="full", msgs=2,
                                     bytes=100, measured_s=0.0)])


def test_dist_candidates_enumeration():
    cands = T.dist_candidates(8)
    assert all(set(c) == {"grid", "halo", "mode", "halo_w"} for c in cands)
    # 1-D row partitioning is stored as grid=None
    assert any(c["grid"] is None for c in cands)
    grids = {c["grid"] for c in cands}
    assert {(1, 8), (2, 4), (4, 2)} <= grids
    # naive is dominated; a staged full exchange cannot win
    assert not any(c["mode"] == "naive" for c in cands)
    assert not any(c["mode"] == "pipeline" and c["halo"] == "full"
                   for c in cands)
    # pipeline+gathered survives — it is the tentpole configuration
    assert any(c["mode"] == "pipeline" and c["halo"] == "gathered"
               for c in cands)
    # deduped
    keys = [tuple(sorted(c.items(), key=lambda kv: kv[0])) for c in cands]
    assert len(keys) == len(set(keys))
    # degenerate mesh still enumerates
    assert all(c["grid"] is None for c in T.dist_candidates(1))


# ------------------------------------------------- end-to-end threading
def test_as_device_tune_auto_builds_tuned_statics(cache, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache.path))
    a, m = _mat(256, 0.05, 7)
    calls = []
    # seed the persistent cache through the injected harness, then let
    # as_device pick the decision up from disk
    res = T.autotune(m, cache=T.TuneCache(cache.path),
                     measure_fn=_model_measure(calls))
    sd = ops.as_device(m, tune="auto")
    assert sd.fmt == res.best.fmt
    d = sd.dev
    if res.best.fmt in ("pjds", "sell"):
        assert d.b_r == res.best.b_r and d.chunk_l == res.best.chunk_l
    x = np.random.default_rng(1).standard_normal(m.shape[1]).astype(np.float32)
    truth = a.astype(np.float64) @ x
    from repro.core.operator import operator
    y = np.asarray(operator(m, tune="auto") @ jnp.asarray(x), np.float64)
    scale = max(np.abs(truth).max(), 1.0)
    np.testing.assert_allclose(y / scale, truth / scale, atol=1e-5)


def test_operator_tune_auto_parity(cache, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache.path))
    from repro.core.operator import operator
    a, m = _mat(192, 0.06, 8)
    T.autotune(m, cache=T.TuneCache(cache.path),
               measure_fn=_model_measure([]))
    op = operator(m, tune="auto")
    x = np.random.default_rng(2).standard_normal(m.shape[1]).astype(np.float32)
    truth = a.astype(np.float64) @ x
    scale = max(np.abs(truth).max(), 1.0)
    np.testing.assert_allclose(np.asarray(op @ jnp.asarray(x)) / scale,
                               truth / scale, atol=1e-5)


def test_bad_tune_value_raises():
    _, m = _mat(64, 0.1, 9)
    with pytest.raises(ValueError):
        ops.as_device(m, tune="always")


# ------------------------------------------------------- partition tune
def test_tune_partition_independent_and_cached(cache):
    m = M.poisson_2d(24, 24)
    tp = T.tune_partition(m, 4, b_r=32, cache=cache, iters=2)
    assert not tp.cached
    assert tp.chunk_l in (8, 16, 32) and tp.rem_chunk_l in (8, 16, 32)
    operands = {r["operand"] for r in tp.rows}
    assert operands == {"loc", "rem"}        # both measured, independently
    tp2 = T.tune_partition(m, 4, b_r=32, cache=cache, iters=2)
    assert tp2.cached and (tp2.chunk_l, tp2.rem_chunk_l) == \
        (tp.chunk_l, tp.rem_chunk_l)
    # a different geometry is a different cache entry
    tp3 = T.tune_partition(m, 2, b_r=32, cache=cache, iters=2)
    assert not tp3.cached


def test_partition_rem_chunk_l_matches_shared_build():
    """rem_chunk_l == chunk_l must reproduce the shared-tile partition
    bit-for-bit (the tuned path degenerates cleanly)."""
    from repro.core import dist_spmv as D
    m = M.poisson_2d(16, 16)
    d_shared = D.partition_csr(m, 2, b_r=32, chunk_l=8)
    d_tuned = D.partition_csr(m, 2, b_r=32, chunk_l=8, rem_chunk_l=8)
    assert d_tuned.rem_chunk_l is None       # canonicalised
    np.testing.assert_array_equal(np.asarray(d_shared.rem_val),
                                  np.asarray(d_tuned.rem_val))
    np.testing.assert_array_equal(np.asarray(d_shared.rem_chunk_map),
                                  np.asarray(d_tuned.rem_chunk_map))


# --------------------------------------------------------------- solver tune
def _solver_measure(calls, fused_s=1e-6, composed_s=2e-6):
    """Injected stand-in for measure_solver_candidate: fused always wins,
    every invocation recorded."""
    def fn(m, strategy, c, **kw):
        calls.append((strategy, c.label()))
        return (fused_s if strategy == "fused" else composed_s) \
            + (hash((strategy, c)) % 7) * 1e-12
    return fn


def test_tune_solver_cached_under_method_key(cache):
    m = M.poisson_2d(16, 16)
    calls = []
    st1 = T.tune_solver(m, method="cg", cache=cache,
                        measure_fn=_solver_measure(calls))
    assert not st1.cached and len(calls) > 0
    assert st1.strategy == "fused"           # the injected winner
    assert {s for s, _ in calls} == {"fused", "composed"}
    n_first = len(calls)

    st2 = T.tune_solver(m, method="cg", cache=cache,
                        measure_fn=_solver_measure(calls))
    assert st2.cached and len(calls) == n_first      # nothing re-measured
    assert (st2.strategy, st2.layout) == (st1.strategy, st1.layout)
    assert st2.key == st1.key

    # the method is part of the cache key: bicgstab tunes independently
    st3 = T.tune_solver(m, method="bicgstab", cache=cache,
                        measure_fn=_solver_measure(calls))
    assert not st3.cached and st3.key != st1.key

    # force re-measures through the same key
    st4 = T.tune_solver(m, method="cg", cache=cache, force=True,
                        measure_fn=_solver_measure(calls))
    assert not st4.cached and st4.key == st1.key


def test_tune_solver_picks_composed_when_it_wins(cache):
    m = M.poisson_2d(12, 12)
    st = T.tune_solver(m, method="cg", cache=cache,
                       measure_fn=_solver_measure([], fused_s=5e-6,
                                                  composed_s=1e-6))
    assert st.strategy == "composed"
    # every row records (strategy, layout, seconds) for diagnostics
    assert all({"strategy", "layout", "seconds_per_iter"} <= set(r)
               for r in st.rows)
