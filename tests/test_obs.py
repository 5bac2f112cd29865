"""Host spans (``repro.obs``), the phase times ``repro.solve`` reads from
them, and the stored-slot counts the benchmark's padding metric reads."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import obs
from repro.core import dist_spmv as D
from repro.core import matrices as M
from repro.core.operator import DistOperator, operator


@pytest.fixture
def recorder():
    """The recorder on for one test, empty before and after."""
    obs.drain()
    obs.record(True)
    yield obs
    obs.record(False)
    obs.drain()


def test_off_records_nothing():
    obs.drain()
    obs.record(False)
    with obs.span("repro.a") as s:
        pass
    assert obs.drain() == []
    assert s.end_ns >= s.start_ns and s.seconds >= 0


def test_nesting_gives_parents_and_drain_empties(recorder):
    with obs.span("repro.outer"):
        with obs.span("repro.inner"):
            pass
        with obs.span("repro.inner2"):
            pass
    kept = recorder.drain()
    assert [(n, p) for n, _, _, p in kept] == [
        ("repro.inner", "repro.outer"), ("repro.inner2", "repro.outer"),
        ("repro.outer", None)]
    outer = kept[-1]
    assert all(outer[1] <= s and e <= outer[2] for _, s, e, _ in kept[:2])
    assert recorder.drain() == []


def test_span_decorates_a_function(recorder):
    @obs.span("repro.fn")
    def f(v):
        return v + 1

    assert f(1) == 2 and f(2) == 3
    assert [n for n, *_ in recorder.drain()] == ["repro.fn", "repro.fn"]


@pytest.mark.parametrize("on", [False, True])
def test_settled_syncs_only_while_recording(monkeypatch, on):
    """``repro.transfer`` waits for its arrays only in a recorded run:
    with the recorder off, building an operator adds no host sync."""
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda tree: waited.append(tree) or tree)
    obs.drain()
    obs.record(on)
    try:
        tree = {"a": jnp.ones(3)}
        assert obs.settled(tree) is tree
    finally:
        obs.record(False)
        obs.drain()
    assert len(waited) == int(on)


def test_kept_span_and_profiler_trace_share_a_clock(recorder, tmp_path):
    """The same span, kept in memory and written by the CPU profiler,
    lies at the same wall-clock time within 1 ms: a trace's events
    count from its ``profile_start_time``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("repro.clock"):
            jnp.ones(1000).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (_, start_ns, end_ns, _), = recorder.drain()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pd = ProfileData.from_file(path)
    origin = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    traced = [(e.start_ns, e.start_ns + e.duration_ns)
              for plane in pd.planes for line in plane.lines
              for e in line.events if e.name == "repro.clock"]
    (t0, t1), = traced
    assert abs(origin + t0 - start_ns) < 1e6
    assert abs(origin + t1 - end_ns) < 1e6


def test_operator_build_spans(recorder):
    operator(M.poisson_2d(12, 12), format="sell")
    kept = {n: p for n, _, _, p in recorder.drain()}
    assert kept == {"repro.convert": "repro.operator.build",
                    "repro.transfer": "repro.operator.build",
                    "repro.operator.build": None}


def test_solve_phases_are_its_spans(recorder):
    m = M.poisson_2d(12, 12)
    b = np.ones(m.n_rows, np.float32)
    res = repro.solve(m, b, method="cg", tune="off", fallback="off")
    kept = recorder.drain()
    parents = {n: p for n, _, _, p in kept}
    assert parents["repro.solve.tune"] == "repro.solve"
    assert parents["repro.solve.build"] == "repro.solve"
    assert parents["repro.solve.iterate"] == "repro.solve"
    assert parents["repro.operator.build"] == "repro.solve.build"
    seconds = {n: (e - s) / 1e9 for n, s, e, _ in kept}
    assert res.info["phase_s"] == {
        "tune": seconds["repro.solve.tune"],
        "build": seconds["repro.solve.build"],
        "solve": seconds["repro.solve.iterate"]}


@pytest.mark.parametrize("fmt", ["csr", "ellpack_r", "sell", "pjds", "cmrs"])
def test_stored_slots_per_format(fmt):
    m = M.power_law(700)
    op = operator(m, format=fmt)
    inner = op.dev.dev
    stored = inner.data if fmt == "csr" else inner.val
    assert op.stored_slots == op.dev.stored_slots == stored.size
    assert op.stored_slots >= m.nnz


def test_stored_slots_sum_over_devices():
    m = M.poisson_2d(30, 30)
    dist = D.partition_csr(m, 4, b_r=32)
    op = DistOperator(dist, mesh=None)
    per_device = [dist.loc_val[p].size + dist.rem_val[p].size
                  for p in range(4)]
    assert op.stored_slots == sum(per_device) >= m.nnz


def test_totals_sum_every_span_with_the_recorder_off():
    obs.reset()
    obs.record(False)
    try:
        spans = []
        for _ in range(2):
            with obs.span("repro.t") as s:
                pass
            spans.append(s.seconds)
        with obs.span("repro.u") as u:
            pass
        assert obs.totals() == pytest.approx(
            {"repro.t": sum(spans), "repro.u": u.seconds})
        assert obs.drain() == []
    finally:
        obs.reset()
    assert obs.totals() == {} and obs.gauges() == {}


def test_operator_build_notes_its_stored_slots():
    obs.reset()
    try:
        op = operator(M.power_law(700), format="pjds")
        assert obs.gauges() == {"repro.stored_slots": op.stored_slots,
                                "repro.window_share": 0.0,
                                "repro.fused_unpermute": 0.0,
                                "repro.grid_fill": op.grid_fill}
        assert set(obs.totals()) == {"repro.convert", "repro.transfer",
                                     "repro.operator.build"}
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        from repro.core.operator import dist_operator
        dop = dist_operator(M.poisson_2d(12, 12), mesh, transpose=None)
        assert obs.gauges()["repro.stored_slots"] == dop.stored_slots
        assert obs.gauges()["repro.window_share"] == 0.0
        assert obs.gauges()["repro.fused_unpermute"] == 0.0
    finally:
        obs.reset()
