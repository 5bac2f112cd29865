"""Device time by program scope (``chipbench/scopes.py``) and the
per-layer readers built on it and on the program's host spans and
gauges, on the CPU: a recorded TPU v5e trace, a hand-made trace joined
to the HLO of the power loop's real step, and hand-made inputs."""
import json
import pathlib
import re
import shutil
import sys
import time

import pytest

from chipbench import run as RUN
from chipbench import scopes as S
from chipbench import trace as T

HERE = pathlib.Path(__file__).resolve().parent
RECORDED = HERE / "data" / "dlr1.spmvm.scoped.xplane.pb"
RECORDED_APPLIES = 25
SCOPES = ("repro.gather_rhs", "repro.kernel", "repro.unpermute")
DEVICE_READERS = ("gather_ms.spmvm", "kernel_ms.spmvm", "unpermute_ms.spmvm")
NEW_READERS = DEVICE_READERS + ("slots_per_nnz.spmvm", "convert_s")


def test_recorded_tpu_trace_names_its_ops():
    """The trace's own metadata gives each op of the step its path."""
    paths = S.op_paths(RECORDED)
    assert "/device:TPU:0" in paths
    tpu = paths["/device:TPU:0"]
    gather = [p for op, p in tpu.items() if op.startswith("%fusion.1 = ")]
    assert gather and S.scope_of(gather[0]) == "repro.gather_rhs"
    kernel = [p for op, p in tpu.items() if "pjds_spmv" in op]
    assert kernel and S.scope_of(kernel[0]) == "repro.kernel"


def test_recorded_tpu_trace_by_scope():
    """A run of dlr1.spmvm on a TPU v5e (``--seed 3000000013 --seconds 3
    --trace 1``, 25 applies; its source paths made relative to the
    checkout): the scopes and ``other`` add up to the busy time
    ``trace.reduce`` gives, the gather, the kernel and the unpermute
    cover 99% of it."""
    sc = S.read(RECORDED)
    summary = T.reduce(T.load(RECORDED))
    assert sc.busy_s == pytest.approx(summary.busy_s, rel=1e-12)
    assert sc.window_s == summary.window_s
    assert set(sc.seconds) == set(SCOPES) | {S.OTHER}
    assert sum(sc.seconds.values()) == pytest.approx(sc.busy_s, rel=1e-6)
    assert sum(sc.seconds[k] for k in SCOPES) >= 0.99 * sc.busy_s
    gather_ms = 1000 * sc.seconds["repro.gather_rhs"] / RECORDED_APPLIES
    assert gather_ms == pytest.approx(366.5, rel=0.01)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_steps)/while/body/repro.gather_rhs/gather:", "repro.gather_rhs"),
    ("jit(f)/repro.kernel/jit(searchsorted)/repro.unpermute/x",
     "repro.unpermute"),
    ("jit(_steps)/while/body/repro.kernel:", "repro.kernel"),
    ("jit(_steps)/while/body/div:", S.OTHER),
    (None, S.OTHER),
])
def test_scope_of_takes_the_innermost(tf_op, scope):
    assert S.scope_of(tf_op) == scope


@pytest.fixture(scope="module")
def steps_program():
    """The HLO text of the power loop's real step, compiled on the CPU
    for a small sAMG in pJDS (the format the chip picks for it at full
    size) with the Pallas kernels forced (interpret mode)."""
    import jax
    import jax.numpy as jnp

    from chipbench import matrices
    from chipbench.loops import power
    from repro.core.formats import CSRMatrix
    from repro.core.operator import operator

    cfg = json.loads((HERE.parent / "configs" / "samg.json").read_text())
    m = matrices.build(cfg, 3, scale=0.001)
    op = operator(CSRMatrix(m.indptr, m.indices, m.data,
                            (m.n_rows, m.n_rows)),
                  format="pjds", backend="kernel")
    x = jnp.ones(op.shape[1], jnp.float32)
    step = jax.jit(power._steps, static_argnums=(2, 3))
    return step.lower(op, x, 1, m.n_rows).compile().as_text()


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def _op_names(hlo_text):
    """{instruction: op_name} of a compiled program's HLO text."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}


def test_the_real_step_carries_the_three_scopes(steps_program):
    found = {S.scope_of(p) for p in _op_names(steps_program).values()}
    assert set(SCOPES) <= found


def _scoped_events(op_names):
    """One device plane: an unscoped ``while`` that holds an op of each
    scope in turn, then an op under none (the norm) that the window's
    close cuts in half; the paths as a trace's metadata gives them, from
    the program's own op names."""
    first = {}
    for instr, path in op_names.items():
        first.setdefault(S.scope_of(path), instr)
    ops, paths, t = [["%while.1 = f32[8]{0} while(f32[8] %p)", 90, 380]], {}, 100
    for scope, dur in zip(SCOPES, (300, 20, 50)):
        name = f"%{first[scope]} = f32[8]{{0}} fusion(f32[8] %p)"
        ops.append([name, t, dur])
        paths[name] = op_names[first[scope]]
        t += dur
    ops.append(["%norm.9 = f32[] fusion(f32[8] %y), kind=kLoop", t, 100])
    events = {"device": {"/device:TPU:0": {
                  "ops": ops, "modules": [["jit__steps(7)", 90, 480]]}},
              "host": [["chipbench.window", 0, 520, "python"],
                       ["chipbench.dispatch", 0, 520, "python"]]}
    return events, {"/device:TPU:0": paths}


def test_scope_times_add_up_to_busy(steps_program):
    events, paths = _scoped_events(_op_names(steps_program))
    sc = S.reduce(events, paths)
    # the while's own time is 90..100, before its body; the norm runs
    # 470..570 and the window closes at 520: half of it counts
    assert sc.seconds == pytest.approx({
        "repro.gather_rhs": 300e-9, "repro.kernel": 20e-9,
        "repro.unpermute": 50e-9, S.OTHER: 50e-9 + 10e-9})
    assert sc.busy_s == pytest.approx(T.reduce(events).busy_s)
    assert sum(sc.seconds.values()) == pytest.approx(sc.busy_s)
    # without the trace's op paths every op is ``other``
    assert S.reduce(events, {}).seconds == pytest.approx(
        {S.OTHER: sc.busy_s})


def test_scopes_average_over_device_planes():
    one = {"ops": [["%a = f32[]", 0, 100]], "modules": []}
    two = {"ops": [["%a = f32[]", 0, 300]], "modules": []}
    events = {"device": {"/device:TPU:0": one, "/device:TPU:1": two},
              "host": [["chipbench.window", 0, 1000, "python"]]}
    paths = {"/device:TPU:0": {"%a = f32[]": "jit(f)/repro.halo/x"},
             "/device:TPU:1": {}}
    sc = S.reduce(events, paths)
    assert sc.seconds == pytest.approx({"repro.halo": 50e-9,
                                        S.OTHER: 150e-9})
    assert sc.busy_s == pytest.approx(200e-9)


@pytest.fixture
def traces(tmp_path, monkeypatch):
    """An empty trace directory of the harness's layout, read by
    ``scopes.for_run`` in place of the checkout's."""
    monkeypatch.setattr(S, "TRACES", tmp_path)
    S._read_cache.clear()
    yield tmp_path
    S._read_cache.clear()


def _place(traces, cell="dlr1.spmvm"):
    out = traces / cell / "plugins" / "profile" / "2026_01_01" / "h.xplane.pb"
    out.parent.mkdir(parents=True)
    shutil.copy(RECORDED, out)
    return out


def _ctx(**kw):
    return {"counters": {}, "trace": None, "peaks": None, "n_rows": 10,
            "nnz": 20, **kw}


def test_for_run_takes_only_the_run_s_own_trace(traces):
    summary = T.reduce(T.load(_place(traces)))
    assert S.for_run(_ctx(trace=summary)).busy_s == summary.busy_s
    other = T.Summary(busy_s=summary.busy_s / 2, window_s=summary.window_s,
                      op_seconds={}, gaps=[])
    assert S.for_run(_ctx(trace=other)) is None
    assert S.for_run(_ctx()) is None


def test_device_readers_read_the_recorded_trace(traces):
    summary = T.reduce(T.load(_place(traces)))
    ctx = _ctx(trace=summary, counters={"applies": RECORDED_APPLIES})
    read = {k: RUN._reader(k)(ctx) for k in DEVICE_READERS}
    sc = S.read(RECORDED)
    assert read == pytest.approx({
        f"{name}": 1000 * sc.seconds[scope] / RECORDED_APPLIES
        for name, scope in zip(DEVICE_READERS, SCOPES)})
    assert read["gather_ms.spmvm"] == pytest.approx(366.5, rel=0.01)


@pytest.fixture
def fresh_obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_without_its_input_reads_nothing(metric, traces,
                                                    fresh_obs):
    read = RUN._reader(metric)
    assert read(_ctx()) is None
    # a trace of a program without scopes, no gauge, no span
    bare = T.Summary(busy_s=1.0, window_s=1.0, op_seconds={}, gaps=[])
    assert read(_ctx(trace=bare, counters={"applies": 3})) is None


@pytest.mark.parametrize("metric", ["slots_per_nnz.spmvm", "convert_s"])
def test_program_readers_without_the_program_s_module_read_nothing(
        metric, monkeypatch, fresh_obs):
    fresh_obs.gauge("repro.stored_slots", 50)
    with fresh_obs.span("repro.convert"):
        pass
    import repro
    monkeypatch.delattr(repro, "obs")                     # import fails
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert RUN._reader(metric)(_ctx()) is None


def test_program_readers_read_the_gauge_and_the_spans(fresh_obs):
    fresh_obs.gauge("repro.stored_slots", 50)
    for _ in range(2):
        with fresh_obs.span("repro.convert"):
            time.sleep(0.01)
    with fresh_obs.span("repro.transfer"):
        time.sleep(0.05)
    assert RUN._reader("slots_per_nnz.spmvm")(_ctx()) == 2.5
    convert = RUN._reader("convert_s")(_ctx())
    assert 0.02 <= convert < 0.05
