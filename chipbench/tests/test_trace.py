"""The reduction from a profiler trace to busy time, idle share, op
times and labelled gaps, on a hand-made trace and on a small trace
recorded on a TPU v5e."""
import gzip
import json
import pathlib

import pytest

from chipbench import trace as T

HERE = pathlib.Path(__file__).resolve().parent


def _hand_made():
    # Window 0..1000 ns.  Device ops (overlapping pair, one op partly
    # outside the window): busy = [100,300] + [400,500] + [900,1000].
    return {
        "device": {"/device:TPU:0": {
            "ops": [["%gather = f32[8]{0} fusion(f32[4] %x), kind=kLoop", 100, 150],
                    ["%k.1 = f32[8,128]{1,0} custom-call(f32[8] %g)", 200, 100],
                    ["%while.2 = (f32[], s32[]) while((f32[], s32[]) %t)", 400, 100],
                    ["%norm = f32[] fusion(f32[8] %y), kind=kLoop", 420, 50],
                    ["late", 900, 300], ["early", -50, 20]],
            "modules": [["jit_step(123)", 90, 420], ["jit_tail(9)", 890, 400]]}},
        "host": [
            ["chipbench.window", 0, 1000, "python"],
            ["chipbench.dispatch", 0, 350, "python"],
            ["chipbench.dispatch", 350, 650, "python"],
            ["PjitFunction(step)", 350, 40, "python"],
            ["PjitFunction(hash)", 520, 300, "python"],
            ["PjitFunction(other)", 500, 400, "worker"],
        ],
    }


def test_busy_idle_and_op_times():
    s = T.reduce(_hand_made())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.op_seconds == pytest.approx({
        "jit_step/gather f32[8] fusion kLoop": 150e-9,
        "jit_step/k.1 f32[8,128] custom-call": 100e-9,
        "jit_step/while.2 tuple while": 50e-9,     # self time: less its body
        "jit_step/norm f32[] fusion kLoop": 50e-9,
        "jit_tail/late": 100e-9})


def test_gaps_longest_first_with_the_span_around_them():
    s = T.reduce(_hand_made())
    # gaps: [500,900] 400 ns, [0,100] 100 ns, [300,400] 100 ns
    assert [round(g * 1e9) for _, g in s.gaps] == [400, 100, 100]
    assert s.gaps[0][0] == "chipbench.dispatch > PjitFunction(hash)"
    labels = {lab for lab, _ in s.gaps[1:]}
    assert labels == {"chipbench.dispatch",
                      "chipbench.dispatch > PjitFunction(step)"}
    b = s.breakdown(top=2)
    assert [k for k, _ in b["device_ops"]] == [
        "jit_step/gather f32[8] fusion kLoop", "jit_step/k.1 f32[8,128] custom-call"]
    assert len(b["idle_gaps"]) == 2


def test_needs_one_window_and_a_device_op():
    ev = _hand_made()
    with pytest.raises(ValueError):
        T.reduce({**ev, "device": {"/device:TPU:0": {"ops": [], "modules": []}}})
    with pytest.raises(ValueError):
        T.reduce({**ev, "host": ev["host"][1:]})


def test_recorded_tpu_trace():
    """A --trace 1 run of dlr1.spmvm on a TPU v5e (10 s window, 27
    applies): the RHS gather fusion takes nearly all the busy time, and
    self times add up to the busy time, as ops on one core do not
    overlap."""
    s = T.reduce(T.load(HERE / "data" / "dlr1.spmvm.xplane.pb"))
    assert s.window_s == pytest.approx(10.114144692, rel=1e-9)
    assert s.busy_s == pytest.approx(10.072432823, rel=1e-9)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s, rel=1e-3)
    top, secs = s.breakdown()["device_ops"][0]
    assert top.endswith("f32[42541056] fusion kCustom") and secs > 0.9 * s.busy_s
    assert all(lab.startswith(T.SPAN_PREFIX) for lab, _ in s.gaps)
