"""The ``fused_unpermute.spmvm`` reader: the program's gauge
``repro.fused_unpermute`` when the program notes it, nothing
otherwise."""
import sys

import pytest

from chipbench import run as RUN


def _ctx():
    return {"counters": {}, "trace": None, "peaks": None, "n_rows": 10,
            "nnz": 20}


@pytest.fixture
def fresh_obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


def _read():
    return RUN._reader("fused_unpermute.spmvm")(_ctx())


def test_reads_nothing_without_the_gauge(fresh_obs):
    fresh_obs.gauge("repro.window_share", 0.9)
    assert _read() is None


def test_reads_nothing_without_the_program_s_module(monkeypatch, fresh_obs):
    fresh_obs.gauge("repro.fused_unpermute", 1.0)
    import repro
    monkeypatch.delattr(repro, "obs")                     # import fails
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read() is None


@pytest.mark.parametrize("share", [0.0, 1.0])
def test_reads_the_gauge(fresh_obs, share):
    """An operator that unpermutes in XLA notes 0, which is a reading."""
    fresh_obs.gauge("repro.fused_unpermute", share)
    assert _read() == share


def test_reads_what_the_operator_build_notes(fresh_obs):
    from repro.core import matrices as M
    from repro.core.operator import operator
    op = operator(M.poisson_2d(40, 40), format="wsell")
    assert _read() == op.fused_unpermute == 1.0
    operator(M.poisson_2d(40, 40), format="sell")
    assert _read() == 0.0
