"""The harness, driven on the CPU at a small size with the chip check
skipped: sound runs come out correct, and each fault a cell can have,
planted under the timed path, makes ``correct`` false.  Also the exits
without a chip."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from chipbench import run as RUN

ROOT = pathlib.Path(__file__).resolve().parents[2]
SCALE = 0.002          # ~6.8k sAMG rows, ~560 DLR1 rows (held at 1024)
SEED = 2**31 + 977     # larger than 32 signed bits hold


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test traces its own programs: a fault planted under the timed
    path must not be served a program traced before it."""
    import jax
    jax.clear_caches()


# The CG-stream cell's harness (``loops/solve.py``, ``traffic/cg_stream.json``,
# ``limits/samg.cg.json``) stands ready for the cell that BENCHMARK.json
# leaves out for now; the tests run it from this entry.
CG_CELL = {"name": "samg.cg", "config": "samg", "traffic": "cg_stream",
           "chips": 1, "why": "closed-loop repro.solve CG on sAMG"}


def _bench():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if all(w["name"] != CG_CELL["name"] for w in bench["workloads"]):
        bench["workloads"].append(CG_CELL)
    return bench


def _run(workload, **kw):
    return RUN.run_cell(workload, SEED, 1.0, False, t_start=time.perf_counter(),
                        require_chip=False, scale=SCALE, bench=_bench(), **kw)


@pytest.mark.parametrize("workload", ["samg.spmvm", "dlr1.spmvm", "samg.cg"])
def test_sound_run_is_correct(workload):
    line = _run(workload)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "setup_s" in line["metrics"] and "hbm_gb" in line["metrics"]
    assert list(line)[-1] == "checks"


def test_prebuilt_operator_is_data_alone():
    """A cell that solves on a prebuilt operator needs only its traffic."""
    line = _run("samg.cg", traffic_overrides={"prebuilt": {"format": "sell"}})
    assert line["correct"] is True and line["attempted"] > 0


def test_distributed_operator_is_data_alone():
    """A 4-chip spMVM cell over ``dist_operator`` needs only its traffic
    and a ``workloads`` entry: here on 4 virtual CPU devices."""
    code = f"""
import json, time
from chipbench import run as RUN
bench = json.loads(open("BENCHMARK.json").read())
bench["workloads"].append({{"name": "samg.spmvm", "config": "samg",
    "traffic": "spmvm_power", "chips": 4, "why": "test"}})
bench["workloads"] = bench["workloads"][-1:]
line = RUN.run_cell("samg.spmvm", {SEED}, 1.0, False,
    t_start=time.perf_counter(), require_chip=False, scale={SCALE},
    bench=bench, traffic_overrides={{"operator": {{"build": "dist_operator"}}}})
print(json.dumps(line))
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}"}
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4


def _matvec_fault(kind):
    from repro.core.operator import DeviceOperator
    sound = DeviceOperator.matvec

    def broken(self, x, backend=None):
        if kind == "unchanged":
            return x[: self.shape[0]]
        y = sound(self, x, backend)
        return y.at[0].add(0.01 * jnp.abs(y).max())
    return broken


def _solve_fault(kind):
    import repro
    sound = repro.solve

    def broken(*a, **k):
        res = sound(*a, **k)
        res.x = (jnp.zeros_like(res.x) if kind == "unchanged"
                 else res.x.at[0].add(1.0))
        return res
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "altered"])
@pytest.mark.parametrize("workload", ["samg.spmvm", "dlr1.spmvm"])
def test_spmvm_fault_is_caught(monkeypatch, workload, kind):
    from repro.core.operator import DeviceOperator
    monkeypatch.setattr(DeviceOperator, "matvec", _matvec_fault(kind))
    assert _run(workload)["correct"] is False


@pytest.mark.parametrize("kind", ["unchanged", "altered"])
def test_solve_fault_is_caught(monkeypatch, kind):
    import repro
    monkeypatch.setattr(repro, "solve", _solve_fault(kind))
    assert _run("samg.cg")["correct"] is False


def test_reference_backend_on_the_chip_is_caught(monkeypatch):
    """The operator resolves the jnp reference path where the platform
    calls for the Pallas kernels."""
    monkeypatch.setattr(RUN, "expected_backend", lambda platform: "kernel")
    line = _run("samg.spmvm")
    assert line["checks"]["off_backend"]["value"] == 1
    assert line["correct"] is False


def test_solve_on_the_reference_rung_is_caught(monkeypatch):
    """The degradation ladder leaves the kernels for the jnp reference
    path: the answer is right, but not from the path being timed."""
    import repro
    sound = repro.solve

    def on_ref(*a, **k):
        res = sound(*a, **k)
        res.info["ladder"] = [{"rung": "primary", "status": "diverged"},
                              {"rung": "kernel->ref", "status": "converged"}]
        return res
    monkeypatch.setattr(repro, "solve", on_ref)
    line = _run("samg.cg")
    assert line["checks"]["ref_rung_solves"]["value"] > 0
    assert line["correct"] is False


def test_exits_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "samg.spmvm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_without_the_program(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"), "--workload",
         "samg.spmvm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert RUN.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(RUN.NoChip):
        RUN.device_peaks("TPU v9 imaginary")
