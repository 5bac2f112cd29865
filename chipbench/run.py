#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process finds.

    python chipbench/run.py --workload samg.spmvm --seed 7 --seconds 10 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``configs/<name>.json``, the matrix) and a traffic mix
(``traffic/<name>.json``, whose ``loop`` names the code that drives
it, ``loops/<loop>.py``);
its correctness limits are in ``limits/<cell>.json``, and each per-layer
metric is read by ``metrics/<metric>.py``.  With ``--trace 0`` the last
line of standard output is the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiled window.  The run
exits non-zero, and prints no result, where JAX finds no TPU or the
device is not in ``peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache"


class NoChip(RuntimeError):
    """JAX found no device this benchmark may report on."""


def _configure_environment() -> None:
    """Caches at fixed paths inside the checkout, set before JAX loads:
    a cell's later runs read the programs and the tuner's decisions its
    first run wrote."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["REPRO_TUNE_CACHE"] = str(CACHE / "tune_cache.json")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def _load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def _cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(ROOT / cfg_entry["file"])
    traffic = _load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = _load_json(HERE / "limits" / f"{workload}.json")
    return cell, cfg, traffic, limits


def _reader(metric: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric}", HERE / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def device_peaks(kind: str) -> dict:
    """The peaks of ``kind`` from ``peaks.json``; an unknown device is
    an error, never a default."""
    peaks = _load_json(HERE / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"({sorted(peaks)})")
    return peaks[kind]


def expected_backend(platform: str) -> str:
    """The spMV backend the program has to resolve on ``platform``: its
    Pallas kernels on a TPU, never the jnp reference path there."""
    return "kernel" if platform == "tpu" else "ref"


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, require_chip: bool = True,
             scale: float = 1.0, traffic_overrides: dict | None = None,
             bench: dict | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.  Set-up is
    timed from ``t_start``, the process's start for a run of its own.

    ``require_chip=False`` and ``scale`` < 1 are for the tests on the
    CPU: the device check is skipped and the matrix shrunk.
    ``traffic_overrides`` replaces keys of the traffic file (the control
    switches the program's lower precision on this way); ``bench``
    replaces ``BENCHMARK.json``, for tests of cells it does not hold."""
    _configure_environment()
    bench = bench or _load_json(ROOT / "BENCHMARK.json")
    cell, cfg, traffic, limits = _cell(bench, workload)
    traffic = {**traffic, **(traffic_overrides or {})}

    import jax
    import repro  # noqa: F401  (fails here, before any work, without src/)

    from chipbench import loops, matrices
    from chipbench import trace as T

    loop = loops.load(traffic["loop"])
    devices = jax.devices()
    d0 = devices[0]
    peaks = None
    if require_chip:
        if d0.platform != "tpu":
            raise NoChip(f"no TPU: JAX found {d0.platform!r}")
        if len(devices) < cell["chips"]:
            raise NoChip(f"the cell needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
        peaks = device_peaks(d0.device_kind)

    phases = {"start_to_device_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    m = matrices.build(cfg, seed, scale=scale)
    phases["matrix_s"] = time.perf_counter() - t0
    tracer = T.Tracer(CACHE / "trace" / workload) if trace else T.NoTracer()
    run = loops.Run(cfg, traffic, limits, m, seed, seconds, t_start, tracer,
                    phases, chips=cell["chips"],
                    expected_backend=expected_backend(d0.platform))
    out = loop.run(run)

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": None, "attempted": out.attempted,
            "failed": out.failed, "metrics": {}, "device": device}
    if trace:
        summary = tracer.reduce()
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = {"counters": out.counters, "trace": summary, "peaks": peaks,
               "n_rows": m.n_rows, "nnz": m.nnz}
        for entry in bench["per_layer"]:
            if _applies(entry, workload):
                value = _reader(entry["name"])(ctx)
                if value is not None:
                    line["metrics"][entry["name"]] = {"value": value,
                                                      "unit": entry["unit"]}
        line["breakdown"] = summary.breakdown()
    else:
        e2e = {**out.end_to_end, "setup_s": out.setup_s,
               "hbm_gb": out.hbm_bytes_in_use / 1e9}
        for entry in bench["end_to_end"]:
            if _applies(entry, workload):
                line["metrics"][entry["name"]] = {"value": e2e[entry["name"]],
                                                  "unit": entry["unit"]}
    line["correct"] = all(v <= lim for v, lim in out.checks.values())
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    counters = {k: v for k, v in out.counters.items()
                if not isinstance(v, list)}
    print(f"window: {out.window_s:.3f} s, {out.attempted} attempted, "
          f"{out.failed} failed, counters {json.dumps(counters)}",
          file=sys.stderr)
    print(f"setup phases: {json.dumps(run.phases)}", file=sys.stderr)
    for k, (v, lim) in out.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except (NoChip, ImportError, FileNotFoundError) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
