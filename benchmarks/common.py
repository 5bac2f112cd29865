"""Shared benchmark utilities."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np

# One seed policy for every suite (mirrored by tests/conftest.DEFAULT_SEED):
# benchmark inputs are deterministic so BENCH_*.json rows are comparable
# across runs and the CI regression guards never flake on input draw.
DEFAULT_SEED = 0


def seeded_rng(seed: int | None = None) -> np.random.Generator:
    """Deterministic generator for benchmark inputs."""
    return np.random.default_rng(DEFAULT_SEED if seed is None else seed)


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-clock seconds per call of a jitted fn."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


# Appended to every child script: the child names the platform it ran on.
_PLATFORM_LINE = ("\nimport jax as _jax\n"
                  "print('PLATFORM ' + _jax.devices()[0].platform)\n")


def run_cpu_child(script: str, timeout: int = 560):
    """Run ``script`` (which sets its own virtual-device count and prints
    one ``RESULTS <json>`` line) in a child Python process pinned to the
    CPU backend, and return ``(results, platform)``.

    The children exist to emulate a multi-device mesh on host CPUs.
    ``JAX_PLATFORMS=cpu`` keeps them off any accelerator: a parent that
    already holds a TPU never has a child reaching for it (the chip
    belongs to one process at a time)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script + _PLATFORM_LINE],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    lines = r.stdout.splitlines()
    res = [l for l in lines if l.startswith("RESULTS ")][-1]
    platform = [l for l in lines if l.startswith("PLATFORM ")][-1]
    return json.loads(res[len("RESULTS "):]), platform[len("PLATFORM "):]


def _jsonable(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON-serializable: {type(o)}")


def write_bench_json(suite: str, rows: list, out_dir: str | None = None) -> str:
    """Write machine-readable benchmark rows to ``BENCH_<suite>.json``
    (cwd by default) — the perf-trajectory artifact CI uploads."""
    path = pathlib.Path(out_dir or ".") / f"BENCH_{suite}.json"
    payload = {"suite": suite, "jax": jax.__version__, "rows": rows}
    path.write_text(json.dumps(payload, indent=2, default=_jsonable) + "\n")
    return str(path)
