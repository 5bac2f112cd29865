"""The traffic generator's shared parts.  A traffic file
(``traffic/<name>.json``) names a ``loop``, a file of this package
(``loops/<loop>.py``) that exposes ``run(run: Run) -> Outcome``, and
gives its parameters.  Each loop builds what the program needs in
set-up, warms every shape it will use, drives the program closed loop
for the window, reads the device's memory, frees the program's state and
only then compares a sample of the window's answers with the float64
reference.

``run.py`` finds the loop by the name in the traffic file and picks from
its :class:`Outcome` the metrics that ``BENCHMARK.json`` lists for the
cell, so a new traffic mix on an existing loop is a data file alone.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib

import numpy as np


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    end_to_end: dict            # metric name -> value
    counters: dict              # what per-layer readers read
    checks: dict                # check name -> (value, limit)
    hbm_bytes_in_use: int
    memory_peak_bytes: int


class Run:
    """What a loop is handed: the configuration, the traffic, the limits,
    the matrix, the seed, the window, the chips the cell asks for and
    the backend the program must resolve on this platform, and the hooks
    for tracing."""

    def __init__(self, cfg, traffic, limits, matrix, seed, seconds,
                 t_start, tracer, phases, *, chips=1,
                 expected_backend="kernel"):
        self.cfg, self.traffic, self.limits = cfg, traffic, limits
        self.matrix, self.seed, self.seconds = matrix, seed, seconds
        self.t_start, self.tracer = t_start, tracer
        self.phases = phases          # set-up seconds by phase, for stderr
        self.chips, self.expected_backend = chips, expected_backend

    def rng(self, stream: int) -> np.random.Generator:
        """A numpy stream of its own for each use of the seed; the
        matrix values use ``default_rng(seed)`` itself."""
        return np.random.default_rng([stream, self.seed])

    def jax_key(self, stream: int):
        import jax
        s = np.random.SeedSequence([stream, self.seed]).generate_state(1)[0]
        return jax.random.key(int(s))

    def csr(self):
        from repro.core.formats import CSRMatrix
        m = self.matrix
        return CSRMatrix(m.indptr, m.indices, m.data, (m.n_rows, m.n_rows))

    def devices(self):
        import jax
        return jax.devices()[: self.chips]


class Reservoir:
    """A seeded uniform sample of ``k`` of the window's answers.  It
    holds the device arrays, which cost the window nothing, and copies
    them to the host once the window has closed."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, *arrays):
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(arrays)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = arrays

    def to_host(self):
        """The sample as numpy arrays; the device copies are dropped."""
        self.items = [tuple(np.asarray(a) for a in it) for it in self.items]
        return self.items


def memory(devices):
    """(bytes in use summed over ``devices``, peak of the fullest one)."""
    in_use, peak = 0, 0
    for d in devices:
        st = d.memory_stats() or {}
        in_use += int(st.get("bytes_in_use", 0))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return in_use, peak


def free_program_state():
    from repro.kernels import ops
    ops.clear_device_cache()
    gc.collect()


def value_dtype(traffic):
    """The stored value type the traffic asks for; None keeps the
    program's default."""
    import jax.numpy as jnp
    return jnp.dtype(traffic["dtype"]) if traffic.get("dtype") else None


def timing(seconds) -> dict:
    """Per-call host times of the window, for stderr: whether a slow run
    is slow in every call or stalls in a few."""
    if not seconds:
        return {}
    s = np.asarray(seconds)
    half = len(s) // 2
    return {"calls": len(s), "min": float(s.min()),
            "median": float(np.median(s)), "max": float(s.max()),
            "first_half_mean": float(s[:half].mean()) if half else None,
            "second_half_mean": float(s[half:].mean())}


def load(name: str):
    """The loop module ``loops/<name>.py``."""
    return importlib.import_module(f"chipbench.loops.{name}")
