"""The benchmark's own matrices: structure from the configuration's fixed
seed, values from the run's ``--seed``.

Each configuration file names a generator (``matrices/<generator>.py``)
that exposes ``structure(cfg, n_rows) -> (indptr, indices)`` and
``values(cfg, indptr, indices, rng) -> float64 array``.  The structure
is the deployment's matrix and never changes with the run's seed, so it
is generated once per checkout and kept under ``chipbench/.cache``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import json
import os
import pathlib

import numpy as np

CACHE = pathlib.Path(__file__).resolve().parents[1] / ".cache" / "structure"

@dataclasses.dataclass
class HostMatrix:
    """A square CSR matrix on the host: int64 ``indptr``, int32
    ``indices`` (ascending within each row), float32 ``data``."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray

    @property
    def n_rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def dedup_csr(rows: np.ndarray, cols: np.ndarray, n: int):
    """(indptr, indices) of the distinct (row, col) pairs, columns
    ascending within each row."""
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    key.sort()
    key = key[np.concatenate(([True], key[1:] != key[:-1]))]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return indptr, (key % n).astype(np.int32)


def _generator(cfg: dict):
    return importlib.import_module(f"chipbench.matrices.{cfg['generator']}")


def structure(cfg: dict, n_rows: int, *, use_cache: bool = True):
    """The configuration's (indptr, indices) at ``n_rows`` rows, from
    the structure cache when a file made from this very configuration
    and generator is there: the tag hashes the whole configuration and
    the generator's source, so a changed key or generator never reads a
    stale structure."""
    gen = _generator(cfg)
    h = hashlib.sha1(json.dumps(cfg, sort_keys=True).encode())
    h.update(pathlib.Path(gen.__file__).read_bytes())
    tag = h.hexdigest()
    path = CACHE / f"{cfg['name']}-{n_rows}-{tag[:12]}.npz"
    if use_cache and path.exists():
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    indptr, indices = gen.structure(cfg, n_rows)
    if use_cache:
        CACHE.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
        # Synced here, in set-up: left to the kernel, the write-back of
        # these ~100 MB comes some 30 s later, inside the measured window.
        with open(tmp, "wb") as f:
            np.savez(f, indptr=indptr, indices=indices)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    return indptr, indices


def build(cfg: dict, value_seed: int, *, scale: float = 1.0,
          use_cache: bool = True) -> HostMatrix:
    """The configuration's matrix with values drawn from ``value_seed``.
    ``scale`` < 1 shrinks the row count for tests on the CPU."""
    n_rows = max(int(cfg["rows"] * scale), 1024)
    indptr, indices = structure(cfg, n_rows, use_cache=use_cache)
    rng = np.random.default_rng(value_seed)
    data = _generator(cfg).values(cfg, indptr, indices, rng)
    return HostMatrix(indptr, indices, data.astype(cfg["value_dtype"]))
