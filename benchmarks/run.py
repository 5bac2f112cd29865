"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Heavy multi-device cases
run in child processes pinned to the CPU (``JAX_PLATFORMS=cpu``, 8
virtual devices, ``common.run_cpu_child``), so this process keeps its
one device: on a TPU host the chip belongs to this process alone, and
no child ever reaches for it.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig3,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig3,eq,scaling,kernels,sell,"
                         "ops,dist,tune,solve,serve,formats")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_formats, bench_histograms, bench_perf_model,
                   bench_scaling, bench_kernels, bench_sell, bench_sparse_ffn,
                   bench_ops, bench_dist, bench_tune, bench_solve,
                   bench_serve)
    suites = [
        ("table1", bench_formats.run),      # paper Table 1
        ("fig3", bench_histograms.run),     # paper Fig. 3
        ("eq", bench_perf_model.run),       # paper Eq. 1-4
        ("kernels", bench_kernels.run),     # kernel study
        ("sell", bench_sell.run),           # SELL-C-sigma sigma sweep
        ("ops", bench_ops.run),             # operator-wrapper overhead
        ("sparse_ffn", bench_sparse_ffn.run),  # beyond-paper: pJDS in LMs
        ("scaling", bench_scaling.run),     # paper Fig. 5
        ("dist", bench_dist.run),           # gathered vs full halo, spMM
        ("tune", bench_tune.run),           # autotuner vs heuristic + calib
        ("solve", bench_solve.run),         # fused solver iterations
        ("serve", bench_serve.run),         # multi-tenant solve serving
        ("formats", bench_formats.run_corpus),  # .mtx corpus format sweep
    ]
    if only:
        unknown = only - {name for name, _ in suites}
        if unknown:
            sys.exit(f"unknown suite(s): {','.join(sorted(unknown))}; "
                     f"known: {','.join(name for name, _ in suites)}")

    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        if only and name not in only:
            continue
        try:
            fn(print_rows=True)
        except Exception:
            failed += 1
            print(f"{name},0,FAILED", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
