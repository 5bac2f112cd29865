"""§Perf hillclimbing harness: re-lower a cell with a knob changed and
diff the roofline terms against the recorded baseline.

    PYTHONPATH=src python -m benchmarks.perf_iter \
        --arch deepseek-moe-16b --shape train_4k \
        --tag onehot --set moe_dispatch=onehot

Knobs: --attn-impl pairs|qloop, --q-chunk N, --k-chunk N, and
--set field=value for any ArchConfig field (type-coerced).  Results land
in experiments/perf/<arch>__<shape>__<tag>.json.

Solver mode prices one Krylov ITERATION instead of a model cell:

    PYTHONPATH=src python -m benchmarks.perf_iter \
        --solver samg --scale 0.001 --method cg

For each (strategy x stored dtype) it prints the spMV-only bytes next
to the full per-iteration bytes (spMV streams PLUS the carrier-vector
axpy/dot passes, ``perf_model.solver_iteration_bytes``) and the
predicted seconds.  The spMV-only column is the number this harness
used to (wrongly) report as the iteration cost — the carrier traffic it
hid is exactly what the fused kernel removes.
"""
from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json

from repro import configs
from repro.core import perf_model as PM


def term_row(cost: dict, tokens: int, chips: int, n_active: int,
             kind: str) -> dict:
    r = PM.roofline_terms(cost["flops"], cost["bytes"],
                          cost["collective_bytes"], chips=1)
    mult = 6 if kind == "train" else 2
    model_flops = mult * n_active * tokens / chips
    bound = r.bound_s
    return dict(compute_s=r.compute_s, memory_s=r.memory_s,
                collective_s=r.collective_s, dominant=r.dominant,
                bound_s=bound,
                roofline_fraction=(model_flops / PM.TPU_V5E.peak_flops)
                / bound if bound else 0.0)


def solver_pricing(matrix: str, scale: float, method: str) -> list[dict]:
    """Per-iteration pricing rows for one bench matrix: composed vs
    fused strategy, f32 vs bf16-compressed storage, each with the
    spMV-only figure alongside the full with-carriers figure."""
    from repro.core import matrices as M
    from repro.kernels import ops
    import jax.numpy as jnp

    m = getattr(M, matrix)(scale=scale)
    rows = []
    for dlabel, dtype in (("f32", None), ("bf16", jnp.bfloat16)):
        sd = ops.as_device(m, format="sell", dtype=dtype,
                           index_dtype="auto")
        vb = jnp.dtype(sd.value_dtype).itemsize
        ib = jnp.dtype(sd.index_dtype).itemsize
        stored = sd.dev.stored_slots
        spmv_only = PM.SOLVER_SPMV_COUNT[method] * (
            PM.spmvm_bytes(stored, m.n_rows, 0.0, m.n_nzr,
                           value_bytes=vb, index_bytes=ib, vec_bytes=4)
            + PM.gathered_rhs_bytes(stored, 4))
        for strategy in ("composed", "fused"):
            full = PM.solver_iteration_bytes(
                stored, m.n_rows, m.n_nzr, method=method,
                strategy=strategy, value_bytes=vb, index_bytes=ib)
            rows.append(dict(
                matrix=matrix, method=method, strategy=strategy,
                dtype=dlabel, spmv_only_bytes=spmv_only,
                iteration_bytes=full,
                carrier_fraction=1.0 - spmv_only / full,
                predicted_s=PM.predicted_iteration_seconds(
                    stored, m.n_rows, m.n_nzr, method=method,
                    strategy=strategy, value_bytes=vb, index_bytes=ib,
                    fmt="sell")))
    return rows


def solver_main(args):
    rows = solver_pricing(args.solver, args.scale, args.method)
    print(f"== solver iteration pricing: {args.solver} scale={args.scale} "
          f"method={args.method} ==")
    print(f"{'strategy':10s} {'dtype':6s} {'spMV-only B':>12s} "
          f"{'iter B':>12s} {'carrier %':>10s} {'pred s':>10s}")
    for r in rows:
        print(f"{r['strategy']:10s} {r['dtype']:6s} "
              f"{r['spmv_only_bytes']:12.0f} {r['iteration_bytes']:12.0f} "
              f"{r['carrier_fraction'] * 100:9.1f}% "
              f"{r['predicted_s']:10.3e}")
    os.makedirs(args.out, exist_ok=True)
    fname = os.path.join(
        args.out, f"solver__{args.solver}__{args.method}.json")
    with open(fname, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {fname}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--tag")
    ap.add_argument("--solver", metavar="MATRIX",
                    help="price a solver iteration on this bench matrix "
                         "(samg/uhbr/dlr1/...) instead of a model cell")
    ap.add_argument("--scale", type=float, default=0.001)
    ap.add_argument("--method", default="cg",
                    choices=sorted(PM.SOLVER_SPMV_COUNT))
    ap.add_argument("--attn-impl", default="pairs")
    ap.add_argument("--q-chunk", type=int, default=512)
    ap.add_argument("--k-chunk", type=int, default=512)
    ap.add_argument("--set", action="append", default=[],
                    help="ArchConfig field override: name=value")
    ap.add_argument("--baseline-dir", default="experiments/dryrun/single")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    if args.solver:
        solver_main(args)
        return
    if not (args.arch and args.shape and args.tag):
        ap.error("--arch/--shape/--tag are required (or use --solver)")

    from repro.launch.dryrun import dryrun_cell

    overrides = {}
    cfg = configs.get(args.arch)
    for s in args.set:
        name, val = s.split("=", 1)
        cur = getattr(cfg, name)
        if isinstance(cur, bool):
            val = val.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            val = int(val)
        elif isinstance(cur, float):
            val = float(val)
        overrides[name] = val

    rec = dryrun_cell(args.arch, args.shape, "single",
                      q_chunk=args.q_chunk, k_chunk=args.k_chunk,
                      attn_impl=args.attn_impl, overrides=overrides)
    os.makedirs(args.out, exist_ok=True)
    fname = os.path.join(args.out,
                         f"{args.arch}__{args.shape}__{args.tag}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)

    if rec["status"] != "ok":
        print(f"STATUS {rec['status']}: {rec.get('error','')[:400]}")
        return

    new = term_row(rec["cost"], rec["tokens"], rec["chips"],
                   rec["n_active_params"],
                   "train" if args.shape.startswith("train") else "other")
    base_f = os.path.join(args.baseline_dir,
                          f"{args.arch}__{args.shape}.json")
    print(f"== {args.arch} / {args.shape} / {args.tag} ==")
    if os.path.exists(base_f):
        base_rec = json.load(open(base_f))
        if base_rec.get("cost"):
            base = term_row(base_rec["cost"], base_rec["tokens"],
                            base_rec["chips"], base_rec["n_active_params"],
                            "train" if args.shape.startswith("train")
                            else "other")
            for k in ("compute_s", "memory_s", "collective_s", "bound_s",
                      "roofline_fraction"):
                delta = (new[k] - base[k]) / base[k] * 100 if base[k] else 0
                print(f"{k:18s} base={base[k]:.5f} new={new[k]:.5f} "
                      f"({delta:+.1f}%)")
            print(f"dominant: {base['dominant']} -> {new['dominant']}")
            return
    for k, v in new.items():
        print(f"{k:18s} {v}")


if __name__ == "__main__":
    main()
