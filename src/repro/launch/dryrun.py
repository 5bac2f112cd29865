import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell and record memory / cost /
collective analysis for the roofline (deliverable g).

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init (task spec, MULTI-POD DRY-RUN
item 0).  Only this entry point sees 512 placeholder devices.

Usage:
    python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--out experiments/dryrun]

Each cell writes experiments/dryrun/<mesh>/<arch>__<shape>.json
(existing files are skipped -> the full sweep is resumable).
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models.api import build_model
from repro.models.sharding import rules_for, use_rules, logical_to_pspec
from repro.models.unroll import cost_mode_enabled
from repro.train.optimizer import AdamW
from repro.train.schedules import cosine
from repro.train.step import (make_train_step, train_state_shardings,
                              specs_to_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import collective_bytes, hlo_flops_bytes


SKIP = {
    # long_500k only for sub-quadratic archs (DESIGN.md §7)
    ("llava-next-mistral-7b", "long_500k"): "full attention at 500k",
    ("granite-moe-3b-a800m", "long_500k"): "full attention at 500k",
    ("deepseek-moe-16b", "long_500k"): "full attention at 500k",
    ("starcoder2-15b", "long_500k"): "full attention at 500k",
    ("minicpm-2b", "long_500k"): "full attention at 500k",
    ("qwen2.5-14b", "long_500k"): "full attention at 500k",
    ("seamless-m4t-medium", "long_500k"): "enc-dec full attention at 500k",
}


def _lower_cell(cfg, shape, mesh, rules, *, q_chunk, k_chunk,
                seq_override=None):
    """Lower (not compile) the cell's step function."""
    model = build_model(cfg)
    with jax.set_mesh(mesh), use_rules(rules):
        batch_sds, batch_spec_tree = model.input_specs(
            shape, seq_override=seq_override)
        batch_sh = specs_to_shardings(batch_spec_tree, mesh, rules)
        params_sds = model.param_shapes()
        param_sh, opt_sh = train_state_shardings(model, mesh, rules)

        if shape.kind == "train":
            opt = AdamW(lr_fn=cosine(3e-4, 100, 10_000))
            opt_sds = jax.eval_shape(opt.init, params_sds)
            step = make_train_step(model, opt, remat=True,
                                   q_chunk=q_chunk, k_chunk=k_chunk)
            return jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
            ).lower(params_sds, opt_sds, batch_sds)
        if shape.kind == "prefill":
            max_len = seq_override or shape.seq_len

            def prefill(params, batch):
                return model.prefill(params, batch, max_len=max_len,
                                     q_chunk=q_chunk, k_chunk=k_chunk)
            return jax.jit(
                prefill, in_shardings=(param_sh, batch_sh),
            ).lower(params_sds, batch_sds)
        # decode
        cache_sh = specs_to_shardings(batch_spec_tree["cache"], mesh, rules)
        tok_sh = specs_to_shardings(
            {"tokens": batch_spec_tree["tokens"],
             "pos": batch_spec_tree["pos"]}, mesh, rules)
        return jax.jit(
            model.decode_step,
            in_shardings=(param_sh, cache_sh, tok_sh["tokens"],
                          tok_sh["pos"]),
            out_shardings=(cache_sh, None),
        ).lower(params_sds, batch_sds["cache"], batch_sds["tokens"],
                batch_sds["pos"])


def _cost_of(compiled) -> np.ndarray:
    """(flops, bytes, collective_bytes) vector from a compiled module."""
    cost = compiled.cost_analysis() or {}
    flops, byts = hlo_flops_bytes(cost)
    coll = collective_bytes(compiled.as_text())
    return np.array([flops, byts, coll["total"]])


def _depth_variants(cfg):
    """Depth-variant plan for the cost extrapolation.

    Uniform patterns (K=1): [(small1, small2, count)] with one- and
    two-period configs; count = n_periods.

    Multi-kind patterns (gemma3 5:1, recurrentgemma 1:2): layers don't
    interact in cost, so each KIND's per-layer delta is measured from
    1- vs 2-layer single-kind configs (cheap) and combined by the kind's
    occurrence count over the full depth — instead of unrolling whole
    10/16-layer periods (which took 20+ min/compile on one core).
    """
    from repro.models.transformer import make_plan
    plan = make_plan(cfg, cfg.n_layers)
    k = len(plan.period_kinds)
    if k == 1:
        base = len(plan.prefix_kinds) + len(plan.suffix_kinds)
        n1, n2 = base + 1, base + 2
        e1, e2 = (1, 2) if cfg.is_encdec else (0, 0)
        quad = plan.period_kinds[0] == "global"
        return [(dataclasses.replace(cfg, n_layers=n1, enc_layers=e1),
                 dataclasses.replace(cfg, n_layers=n2, enc_layers=e2),
                 plan.n_periods, quad)]
    all_kinds = (list(plan.prefix_kinds)
                 + list(plan.period_kinds) * plan.n_periods
                 + list(plan.suffix_kinds))
    variants = []
    for kind in dict.fromkeys(plan.period_kinds):  # stable unique
        count = sum(1 for x in all_kinds if x == kind)
        # per-layer cost in S: quadratic only for full (global) attention;
        # local windows, recurrences, and SSM scans are linear — fitting
        # them quadratically extrapolates unstably to 32k+ sequences.
        variants.append((
            dataclasses.replace(cfg, layer_pattern=(kind,), n_layers=1),
            dataclasses.replace(cfg, layer_pattern=(kind,), n_layers=2),
            count, kind == "global"))
    return variants


SEQ_VARS = (2560, 3584, 4096)   # >= all windows; multiples of 512; 3 points
                                # solve [1, S, S^2] exactly


def extrapolated_cost(cfg, shape, mesh, rules, *, q_chunk=512, k_chunk=512):
    """Exact cost reconstruction for scan-structured models.

    XLA counts while bodies once, so we compile small UNROLLED variants:
    cost(depth d, seq S) = alpha(S) + d_periods * beta(S), and both
    alpha/beta are exact polynomials [1, S, S^2] for S >= window (block-
    pair attention is chunk-quadratic, everything else linear/const).
    Returns dict with extrapolated (flops, bytes, collective_bytes).

    Variants run with >=1024-token attention chunks: 4x fewer unrolled
    pair bodies than the 512 default, keeping the biggest unrolled
    variant (gemma3: 16 layers) compilable in minutes on one core.  The
    polynomial stays exact for fixed chunking; attention flops differ
    from the 512-chunk schedule only at masked block edges (<~10%).
    """
    q_chunk = max(q_chunk, 1024)
    k_chunk = max(k_chunk, 1024)
    variants = _depth_variants(cfg)
    compiles = 0
    with cost_mode_enabled():
        if shape.kind == "decode":
            total = None
            for vi, (small1, small2, count, _quad) in enumerate(variants):
                c1 = _cost_of(_lower_cell(small1, shape, mesh, rules,
                                          q_chunk=q_chunk,
                                          k_chunk=k_chunk).compile())
                c2 = _cost_of(_lower_cell(small2, shape, mesh, rules,
                                          q_chunk=q_chunk,
                                          k_chunk=k_chunk).compile())
                beta = c2 - c1
                compiles += 2
                if vi == 0:
                    total = (c1 - beta) + count * beta  # alpha + n*beta
                else:
                    total = total + count * beta
        else:
            seqs = list(SEQ_VARS)
            st = float(shape.seq_len)
            f_quad = np.array([[1.0, s, float(s) * s] for s in seqs])
            f_lin = np.array([[1.0, s] for s in seqs])
            t_quad = np.array([1.0, st, st * st])
            t_lin = np.array([1.0, st])
            total = None
            for vi, (small1, small2, count, quad) in enumerate(variants):
                alphas, betas = [], []
                for s in seqs:
                    c1 = _cost_of(_lower_cell(small1, shape, mesh, rules,
                                              q_chunk=q_chunk,
                                              k_chunk=k_chunk,
                                              seq_override=s).compile())
                    c2 = _cost_of(_lower_cell(small2, shape, mesh, rules,
                                              q_chunk=q_chunk,
                                              k_chunk=k_chunk,
                                              seq_override=s).compile())
                    betas.append(c2 - c1)
                    alphas.append(2 * c1 - c2)
                    compiles += 2
                feats, ft = (f_quad, t_quad) if quad else (f_lin, t_lin)
                beta_t = ft @ np.linalg.lstsq(feats, np.array(betas),
                                              rcond=None)[0]
                if vi == 0:
                    # alpha (embed/head/loss/optimizer) is linear in S
                    alpha_t = t_lin @ np.linalg.lstsq(
                        f_lin, np.array(alphas), rcond=None)[0]
                    total = alpha_t + count * beta_t
                else:
                    total = total + count * beta_t
    return {"flops": float(total[0]), "bytes": float(total[1]),
            "collective_bytes": float(total[2]),
            "n_variant_compiles": compiles}


def dryrun_cell(arch: str, shape_name: str, mesh_name: str,
                q_chunk: int = 512, k_chunk: int = 512,
                with_cost: bool = True, attn_impl: str = "pairs",
                overrides: dict | None = None) -> dict:
    """Lower + compile one cell; returns the analysis record.

    ``attn_impl`` / ``overrides`` (ArchConfig fields) are the §Perf
    hillclimbing knobs; baselines use the defaults.
    """
    import contextlib
    from repro.models.attention import use_attn_impl
    with contextlib.ExitStack() as stack:
        stack.enter_context(use_attn_impl(attn_impl))
        return _dryrun_cell_inner(arch, shape_name, mesh_name, q_chunk,
                                  k_chunk, with_cost, attn_impl, overrides)


def _dryrun_cell_inner(arch, shape_name, mesh_name, q_chunk, k_chunk,
                       with_cost, attn_impl, overrides):
    if (arch, shape_name) in SKIP:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": SKIP[(arch, shape_name)]}
    cfg = configs.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rules = rules_for(shape.kind, shape.global_batch, dict(mesh.shape))

    t0 = time.time()
    lowered = _lower_cell(cfg, shape, mesh, rules, q_chunk=q_chunk,
                          k_chunk=k_chunk)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    flops, byts = hlo_flops_bytes(cost)
    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            mem_rec[attr] = int(getattr(mem, attr))
    coll = collective_bytes(compiled.as_text())
    chips = 1
    for v in dict(mesh.shape).values():
        chips *= v
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "attn_impl": attn_impl,
        "overrides": overrides or {},
        "compile_s": round(t_compile, 1),
        "hlo_flops_raw": flops, "hlo_bytes_raw": byts,
        "collective_raw": coll,
        "memory": mem_rec,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "tokens": shape.global_batch * (1 if shape.kind == "decode"
                                        else shape.seq_len),
        "rules": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in rules.items()},
    }
    if with_cost:
        t1 = time.time()
        rec["cost"] = extrapolated_cost(cfg, shape, mesh, rules,
                                        q_chunk=q_chunk, k_chunk=k_chunk)
        rec["cost"]["variant_compile_s"] = round(time.time() - t1, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the unrolled cost-extrapolation variants")
    args = ap.parse_args()

    archs = configs.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(configs.SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for mesh_name in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape in shapes:
                fname = os.path.join(outdir, f"{arch}__{shape}.json")
                if os.path.exists(fname) and not args.force:
                    print(f"[skip-existing] {mesh_name}/{arch}/{shape}")
                    continue
                print(f"[dryrun] {mesh_name}/{arch}/{shape} ...", flush=True)
                try:
                    rec = dryrun_cell(arch, shape, mesh_name,
                                      with_cost=not args.no_cost)
                except Exception as e:  # noqa: BLE001 - record and continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-2000:]}
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    c = rec.get("cost") or {}
                    extra = (f" flops={c.get('flops', rec['hlo_flops_raw']):.3e}"
                             f" coll={rec['collective_raw']['total']:.3e}B"
                             f" compile={rec['compile_s']}s")
                print(f"[done] {mesh_name}/{arch}/{shape}: {status}{extra}",
                      flush=True)


if __name__ == "__main__":
    main()
