"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b \
        [--smoke] [--steps N] [--mesh host|single] [--ckpt DIR]

--mesh host   : 1-D data mesh over however many devices exist (the real
                execution path on this box; use XLA_FLAGS to fake more).
--mesh single : the production (16,16) mesh — only valid on real
                hardware of that size; on this box use dryrun.py instead.
--smoke       : reduced same-family config (CPU-runnable end to end).

Fault tolerance: auto-resumes from the latest committed checkpoint in
--ckpt; straggler watchdog logs slow steps (see train/loop.py).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro import configs
from repro.models.api import build_model
from repro.models.sharding import (DEFAULT_SINGLE_POD, set_rules)
from repro.train.optimizer import AdamW
from repro.train.schedules import wsd, cosine
from repro.train.step import (make_train_step, train_state_shardings)
from repro.train.loop import train
from repro.data.pipeline import for_config
from repro.launch.mesh import make_production_mesh, make_host_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="wsd", choices=["wsd", "cosine"])
    ap.add_argument("--mesh", default="host", choices=["host", "single"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = build_model(cfg)
    if args.mesh == "single":
        mesh = make_production_mesh()
        rules = dict(DEFAULT_SINGLE_POD)
    else:
        mesh = make_host_mesh()
        rules = {"batch": ("data",), "model": None, "expert": None,
                 "seq": None, "kvseq": None}

    lr_fn = (wsd(args.lr, warmup=max(args.steps // 10, 1),
                 stable=args.steps // 2, decay=args.steps // 3)
             if args.schedule == "wsd"
             else cosine(args.lr, max(args.steps // 10, 1), args.steps))
    opt = AdamW(lr_fn=lr_fn)

    with jax.set_mesh(mesh):
        set_rules(rules)
        param_sh, opt_sh = train_state_shardings(model, mesh, rules)
        params = jax.jit(model.init, out_shardings=param_sh)(
            jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"arch={cfg.name} params={n/1e6:.1f}M mesh={dict(mesh.shape)} "
              f"devices={mesh.devices.size}")
        step = jax.jit(make_train_step(model, opt, q_chunk=128, k_chunk=128),
                       in_shardings=(param_sh, opt_sh, None),
                       out_shardings=(param_sh, opt_sh, None))
        data = for_config(cfg, batch=args.batch, seq=args.seq)
        train(step_fn=step, params=params, opt_state=opt_state, data=data,
              steps=args.steps, ckpt_dir=args.ckpt,
              ckpt_every=args.ckpt_every)
        set_rules(None)


if __name__ == "__main__":
    main()
