"""End-to-end training driver: ~100M-param qwen2.5-family model for a few
hundred steps on CPU with the production substrate (AdamW + WSD,
checkpoints, auto-resume, straggler watchdog).

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--arch ID]
"""
import argparse
import dataclasses

import jax

from repro import configs
from repro.models.api import build_model
from repro.train.optimizer import AdamW
from repro.train.schedules import wsd
from repro.train.step import make_train_step
from repro.train.loop import train
from repro.data.pipeline import for_config


def hundred_m(arch: str) -> configs.ArchConfig:
    """Scale the chosen architecture family down to ~100M params."""
    cfg = configs.get(arch)
    return dataclasses.replace(
        cfg, n_layers=8, d_model=640, n_heads=10,
        n_kv_heads=max(1, min(cfg.n_kv_heads, 5)), d_ff=2048,
        head_dim=64, vocab=32_000, window=min(cfg.window, 256),
        n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        d_inner=1024 if cfg.d_inner else 0,
        dt_rank=32 if cfg.dt_rank else 0,
        enc_layers=2 if cfg.enc_layers else 0,
        frontend_seq=64 if cfg.frontend_seq else 0,
        param_dtype="float32", activation_dtype="float32",
        name=f"{arch}-100m")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    cfg = hundred_m(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch} seq={args.seq}")

    opt = AdamW(lr_fn=wsd(3e-4, warmup=20, stable=args.steps // 2,
                          decay=args.steps // 3))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt, q_chunk=128, k_chunk=128))
    data = for_config(cfg, batch=args.batch, seq=args.seq)

    params, opt_state, hist = train(
        step_fn=step, params=params, opt_state=opt_state, data=data,
        steps=args.steps, ckpt_dir=args.ckpt, ckpt_every=50, log_every=10)
    print(f"final loss {hist['losses'][-1]:.4f} "
          f"(from {hist['losses'][0]:.4f}); "
          f"stragglers flagged: {len(hist['stragglers'])}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
