"""Batched serving example: continuous batching with the Engine.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np
import jax

from repro import configs
from repro.models.api import build_model
from repro.serve.engine import Engine, Request


def main():
    cfg = configs.smoke("gemma3-4b")   # local:global pattern incl. windows
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    eng = Engine(model, params, batch_slots=4, max_len=128)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (4 + 3 * i,))
                    .astype(np.int32),
                    max_new=8)
            for i in range(6)]
    t0 = time.perf_counter()
    eng.run(reqs, max_ticks=500)
    dt = time.perf_counter() - t0
    total_new = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt_len={len(r.prompt)} -> {r.out}")
    print(f"{total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s on CPU, batched over 4 slots)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
