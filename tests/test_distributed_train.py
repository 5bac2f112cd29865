"""Distributed training integration: a reduced model trains for real on
an 8-device host mesh (4 data x 2 model) through the same pjit wiring the
dry-run lowers, including ZeRO-1 opt-state sharding and an elastic
restart on a different mesh (8 -> 4 devices)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.dist, pytest.mark.slow]

_SCRIPT = textwrap.dedent("""
    import os, json, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.models.api import build_model
    from repro.models.sharding import use_rules
    from repro.train.optimizer import AdamW
    from repro.train.schedules import constant
    from repro.train.step import (make_train_step, train_state_shardings,
                                  batch_shardings)
    from repro.checkpoint import store
    from jax import set_mesh
    from repro.launch.mesh import make_mesh

    def mesh_of(dp, tp):
        return make_mesh((dp, tp), ("data", "model"))

    cfg = dataclasses.replace(
        configs.smoke("qwen2.5-14b"), d_model=64, d_ff=128, n_layers=2)
    model = build_model(cfg)
    rules = {"batch": ("data",), "model": ("model",), "expert": ("model",),
             "seq": None, "kvseq": None}
    out = {}

    def build(mesh):
        with set_mesh(mesh), use_rules(rules):
            param_sh, opt_sh = train_state_shardings(model, mesh, rules)
            opt = AdamW(lr_fn=constant(1e-3))
            step = jax.jit(
                make_train_step(model, opt, q_chunk=16, k_chunk=16),
                in_shardings=(param_sh, opt_sh, None),
                out_shardings=(param_sh, opt_sh, None))
            return opt, step, param_sh, opt_sh

    mesh8 = mesh_of(4, 2)
    opt, step, param_sh, opt_sh = build(mesh8)
    with set_mesh(mesh8), use_rules(rules):
        params = jax.jit(model.init, out_shardings=param_sh)(
            jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)
        rng = np.random.default_rng(0)
        losses = []
        for i in range(6):
            batch = {
                "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                      jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                      jnp.int32),
            }
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
        out["losses"] = losses
        # ZeRO-1: the biggest master-weight leaf must be sharded over
        # BOTH axes (param TP-sharding + data sharding)
        leaves = jax.tree.leaves(opt_state.master)
        big = max(leaves, key=lambda x: x.size)
        out["master_ndev"] = int(big.sharding.num_devices)
        out["master_is_fully_sharded"] = not big.sharding.is_fully_replicated
        tmp = tempfile.mkdtemp()
        store.save(tmp, 6, (params, opt_state))

    # elastic restart on a 4-device mesh
    mesh4 = mesh_of(2, 2)
    opt4, step4, p_sh4, o_sh4 = build(mesh4)
    with set_mesh(mesh4), use_rules(rules):
        tgt = (jax.eval_shape(model.init, jax.random.PRNGKey(0)),
               jax.eval_shape(opt4.init,
                              jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0))))
        (params4, opt_state4), _ = store.restore(tmp, 6, tgt,
                                                 shardings=(p_sh4, o_sh4))
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                  jnp.int32),
        }
        params4, opt_state4, metrics4 = step4(params4, opt_state4, batch)
        out["resumed_loss"] = float(metrics4["loss"])
        out["resumed_step"] = int(opt_state4.step)
    print("OUT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("OUT ")][-1]
    return json.loads(line[4:])


def test_loss_decreases_on_mesh(results):
    assert results["losses"][-1] < results["losses"][0]


def test_zero1_master_sharded(results):
    assert results["master_is_fully_sharded"]
    assert results["master_ndev"] == 8


def test_elastic_restart_trains(results):
    assert results["resumed_step"] == 7
    import math
    assert math.isfinite(results["resumed_loss"])
    assert results["resumed_loss"] < results["losses"][0]
