"""Elastic re-mesh end-to-end: train on a 2x4 mesh, 'lose' a data row,
restore the checkpoint under the shrunk 1x4 mesh with a rebatched global
batch, and continue training — the full node-failure recovery path."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_smoke_config
    from repro.distributed.fault import plan_elastic_remesh, rebatch_for_mesh
    from repro.distributed.sharding import AxisRules, batch_specs, param_specs, use_rules
    from repro.launch.mesh import make_mesh
    from repro.models import LM
    from repro.training import OptimizerConfig, init_train_state, make_train_step

    cfg0 = get_smoke_config("smollm-135m")
    cfg = type(cfg0)(**{**cfg0.__dict__, "num_microbatches": 1})
    model = LM(cfg)
    rng = np.random.default_rng(0)
    mk = lambda b: {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 16)), jnp.int32)}
    ckdir = tempfile.mkdtemp()
    mgr = CheckpointManager(ckdir)
    params, opt = init_train_state(model, jax.random.key(0))
    losses = []

    def run(mesh_shape, global_batch, state, steps):
        mesh = make_mesh(tuple(mesh_shape.values()), tuple(mesh_shape.keys()))
        rules = AxisRules(mesh)
        p, o = state
        p_sh = param_specs(jax.eval_shape(lambda: p), rules)
        o_sh = param_specs(jax.eval_shape(lambda: o), rules)
        with use_rules(rules), mesh:
            step_fn = jax.jit(make_train_step(model, OptimizerConfig(lr=1e-3)),
                              in_shardings=(p_sh, o_sh, batch_specs(mk(global_batch), rules)))
            p = jax.device_put(p, p_sh)
            o = jax.device_put(o, o_sh)
            for s in range(steps):
                b = jax.device_put(mk(global_batch), batch_specs(mk(global_batch), rules))
                p, o, m = step_fn(p, o, b)
                losses.append(float(m["loss"]))
        return jax.device_get(p), jax.device_get(o)

    # phase 1: healthy 2x4 mesh, global batch 8
    shape1 = {"data": 2, "model": 4}
    params, opt = run(shape1, 8, (params, opt), steps=3)
    mgr.save(2, {"params": params, "opt": opt})

    # failure: lose one host in a data row -> plan shrink + rebatch
    new_shape = plan_elastic_remesh(shape1, failed_hosts=[1], hosts_per_data_row=1)
    new_batch = rebatch_for_mesh(8, shape1["data"], new_shape["data"])
    step, state = mgr.restore_latest({"params": params, "opt": opt})
    params, opt = run(new_shape, new_batch, (state["params"], state["opt"]), steps=3)

    print(json.dumps({
        "restored_step": step,
        "new_mesh": new_shape, "new_batch": new_batch,
        "losses_finite": bool(np.isfinite(losses).all()),
        "n_steps": len(losses),
    }))
    """
)


def test_elastic_remesh_restart():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["restored_step"] == 2
    assert res["new_mesh"] == {"data": 1, "model": 4}
    assert res["new_batch"] == 4
    assert res["losses_finite"] and res["n_steps"] == 6
