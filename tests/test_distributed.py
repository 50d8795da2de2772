"""Sharding rules + multi-device integration (subprocess with forced host
devices so the main pytest process keeps its single-device view)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.distributed.sharding import AxisRules, DEFAULT_RULES


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def _rules(shape):
    r = AxisRules.__new__(AxisRules)
    r.mesh = FakeMesh(shape)
    r.rules = dict(DEFAULT_RULES)
    return r


class TestAxisRules:
    def test_divisibility_fallback(self):
        r = _rules({"data": 16, "model": 16})
        # 36 heads: tp dropped; flat 4608 feature dim: tp kept
        assert r.spec_for((4608, 4608), ("fsdp", "tp"))[1] == "model"
        assert r.spec_for((100, 36), (None, "heads"))[1] is None

    def test_no_axis_reuse(self):
        r = _rules({"data": 16, "model": 16})
        spec = r.spec_for((32, 32768, 16, 128), ("batch", "kv", "heads", None))
        # kv grabs 'model'; heads must not reuse it
        assert spec[1] == "model" and spec[2] is None

    def test_batch_maps_to_pod_and_data(self):
        r = _rules({"pod": 2, "data": 16, "model": 16})
        spec = r.spec_for((256, 4096), ("batch", None))
        assert tuple(spec[0]) == ("pod", "data")

    def test_batch_of_one_replicates(self):
        r = _rules({"data": 16, "model": 16})
        assert r.spec_for((1, 8), ("batch", None))[0] is None


_MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import AxisRules, batch_specs, param_specs, use_rules
    from repro.launch.mesh import make_mesh
    from repro.models import LM
    from repro.training import OptimizerConfig, adamw_init, init_train_state, make_train_step

    cfg0 = get_smoke_config("smollm-135m")
    cfg = type(cfg0)(**{**cfg0.__dict__, "num_microbatches": 1})
    model = LM(cfg)
    params, opt = init_train_state(model, jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)}

    # single-device reference
    step1 = jax.jit(make_train_step(model, OptimizerConfig(lr=1e-3)))
    p1, _, m1 = step1(params, opt, batch)

    # 2x4 mesh pjit
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = AxisRules(mesh)
    p_sh = param_specs(jax.eval_shape(lambda: params), rules)
    o_sh = param_specs(jax.eval_shape(lambda: opt), rules)
    b_sh = batch_specs(batch, rules)
    with use_rules(rules), mesh:
        stepN = jax.jit(
            make_train_step(model, OptimizerConfig(lr=1e-3)),
            in_shardings=(p_sh, o_sh, b_sh),
        )
        pN, _, mN = stepN(
            jax.device_put(params, p_sh), jax.device_put(opt, o_sh),
            jax.device_put(batch, b_sh),
        )

    max_diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN))
    )
    print(json.dumps({
        "loss1": float(m1["loss"]), "lossN": float(mN["loss"]),
        "max_param_diff": max_diff, "devices": len(jax.devices()),
    }))
    """
)


def test_pjit_train_step_matches_single_device():
    """The sharded train step must be numerically identical to local."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    assert res["loss1"] == pytest.approx(res["lossN"], rel=1e-5)
    assert res["max_param_diff"] < 5e-5


_DRYRUN_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import json
    from repro.launch.dryrun import dryrun_cell
    rec = dryrun_cell("smollm-135m", "decode_32k", multi_pod=True, verbose=False)
    print(json.dumps({
        "fits": rec["fits_hbm"], "chips": rec["chips"],
        "bottleneck": rec["roofline"]["bottleneck"],
        "unscoped": rec["collective_bytes"]["unscoped_while"],
    }))
    """
)


def test_multipod_dryrun_cell():
    """One multi-pod (512-chip) dry-run cell compiles inside the test suite;
    the full 40-cell x 2-mesh sweep runs via launch/dryrun.py --all."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _DRYRUN_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["chips"] == 512
    assert res["fits"] is True
