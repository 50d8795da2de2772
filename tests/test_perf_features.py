"""Correctness of the perf-iteration features: bucketed causal attention,
int8 KV cache, shard_map expert parallelism (subprocess, 8 host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LM, ModelConfig
from repro.models.attention import bucketed_causal_attention, direct_attention


@pytest.mark.parametrize("buckets,window", [(4, 0), (8, 0), (8, 64)])
def test_bucketed_causal_matches_direct(buckets, window):
    rng = np.random.default_rng(buckets + window)
    q = jnp.asarray(rng.normal(0, 1, (2, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 256, 2, 32)), jnp.float32)
    got = bucketed_causal_attention(q, k, v, window=window, block_kv=32, buckets=buckets)
    want = direct_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_int8_kv_decode_close_and_argmax_stable():
    cfg = ModelConfig(
        name="q8", family="dense", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=256, kv_quant="int8",
        dtype="float32", remat=False, tie_embeddings=True,
    )
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 256)
    logits = model.forward(params, tokens)
    _, cache = jax.jit(model.prefill)(params, tokens[:, :-1])
    assert cache["segs"][0]["u0"]["k"].dtype == jnp.int8
    assert "k_scale" in cache["segs"][0]["u0"]
    dl, _ = jax.jit(model.decode_step)(params, cache, tokens[:, -1:])
    rel = float(jnp.max(jnp.abs(dl - logits[:, -1]))) / float(
        jnp.max(jnp.abs(logits[:, -1]))
    )
    assert rel < 0.05
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(dl, -1)), np.asarray(jnp.argmax(logits[:, -1], -1))
    )


def test_int8_kv_init_cache_shapes():
    cfg = ModelConfig(
        name="q8", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=256, kv_quant="int8",
        dtype="float32", remat=False,
    )
    cache = LM(cfg).init_cache(batch=3, cache_len=16, prefilled=15)
    u0 = cache["segs"][0]["u0"]
    assert u0["k"].dtype == jnp.int8 and u0["k_scale"].shape == (2, 3, 16, 2, 1)


_EP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.sharding import AxisRules, use_rules
    from repro.models.moe import moe_mlp, moe_mlp_ep

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    T, D, F, E, k = 64, 32, 48, 8, 2
    x = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    rw = jnp.asarray(rng.normal(0, 1, (D, E)), jnp.float32)
    wg = jnp.asarray(rng.normal(0, 0.2, (E, D, F)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.2, (E, D, F)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.2, (E, F, D)), jnp.float32)

    # big capacity: no drops in either path -> outputs must match
    dense_out, dense_aux = moe_mlp(x, rw, wg, wu, wd, k, capacity_factor=16.0)
    with mesh:
        ep_out, ep_aux = jax.jit(
            lambda *a: moe_mlp_ep(*a, k=k, capacity_factor=16.0, mesh=mesh,
                                  batch_axes=("data",), expert_axis="model")
        )(x, rw, wg, wu, wd)
    diff = float(jnp.max(jnp.abs(dense_out - ep_out)))
    # gradient flows through the a2a pair
    def loss(x):
        y, _ = moe_mlp_ep(x, rw, wg, wu, wd, k=k, capacity_factor=16.0,
                          mesh=mesh, batch_axes=("data",), expert_axis="model")
        return jnp.sum(y * y)
    with mesh:
        g = jax.jit(jax.grad(loss))(x)
    print(json.dumps({
        "diff": diff, "aux_diff": abs(float(dense_aux) - float(ep_aux)),
        "grad_finite": bool(np.isfinite(np.asarray(g)).all()),
        "grad_norm": float(jnp.linalg.norm(g)),
    }))
    """
)


def test_moe_ep_matches_dense_dispatch():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _EP_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["diff"] < 1e-4, res
    # aux is a load-balance regularizer; the EP path averages per-shard
    # statistics (mean of products != product of means) — close, not equal
    assert res["aux_diff"] < 0.2, res
    assert res["grad_finite"] and res["grad_norm"] > 0
