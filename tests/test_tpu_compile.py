"""The served path compiles for a TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a described, not
attached, v5e. This file compiles the two device programs of the main path
(`_wave_scan`, `_sur_greedy_scan`) at the buckets the launcher's deployment
warms, and both serving kernels natively at the shapes that deployment
serves. What the chip's compiler would refuse fails here at no chip time.

The topology is described inside a fixture, never while a module imports:
only one process may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off here, since a program
compiled for a described chip is written to it but cannot be read back.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import selection as selection_mod
from repro.core.x64 import x64
from repro.kernels.belief_aggregate import belief_aggregate_pallas
from repro.kernels.mc_correctness import mc_correctness_grouped_pallas
from repro.launch.serve import BUDGET, DEPLOYMENT, MAX_BATCH, build_deployment
from repro.serving import router as router_mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def warmed_shapes():
    """Arguments of every program the deployment's prewarm builds:
    ``{"wave": [args, ...], "plan": [args, ...]}``, recorded on the CPU."""
    _, router = build_deployment(**DEPLOYMENT)
    seen = {"wave": [], "plan": []}
    real_plan = selection_mod._sur_greedy_scan

    def record_plan(*args, **kw):
        seen["plan"].append((args, kw))
        return real_plan(*args, **kw)

    def record_wave(*args, **kw):
        seen["wave"].append((args, kw))

    with pytest.MonkeyPatch.context() as m:
        m.setattr(selection_mod, "_sur_greedy_scan", record_plan)
        m.setattr(router_mod, "_wave_scan", record_wave)
        router.plans.prewarm(budgets=[BUDGET])
        router.prewarm_compile(max_batch=MAX_BATCH)
    assert seen["wave"] and seen["plan"]
    return seen


def _on(sharding, args):
    """Array arguments as shapes placed on ``sharding``; scalars as is."""
    return [
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=sharding)
        if np.ndim(a) else a
        for a in args
    ]


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("name", ["wave", "plan"])
def test_device_program_compiles_for_v5e(name, one_chip, warmed_shapes):
    fn = {"wave": router_mod._wave_scan,
          "plan": selection_mod._sur_greedy_scan}[name]
    for args, kw in warmed_shapes[name]:
        with x64():
            compiled = fn.lower(*_on(one_chip, args), **kw).compile()
        assert compiled.as_text()


def test_belief_aggregate_compiles_natively(one_chip, warmed_shapes):
    """At the wave program's widest prefix expansion: B * (T + 1) rows."""
    K = DEPLOYMENT["classes"]
    P, B = max(np.shape(args[0]) for args, _ in warmed_shapes["wave"])
    T = (P - 2) // router_mod._WAVE_PLANES       # packed (6 * T + 2, B)
    rows = B * (T + 1)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = belief_aggregate_pallas.lower(
        S((rows, T), np.int32), S((rows, T), np.float32), S((rows,), np.float32),
        num_classes=K, tile=512, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mc_correctness_grouped_compiles_natively(one_chip, warmed_shapes):
    """One group per cluster, the planner's theta bucket, the three Alg. 2
    candidates per group."""
    K, G = DEPLOYMENT["classes"], DEPLOYMENT["clusters"]
    (args, _), = warmed_shapes["plan"]
    _, L, theta = np.shape(args[0])          # resp_t (Gp, L, theta)
    C = 3
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = mc_correctness_grouped_pallas.lower(
        S((G, theta, L), np.int32), S((G, C, L), np.float32),
        S((G, L), np.float32), S((G,), np.float32), S((G, theta), np.float32),
        S((G,), np.float32), num_classes=K, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
