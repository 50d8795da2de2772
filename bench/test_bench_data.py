"""The benchmark is data: every cell resolves by name to its files, the
traffic is a pure function of its seed, and the answer table gives one
answer per (arm, query)."""
import re

import numpy as np
import pytest

from bench.lib import check, harness, traffic
from bench.lib.pool import Pool

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_config_traffic_and_readers(cell):
    w, config, mix = harness.load_cell(cell, BENCH)
    assert config["name"] == w["config"]
    assert set(config["correct_limits"]) == set(check.CHECKS)
    assert mix["rate_qps"] > 0
    e2e = harness.cell_metrics(BENCH, cell, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layer = harness.cell_metrics(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_traffic_is_a_pure_function_of_the_seed(cell):
    _, config, mix = harness.load_cell(cell, BENCH)
    pool = Pool(**config["pool"])
    mix = dict(mix, rate_qps=300.0)
    seed = 2**31 + 77
    a = traffic.generate(mix, pool, config["budgets"], seed, 7.0)
    b = traffic.generate(mix, pool, config["budgets"], seed, 7.0)
    c = traffic.generate(mix, pool, config["budgets"], seed + 1, 7.0)
    for f in ("offsets", "payloads", "emb", "budgets", "answers"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert not np.array_equal(a.offsets, c.offsets)
    assert set(np.unique(a.budgets)) <= set(config["budgets"])
    assert a.answers.shape == (pool.num_arms, a.n)


def test_every_seed_offers_the_same_amount_of_work():
    off = [traffic.poisson_offsets(1234.5, 4.0, np.random.default_rng(s))
           for s in (1, 2**32 + 3)]
    assert off[0].size == off[1].size == 4938
    assert all(np.all(np.diff(o) >= 0) and 0 <= o[0] and o[-1] < 4.0 for o in off)
    assert not np.array_equal(off[0], off[1])


def test_answer_table_gives_one_answer_per_arm_and_query():
    _, config, mix = harness.load_cell("agnews-poisson", BENCH)
    dep = harness.Deployment(config)
    tr = traffic.generate(dict(mix, rate_qps=200.0), dep.pool, dep.budgets, 5, 1.0)
    dep.engine.answers = tr.answers
    L = dep.pool.num_arms
    payloads = dep.engine.prepare_payloads(tr.payloads[:50])
    sched = np.tile(np.arange(L)[:, None], (1, 50))
    grid = dep.engine.invoke_grid(sched, payloads)
    rows = np.arange(50)
    for arm in range(L):
        again = dep.engine.invoke_rows(np.full(50, arm), payloads, rows)
        assert np.array_equal(grid[arm], again)
        assert np.array_equal(grid[arm], tr.answers[arm, :50])


def test_eq1_error_model_hits_at_the_true_rate():
    pool = Pool(arms=3, classes=4, clusters=1, emb_dim=4, skill_spread=0.0,
                base_low=0.2, base_high=0.8, pool_seed=0)
    rng = np.random.default_rng(0)
    labels = rng.integers(4, size=20000)
    ans = pool.answers(np.broadcast_to(pool.p_true[0], (20000, 3)), labels, rng)
    hit = (ans == labels[None, :]).mean(axis=1)
    assert np.allclose(hit, pool.p_true[0], atol=0.015)
    wrong = ans[0][ans[0] != labels]
    assert set(np.unique(wrong)) == {0, 1, 2, 3}
