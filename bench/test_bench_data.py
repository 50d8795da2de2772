"""The benchmark is data: every cell resolves by name to its files, the
traffic is a pure function of its seed (its drift and labels included),
the mixes that predate drift still make the same arrays, and the answer
table gives one answer per (arm, query)."""
import hashlib
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
    assert set(config["correct_limits"]) == set(check.names(config["correct_limits"]))
    assert set(check.CHECKS) <= set(config["correct_limits"])
    if "feedback" in config:
        assert set(check.FEEDBACK_CHECKS) <= set(config["correct_limits"])
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
    dep = harness.Deployment(config)
    pool = dep.pool
    mix = dict(mix, rate_qps=300.0)
    seed = 2**31 + 77
    a = dep.traffic(mix, seed, 7.0)
    b = dep.traffic(mix, seed, 7.0)
    c = dep.traffic(mix, seed + 1, 7.0)
    for f in ("offsets", "payloads", "emb", "budgets", "answers"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert not np.array_equal(a.offsets, c.offsets)
    assert set(np.unique(a.budgets)) <= set(config["budgets"])
    assert a.answers.shape == (pool.num_arms, a.n)


def _digest(tr) -> str:
    h = hashlib.sha256()
    for f in ("offsets", "payloads", "emb", "budgets", "answers"):
        a = getattr(tr, f)
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of the arrays the mixes made before drift and labels existed, at
# their own rates, a 1 s window after their 2 s warm-up
PARENT_ARRAYS = {
    ("agnews-poisson", 2**31 + 77):
        "d8ae2d2e7c3d6df9941c048e1d3564d08809dca44d726d5362bcde15ec42e5b5",
    ("agnews-poisson", 3141592653):
        "619864e47a52a42688a38281f679f36c8156b0786da52c5ffc6e015a99183d43",
    ("agnews-overload", 2**31 + 77):
        "a47a32fdd4c2a8ce211b7cf38cf29048bde9d7124cf9dd82340394b1d15b004e",
    ("agnews-overload", 3141592653):
        "1df5a63e88585e26b63c36b1ec5bc1a820a35cb9d11f9435ad3763913d0a5f8b",
}


@pytest.mark.parametrize("cell,seed", sorted(PARENT_ARRAYS))
def test_existing_mixes_generate_the_same_arrays_as_before(cell, seed):
    _, config, mix = harness.load_cell(cell, BENCH)
    direct = traffic.generate(mix, Pool(**config["pool"]), config["budgets"], seed, 1.0)
    assert _digest(direct) == PARENT_ARRAYS[cell, seed]
    assert not direct.labels and direct.drift == []
    via = harness.Deployment(config).traffic(mix, seed, 1.0)
    assert _digest(via) == PARENT_ARRAYS[cell, seed]


def _drift_traffic(seed, seconds=20.0, **keys):
    _, config, mix = harness.load_cell("hellaswag-api-drift", BENCH)
    dep = harness.Deployment(config)
    mix = dict(mix, rate_qps=400.0, **keys)
    return dep, mix, dep.traffic(mix, seed, seconds)


def test_drift_segments_and_labels_are_a_pure_function_of_the_seed():
    seed = 2**32 + 5
    dep, mix, a = _drift_traffic(seed)
    b = dep.traffic(mix, seed, 20.0)
    c = dep.traffic(mix, seed + 1, 20.0)
    assert a.labels and traffic.drift_events(mix, 20.0) == 6
    # drifts at 3, 9 and 15 s, each restored 3 s later
    assert [(s, e) for s, e, _ in a.drift] == [(3.0, 6.0), (9.0, 12.0), (15.0, 18.0)]
    for (s1, e1, d1), (s2, e2, d2) in zip(a.drift, b.drift):
        assert (s1, e1) == (s2, e2) and np.array_equal(d1, d2)
    assert all(d.size == 4 and np.unique(d).size == 4 for _, _, d in a.drift)
    assert any(not np.array_equal(d1, d2) for (_, _, d1), (_, _, d2)
               in zip(a.drift, c.drift))
    for f in ("offsets", "payloads", "emb", "budgets", "answers"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f


def test_each_answer_is_drawn_under_the_truth_at_its_due_time():
    dep, mix, tr = _drift_traffic(11)
    still = {k: v for k, v in mix.items() if k not in traffic.DRIFT_KEYS}
    plain = traffic.generate(still, dep.pool, dep.budgets, 11, 20.0)
    arms = traffic.drift_arm_sets(mix, dep.calibration.p, dep.pool.costs,
                                  dep.pool.num_classes)
    cid, labels = tr.payloads[:, 0], tr.payloads[:, 1]
    due = np.concatenate([np.full(tr.n_warm, -1.0), tr.offsets[tr.n_warm:]])
    drifted = np.zeros((dep.pool.num_arms, tr.n), bool)
    for start, end, clusters in tr.drift:
        rows = (due >= start) & (due < end) & np.isin(cid, clusters)
        drifted |= rows[None, :] & arms[cid].T
    # the same draws: only answers of drifted (arm, request) cells change
    assert np.array_equal(tr.answers[~drifted], plain.answers[~drifted])
    hit = (tr.answers == labels[None, :])[drifted].mean()
    assert abs(hit - mix["drift_p"]) < 0.03
    assert drifted.sum() > 2000


def test_a_drift_needs_all_its_keys_and_labels_a_known_mode(tmp_path):
    path = tmp_path / "mix.json"
    path.write_text('{"rate_qps": 1, "drift_period_s": 3}')
    with pytest.raises(ValueError):
        traffic.load_mix(path)
    path.write_text('{"rate_qps": 1, "labels": "later"}')
    with pytest.raises(ValueError):
        traffic.load_mix(path)


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
