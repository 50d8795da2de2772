"""The trace reduction on small synthetic event lists."""
import numpy as np
import pytest

from bench.lib import trace


def test_merge_unions_overlaps_and_keeps_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert trace.merge(iv).tolist() == [[0, 3], [5, 9], [10, 11]]


def test_complement_is_idle_time_inside_the_window():
    busy = np.array([[2, 3], [5, 9]], float)
    assert trace.complement(busy, 0, 10).tolist() == [[0, 2], [3, 5], [9, 10]]
    assert trace.complement(np.zeros((0, 2)), 0, 4).tolist() == [[0, 4]]


def test_innermost_names_each_instant_by_the_deepest_span():
    spans = [("outer", 0, 10), ("inner", 2, 4), ("inner2", 6, 7)]
    pieces = trace.innermost(spans)
    total = {}
    for name, s, e in pieces:
        total[name] = total.get(name, 0) + (e - s)
    assert total == {"outer": 7, "inner": 2, "inner2": 1}


def test_attribute_gives_idle_time_to_the_host_activity():
    gaps = np.array([[0, 2], [3, 6]], float)
    spans = [("bench.pump", 0, 4), ("bench.route", 1, 2)]
    got = trace.attribute(gaps, spans)
    # pump covers [0,1) and [2,4) of its own; route [1,2); [4,6) no span
    assert got == pytest.approx({"bench.pump": 2.0, "bench.route": 1.0,
                                 "host_other": 2.0})


def _raw():
    ops = {
        "/device:TPU:0": [("fusion.1", 100, 200), ("fusion.2", 150, 300),
                          ("copy", 600, 700), ("early", 0, 50)],
        "/device:TPU:1": [("fusion.1", 100, 600)],
    }
    modules = {
        "/device:TPU:0": [("jit__wave_scan_core(7)", 100, 300),
                          ("jit__wave_scan_core(7)", 600, 700)],
        "/device:TPU:1": [("jit__sur_greedy_scan_core(3)", 100, 600)],
    }
    host = [("bench.window", 100, 1100), ("bench.route", 300, 500),
            ("bench.pump", 700, 1000)]
    return {"ops": ops, "modules": modules, "host": host}


def test_reduce_busy_idle_programs_and_gaps():
    red = trace.reduce(_raw())
    # chip 0 busy [100,300) + [600,700) = 300 ns; chip 1 busy 500 ns
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx(400e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["idle_pct"] == pytest.approx(60.0)
    assert red["programs"] == pytest.approx(
        {"jit__wave_scan_core": 300e-9, "jit__sur_greedy_scan_core": 500e-9})
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx((100 + 500) / 2 * 1e-9)
    assert "early" not in ops                      # outside the window
    gaps = dict(red["idle_gaps"])
    # chip 0 idle [300,600) [700,1100); chip 1 idle [600,1100)
    assert gaps["bench.route"] == pytest.approx(200e-9 / 2)
    assert gaps["bench.pump"] == pytest.approx((300 + 300) * 1e-9 / 2)
    assert sum(gaps.values()) == pytest.approx(1.0e-6 - 400e-9)


def test_reduce_needs_the_window_span():
    raw = _raw()
    raw["host"] = raw["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(raw)
