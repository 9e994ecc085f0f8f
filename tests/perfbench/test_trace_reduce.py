"""The reduction from a profiler trace to numbers: by hand on a few
intervals, and on a small trace recorded on a TPU v5e kept beside this
file (``data/small_tpu.xplane.pb``: two tiny programs, ``jit_tiny_step``
run six times and ``jit_tiny_scores`` three times, with sleeps between)."""

import os

import pytest

import pbtest_util as u
from harness import trace_reduce as tr

RECORDED = os.path.join(u.HERE, "data", "small_tpu.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    total, merged = tr.union_ns([(0, 10), (5, 12), (20, 30), (30, 31), (50, 60)])
    assert merged == [(0, 12), (20, 31), (50, 60)]
    assert total == 12 + 11 + 10


def test_reduce_by_hand():
    ms = 1e6
    ops = [("fusion.1", 0 * ms, 2 * ms), ("copy.2", 1 * ms, 2 * ms),   # 0..3
           ("fusion.1", 5 * ms, 1 * ms),                                # 5..6
           ("scatter.3", 6.05 * ms, 3.95 * ms)]                         # ..10
    modules = [("jit_train_batch_parallel(123)", 0, 3 * ms),
               ("jit_train_batch_parallel(123)", 5 * ms, 5 * ms),
               ("jit_scores(9)", 5 * ms, 1 * ms)]
    host = [("outer", 0, 10 * ms), ("prep_requests", 3.2 * ms, 1.5 * ms)]
    red = tr.reduce_planes({"/device:TPU:0": {tr.OPS_LINE: ops,
                                              tr.MODULES_LINE: modules}},
                           host, ["jit_train_batch_parallel", "jit_scores"])
    dev = red["devices"][0]
    assert dev["window_s"] == pytest.approx(0.010)
    assert dev["busy_s"] == pytest.approx(0.003 + 0.001 + 0.00395)
    assert red["busy_s"] == dev["busy_s"] and red["window_s"] == dev["window_s"]
    assert red["programs"]["jit_train_batch_parallel"] == \
        {"events": 2, "seconds": pytest.approx(0.008)}
    assert red["programs"]["jit_scores"]["events"] == 1
    ops_top = dict((k, v) for k, v in red["device_ops"])
    assert ops_top["scatter.3"] == pytest.approx(0.00395)
    assert ops_top["fusion.1"] == pytest.approx(0.003)
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    # the 2 ms gap falls in prep_requests (the innermost host span open at
    # its middle); the 0.05 ms gap is a short one
    assert gaps["prep_requests"] == pytest.approx(0.002)
    assert gaps["gaps_under_0.1_ms"] == pytest.approx(0.00005)


def test_an_operation_nested_in_another_is_counted_once():
    ms = 1e6
    ops = [("while.1", 0, 10 * ms), ("dus.2", 1 * ms, 3 * ms),
           ("dus.2", 5 * ms, 3 * ms), ("add.3", 10 * ms, 1 * ms)]
    assert tr.self_ns(ops) == [4 * ms, 3 * ms, 3 * ms, 1 * ms]
    red = tr.reduce_planes({"d": {tr.OPS_LINE: ops, tr.MODULES_LINE: [
        ("jit_step(1)", 0, 11 * ms)]}}, [], [])
    top = dict((k, v) for k, v in red["device_ops"])
    assert top["while.1"] == pytest.approx(0.004)
    assert top["dus.2"] == pytest.approx(0.006)
    assert sum(top.values()) == pytest.approx(red["busy_s"])
    assert red["modules"] == {"jit_step": {"events": 1, "seconds":
                                           pytest.approx(0.011)}}


def test_two_devices_are_averaged_and_an_empty_trace_reads_nothing():
    ms = 1e6
    red = tr.reduce_planes(
        {"a": {tr.OPS_LINE: [("x", 0, 10 * ms)]},
         "b": {tr.OPS_LINE: [("x", 0, 2 * ms), ("x", 8 * ms, 2 * ms)]}},
        [], [])
    assert red["busy_s"] == pytest.approx((0.010 + 0.004) / 2)
    assert red["window_s"] == pytest.approx(0.010)
    empty = tr.reduce_planes({}, [], ["p"])
    assert empty["devices"] == [] and empty["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_tpu_trace():
    red = tr.reduce_path(RECORDED, ["jit_tiny_step", "jit_tiny_scores"])
    assert any(p.startswith("/device:TPU") for p in red["planes"])
    assert tr.OPS_LINE in red["lines"] and tr.MODULES_LINE in red["lines"]
    assert red["programs"]["jit_tiny_step"]["events"] == 6
    assert red["programs"]["jit_tiny_scores"]["events"] == 3
    dev = red["devices"][0]
    # six tiny steps with 2 ms sleeps between: mostly idle, never over
    assert 0 < dev["busy_s"] < 0.5 * dev["window_s"]
    step_s = red["programs"]["jit_tiny_step"]["seconds"]
    assert 0 < step_s <= dev["busy_s"] * 1.05
    assert red["device_ops"] and red["device_ops"][0][1] > 0
