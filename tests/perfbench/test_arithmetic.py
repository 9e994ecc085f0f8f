"""The benchmark's arithmetic against hand counts: percentiles and rates
with a stall inside the window, needed bytes, the
flush histogram, span differences, bfloat16 rounding."""

import numpy as np
import pytest

import pbtest_util
from harness import needed, stats

reference = pbtest_util.subject(1 << 16).ref


def rec(group, t_send, t_recv, result=500, ok=True):
    return (group, 0, 0, t_send, t_send, t_recv, ok, result)


def test_rate_counts_all_the_windows_seconds_across_a_stall():
    # 10 calls of 500 rows answered in [0, 4), nothing in [4, 8): a stall,
    # then 10 more in [8, 10). The window is [0, 10).
    records = [rec("g", t, t + 0.2) for t in np.linspace(0, 3.7, 10)] \
        + [rec("g", t, t + 0.1) for t in np.linspace(8, 9.8, 10)]
    inside = stats.in_window(records, 0.0, 10.0)
    assert len(inside) == 20
    assert stats.rate(sum(r[7] for r in inside), 0.0, 10.0) == 1000.0
    # a call counts where its answer lands: sent inside, answered after
    late = rec("g", 9.9, 10.05)
    assert stats.in_window(records + [late], 0.0, 10.0) == inside


def test_tail_sees_the_stall():
    fast = [rec("g", t, t + 0.010) for t in range(95)]
    stalled = [rec("g", 100 + t, 100 + t + 2.0) for t in range(5)]
    lat = stats.latencies_ms(fast + stalled)
    assert stats.percentile(lat, 50) == pytest.approx(10.0)
    # position 0.95 * 99 = 94.05: between the last fast and first stalled
    assert stats.percentile(lat, 95) == pytest.approx(10.0 + 0.05 * 1990.0)
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0


def test_open_loop_latency_counts_from_when_the_call_was_due():
    r = ("g", 0, 0, 1.0, 1.5, 2.0, True, 500)   # due 1.0, sent late at 1.5
    assert stats.latencies_ms([r]) == [1000.0]


def test_flush_histogram_groups_answers_released_together():
    times = [1.000, 1.001, 1.002, 1.200, 1.201, 1.400]
    records = [rec("g", 0.0, t) for t in times]
    assert stats.flush_histogram(records, 500, 0.025) == \
        {500: 1, 1000: 1, 1500: 1}


def test_span_and_counter_deltas():
    s0 = {"trace.rpc.train.count": 10, "trace.rpc.train.mean_ms": 100.0,
          "c": 5}
    s1 = {"trace.rpc.train.count": 30, "trace.rpc.train.mean_ms": 200.0,
          "c": 9}
    assert stats.span_delta(s0, s1, "rpc.train") == (20, 5000.0)
    assert stats.counter_delta(s0, s1, "c") == 4.0
    assert stats.span_delta({}, {}, "x") == (0, 0.0)


def test_needed_bytes_by_hand():
    # B=2 rows, K=3 features, L=2 labels
    # read: 6 entries x (4 + 4) = 48; gathers 6 x 4 tables x 2 labels x 4
    # = 192; update 6 x 2 rows x 2 tables x (4 + 4) = 192; labels 2 x 4 = 8
    assert needed.train_flush_bytes(2, 3, 2) == 48 + 192 + 192 + 8
    # classify: 48 + 6 x 2 tables x 2 labels x 4 = 96, + 2 x 2 x 4 = 16
    assert needed.classify_flush_bytes(2, 3, 2) == 48 + 96 + 16
    # 819e9 bytes in one second at the peak is 100%
    assert needed.roofline_share_pct(819e9, 1.0, 819e9) == 100.0
    assert needed.roofline_share_pct(819e9, 4.0, 819e9) == 25.0


def test_bfloat16_rounding_is_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.005859375, 1.01171875, -3.14159],
                 np.float32)
    got = reference.to_bfloat16(x)
    # 1 + 2^-8 is a tie: rounds to even (1.0); 1 + 3*2^-9 rounds up
    assert got[0] == 1.0 and got[1] == 1.0
    assert got[2] == np.float32(1.0078125)
    assert got[3] == np.float32(1.0078125) or got[3] == np.float32(1.015625)
    assert abs(got[4] + 3.14159) < 0.01
    assert (got.view(np.uint32) & 0xFFFF == 0).all()
