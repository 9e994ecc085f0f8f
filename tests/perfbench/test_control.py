"""The control: the plain reference computed in bfloat16, put in the
program's place, has to come out as not correct; the float32 reference in
the program's place is exactly correct. At a size a test run can hold
(the chip readings at the cell's own size are in PERF.md)."""

import json
import os

import pytest

import pbtest_util as u
from harness import check

#: the train cell's burst at a size a test run can hold (the cell's own is
#: 192 calls of 500 rows, which need the cell's width to share no column)
TEST_BURST_CALLS = 12


def plan_for(traffic, seed, dim=1 << 20):
    with open(os.path.join(u.BENCH, "traffic", traffic + ".json")) as f:
        chk = json.load(f)["check"]
    plan = check.Plan(u.subject(dim), chk["steps"], [None], "n", seed,
                      lambda i: {}, lambda msg: None)
    # drive the plan's script without servers: the same rows, no answers
    for step in chk["steps"]:
        if step["op"] == "clear":
            plan.script.append(("clear", [0]))
        elif step["op"] == "train":
            for _ in range(step["calls"]):
                plan.script.append(("flush", (0, plan._rows(step["rows"]))))
        elif step["op"] == "train_burst":
            plan.burst_calls[0] = plan._disjoint_calls(
                min(step["calls"], TEST_BURST_CALLS), step["rows"])
            plan.script += [("flush", (0, rows))
                            for rows in plan.burst_calls[0]]
        elif step["op"] == "classify":
            fresh = [plan._rows(step["rows"]) for _ in range(step["calls"])]
            probes = plan._probes(step, 0, fresh)
            plan.answers += [(0, rows, None) for rows in probes]
            plan.script.append(("scores", len(probes)))
    return plan, chk["limits"]["score_gap"]


@pytest.mark.parametrize("traffic", ["train", "serve"])
@pytest.mark.parametrize("seed", [1, 2, 3000000003])
def test_bfloat16_control_fails_and_float32_passes(traffic, seed):
    plan, limit = plan_for(traffic, seed)
    exact = plan.control_answers("float32")
    gap, wrong, _ = plan.score_gap("float32", answers=exact)
    assert gap == 0.0 and wrong == 0
    low = plan.control_answers("bfloat16")
    gap, wrong, _ = plan.score_gap("float32", answers=low)
    assert wrong == 0
    assert gap > 3 * limit, (gap, limit)


def test_calls_of_a_burst_share_no_column_so_their_grouping_is_free():
    """Whichever calls share a flush, and in whatever order the flushes go,
    the model is the same: the reference gives equal scores for one call a
    flush, all calls in one flush, and the calls in reverse."""
    import numpy as np

    plan, _ = plan_for("train", 7, dim=1 << 16)
    calls = plan._disjoint_calls(6, 40)
    sub = plan.subject
    cols = [set(c for r in call for c in sub.featurize(r)) for call in calls]
    assert all(not (a & b) for i, a in enumerate(cols) for b in cols[i + 1:])
    probe = sub.batch([r for call in calls for r in call[:5]])

    def scores(groups):
        batches = [sub.batch(g) for g in groups]
        m = sub.model(sub.ref.universe_of(batches + [probe]), "float32")
        m._slot("1"), m._slot("0")
        for b in batches:
            m.train_flush(b)
        return m.scores(probe)

    one_each = scores(calls)
    together = scores([[r for call in calls for r in call]])
    reverse = scores(calls[::-1])
    assert np.abs(one_each).max() > 0.1
    assert np.allclose(one_each, together, rtol=1e-5, atol=1e-6)
    assert np.allclose(one_each, reverse, rtol=1e-5, atol=1e-6)


def test_a_wrong_label_or_a_missing_row_is_counted():
    plan, _limit = plan_for("serve", 5)
    answers = plan.control_answers("float32")
    i, rows, res = answers[0]
    res[0][0][0] = "unknown-label"
    answers[1] = (answers[1][0], answers[1][1], answers[1][2][:-1])
    _gap, wrong, _ = plan.score_gap("float32", answers=answers)
    assert wrong == 2


def test_probe_rows_of_a_burst_come_from_every_call_in_calls_of_the_steps_size():
    plan, _ = plan_for("train", 9, dim=1 << 18)
    step = {"op": "classify", "calls": 0, "rows": 25, "burst_rows": 5}
    probes = plan._probes(step, 0, [])
    assert [len(p) for p in probes] == [25, 25, 10]
    flat = [r for p in probes for r in p]
    assert flat == [r for call in plan.burst_calls[0] for r in call[:5]]
