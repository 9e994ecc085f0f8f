"""The one command's CPU rehearsal, reachable only from here: a run of
each cell's kind at a tiny width, through real server processes. It names
its platform, reports nothing under a device metric's name, and comes out
``correct``; with a fault planted in the timed path it comes out not."""

import json
import os
import subprocess
import sys

import pytest

import pbtest_util as u

FAULTY = os.path.join(u.HERE, "faulty_server.py")

TRAIN = [{"name": "train", "method": "train", "connections": 6,
          "rows_per_call": 100, "loop": "closed", "pool_calls": 12,
          "server": "each"}]
SERVE = [{"name": "train", "method": "train", "connections": 1,
          "rows_per_call": 100, "loop": "closed", "pool_calls": 8,
          "server": "each"},
         {"name": "classify", "method": "classify", "connections": 3,
          "rows_per_call": 40, "loop": "closed", "pool_calls": 8,
          "server": "each", "keep_every": 3}]
#: a lone call, then closed loops of calls that share no column, as the
#: train cell's plan has them (on the CPU a step takes milliseconds, so
#: the flushes' sizes are not held to anything here)
BURST_PLAN = [
    {"op": "clear"},
    {"op": "train", "calls": 1, "rows": 300},
    {"op": "train_burst", "calls": 12, "rows": 40, "connections": 4},
    {"op": "classify", "calls": 2, "rows": 50, "burst_rows": 5},
]

def checkout(tmp_path, kind):
    root, bench = u.make_checkout(tmp_path)
    if kind == "train":
        u.add_cell(root, bench, "criteo_arow.t_train", "criteo_arow",
                   "t_train", u.small_traffic(TRAIN, plan=BURST_PLAN),
                   like="criteo_arow.train")
        return root, "criteo_arow.t_train"
    if kind == "serve":
        u.add_cell(root, bench, "criteo_arow.t_serve", "criteo_arow",
                   "t_serve", u.small_traffic(SERVE, warm=[
                       {"method": "train", "rows": 100},
                       {"method": "classify", "rows": 8},
                       {"method": "classify", "rows": 40},
                       {"method": "classify", "rows": 80}],
                       window_scores={"sample": 12}),
                   like="criteo_arow.serve")
        return root, "criteo_arow.t_serve"
    raise ValueError(kind)


def entry(fault):
    return [sys.executable, FAULTY, fault]


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_rehearsal_is_correct_and_names_its_platform(tmp_path, kind):
    root, cell = checkout(tmp_path, kind)
    res = u.rehearse(root, cell)
    assert res["correct"] is True, res["compared"]
    # the serving cell's own answers are held against the reference too
    assert ("window_score_gap" in res["compared"]) == (kind == "serve")
    assert res["device"]["platform"] == "cpu" and "rehearsal" in res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "compared"
    assert all(set(v) == {"value", "limit"} for v in res["compared"].values())


@pytest.mark.parametrize("kind,fault", [
    ("train", "state_unchanged"),
    ("train", "half_batch"),
    ("serve", "answer_altered"),
])
def test_a_fault_in_the_timed_path_is_not_correct(tmp_path, kind, fault):
    root, cell = checkout(tmp_path, kind)
    res = u.rehearse(root, cell, server_entry=entry(fault))
    assert res["correct"] is False
    gap = res["compared"]["score_gap"]
    assert not gap["value"] <= gap["limit"]


def test_traced_rehearsal_reports_no_device_metric(tmp_path):
    root, cell = checkout(tmp_path, "serve")
    res = u.rehearse(root, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    # a CPU trace has no device plane: rooflines, idle shares and device
    # times are left out, never 0
    for name in res["metrics"]:
        assert "roofline" not in name and "idle_share" not in name \
            and "device_ms" not in name and name != "device.bytes_in_use"
    assert res["metrics"]["compile.in_window"]["value"] == 0
    assert "coalescer.classify_rows_per_flush" in res["metrics"]


def test_a_burst_that_was_not_cut_into_the_timed_flushes_is_not_correct(
        tmp_path):
    """The plan holds its burst to the flush sizes the traffic file names:
    where the coalescer cut it otherwise, the timed shape was not compared,
    the plan is run again and, thrice unlucky, the run is not correct."""
    root, bench = u.make_checkout(tmp_path)
    plan = [dict(step, full_rows=40 * 9, min_full_flushes=1)
            if step["op"] == "train_burst" else step for step in BURST_PLAN]
    u.add_cell(root, bench, "criteo_arow.t_cut", "criteo_arow", "t_cut",
               u.small_traffic(TRAIN, plan=plan), like="criteo_arow.train")
    res = u.rehearse(root, "criteo_arow.t_cut")
    # four connections never have nine calls out at once
    assert res["correct"] is False
    assert res["compared"]["flushes_not_as_planned"]["value"] == 1
    assert res["compared"]["score_gap"]["value"] \
        <= res["compared"]["score_gap"]["limit"]


#: what the watcher sampled of three attempts of ``criteo_arow.train``'s
#: burst on the chip (my chip runs, PR 33: run p1), as (flushes, rows)
SAMPLED = [
    [(2, 1000), (1, 1000), (2, 14000), (2, 16000), (2, 15500), (2, 13500),
     (3, 20000), (1, 5000), (2, 4000), (2, 1000), (3, 2500), (3, 1500),
     (1, 500), (1, 500)],
    [(2, 1000), (2, 10000), (1, 8000), (1, 8000), (6, 45000), (2, 13500),
     (2, 4000), (2, 1000), (3, 3500), (1, 500), (1, 500), (1, 500), (1, 500)],
    [(2, 1000), (2, 3500), (1, 6000), (1, 8000), (2, 16000), (2, 16000),
     (1, 8000), (2, 16000), (2, 14500), (2, 4000), (3, 1500), (1, 500),
     (1, 500), (1, 500)],
]


@pytest.mark.parametrize("steps,exact,timed", zip(SAMPLED, (2, 2, 8),
                                                   (11, 10, 10)))
def test_a_flush_of_thirteen_to_sixteen_calls_is_of_the_timed_size(
        steps, exact, timed):
    """The train cells' traffic file counts a sampled flush of 6,500 to
    8,000 rows as one of the timed size; with no ``full_rows_min`` only
    exact ones count, and the first two of these attempts fell short of
    the five asked."""
    from harness import check

    with open(os.path.join(u.BENCH, "traffic", "train.json")) as f:
        step = next(s for s in json.load(f)["check"]["steps"]
                    if s["op"] == "train_burst")
    assert (step["full_rows_min"], step["full_rows"]) == (6500, 8000)
    assert check.full_flushes(steps, 8000, 8000) == exact
    assert check.full_flushes(steps, 6500, 8000) == timed \
        >= step["min_full_flushes"]
    # single calls and the start's short flushes never count
    assert check.full_flushes([(3, 1500), (1, 500), (2, 10000)],
                              6500, 8000) == 0


def test_the_command_fails_without_a_tpu():
    """Here JAX is held to the CPU: the command exits non-zero and prints
    no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(u.BENCH, "run.py"), "--workload",
         "criteo_arow.serve", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=u.REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr
