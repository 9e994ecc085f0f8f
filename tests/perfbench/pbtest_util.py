"""Shared by the benchmark's tests: where the benchmark lives, and a
throw-away checkout in which a test may add files."""

import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def load_config(name="criteo_arow"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def generator(config=None):
    """The row generator the configuration names, as the harness loads
    it: ``perfbench/generators/<data.generator>.py``."""
    from harness import cell

    config = config or load_config()
    return cell.load_module(os.path.join(BENCH, "generators"),
                            config["data"]["generator"])


def make_rows(config, seed, stream, n, key_suffix=""):
    return generator(config).make_rows(config["data"], seed, stream, n,
                                       key_suffix)


def subject(dim, config=None):
    """The configuration with the engine, the reference and the generator
    it names, as the harness loads them."""
    from harness import cell, check

    config = config or load_config()
    return check.Subject(
        config, cell.load_module(os.path.join(BENCH, "engines"),
                                 config["engine"]),
        cell.load_module(os.path.join(BENCH, "references"),
                         config["reference"]), generator(config), dim)


#: a check plan that needs no timing: lone calls only. On the CPU a step
#: takes milliseconds, so nothing queues behind a blocker there.
LONE_PLAN = [
    {"op": "clear"},
    {"op": "train", "calls": 2, "rows": 300},
    {"op": "classify", "calls": 2, "rows": 50},
]


def make_checkout(tmp_path, replicas=1):
    """A directory that looks like a checkout to the harness: the
    benchmark copied (so a test can add files), the program linked."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for link in ("jubatus_tpu", "native"):
        os.symlink(os.path.join(REPO, link), os.path.join(root, link))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return root, bench


def small_traffic(groups, plan=LONE_PLAN, warm=None, window_scores=None):
    warmup = {"calls": warm or [], "stream_calls": 8,
              "steady_samples": 1, "timeout_s": 300}
    check = {"steps": plan, "limits": {"score_gap": 1e-4}}
    if window_scores:
        check["window_scores"] = window_scores
        check["limits"]["window_score_gap"] = 1e-4
    return {
        "name": "small", "groups": groups,
        "warmup": warmup,
        "run_past_s": 0.1, "flush_gap_ms": 25,
        "trace": {"start_fraction": 0.3, "seconds": 1},
        "check": check,
    }


def add_cell(root, bench, name, config, traffic_name, traffic, like):
    """What a later PR does: new files and a new ``workloads`` entry. The
    new cell reports the metrics that the cell ``like`` reports."""
    with open(os.path.join(root, "perfbench", "traffic",
                           traffic_name + ".json"), "w") as f:
        json.dump(traffic, f)
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic_name, "chips": 1,
                               "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def rehearse(root, workload, seed=2200000123, seconds=1.5, trace=False,
             server_entry=None):
    from harness import cell

    return cell.run_cell(root, workload, seed, seconds, trace,
                         time.monotonic(), rehearse=True,
                         server_entry=server_entry)
