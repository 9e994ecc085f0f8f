"""A later PR adds a configuration, a traffic mix, a per-layer metric, a
row generator and a cell with new files and one ``workloads`` entry, and
edits no file that is there. This does exactly that in a throw-away
checkout and runs the new cells (CPU rehearsal)."""

import json
import os
import sys

import pytest

import pbtest_util as u
from test_rehearsal import BURST_PLAN, TRAIN

NEW_METRIC = '''"""A test's per-layer metric: train calls answered in the window."""

NAME = "test.train_calls"


def read(run):
    return len(run.window("train")) or None
'''


FIVE_WAY = '''"""A test's generator: rows of any number of labels over
``string_keys`` string keys and ``num_keys`` numeric ones. A row's first
string value names its label three times in four, which a linear model can
learn."""

import numpy as np


def make_rows(data, seed, stream, n, key_suffix=""):
    rng = np.random.default_rng([int(seed), int(stream)])
    labels = data["labels"]
    y = rng.integers(len(labels), size=n)
    said = np.where(rng.random(n) < 0.75, y, rng.integers(len(labels), size=n))
    token = rng.integers(int(data["values"]), size=(n, int(data["string_keys"])))
    number = 1.0 + np.floor(rng.random((n, int(data["num_keys"]))) * 8.0) / 4.0
    rows = []
    for i in range(n):
        strings = [(f"S{j}{key_suffix}", f"v{t}")
                   for j, t in enumerate(token[i].tolist())]
        strings[0] = (strings[0][0], f"says-{said[i]}-{token[i, 0] % 5}")
        rows.append((labels[y[i]], strings,
                     [(f"N{j}{key_suffix}", v)
                      for j, v in enumerate(number[i].tolist())]))
    return rows
'''


def _files(root):
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "perfbench")):
        for fn in files:
            p = os.path.join(d, fn)
            with open(p, "rb") as f:
                before[p] = f.read()
    return before


def _five_way(root, bench, generator="five_way"):
    """A configuration of five labels that names a generator of its own,
    and a train cell on it: files that were not there, and entries."""
    conf = u.load_config()
    conf.update(name="five_way", live_labels=5, features_per_row=5)
    conf["rehearsal"]["hash_max_size"] = 1 << 14
    conf["data"] = {"labels": list("abcde"), "string_keys": 3, "num_keys": 2,
                    "values": 40}
    if generator is not None:
        conf["data"]["generator"] = generator
    with open(os.path.join(root, "perfbench", "configs", "five_way.json"),
              "w") as f:
        json.dump(conf, f)
    bench["configs"].append({
        "name": "five_way", "source": "a test",
        "file": "perfbench/configs/five_way.json", "reduced": [],
        "why": "a test's configuration"})
    u.add_cell(root, bench, "five_way.t_train", "five_way", "t_train",
               u.small_traffic(TRAIN, plan=BURST_PLAN),
               like="criteo_arow.train")
    return conf


@pytest.mark.parametrize("fault", ["none", "half_batch"])
def test_a_generator_file_a_configuration_and_one_entry_make_a_cell(
        tmp_path, fault):
    """Five labels and keys of its own through the real server, the shipped
    engine surface and the shipped ``linear_classifier`` reference: the
    harness finds the rows by the name in the configuration's ``data``,
    and the comparison is as sharp on five labels as on two (a server
    that trains on half of every flush is not ``correct``)."""
    root, bench = u.make_checkout(tmp_path)
    before = _files(root)
    os.makedirs(os.path.join(root, "perfbench", "generators"), exist_ok=True)
    with open(os.path.join(root, "perfbench", "generators", "five_way.py"),
              "w") as f:
        f.write(FIVE_WAY)
    conf = _five_way(root, bench)

    res = u.rehearse(root, "five_way.t_train", server_entry=[
        sys.executable, os.path.join(u.HERE, "faulty_server.py"), fault])
    gap = res["compared"]["score_gap"]
    if fault == "none":
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0 and res["attempted"] > 0
        assert res["metrics"]["train_rows_per_s"]["value"] > 0
        assert gap["value"] > 0                  # a model was learnt
    else:
        assert res["correct"] is False
        assert not gap["value"] <= gap["limit"]
    # the rows were the new generator's, all five labels among them
    from harness import cell

    _bench, run = cell.load_cell(root, os.path.join(root, "perfbench"),
                                 "five_way.t_train")
    rows = run.generator.make_rows(conf["data"], 2200000123, 10, 400)
    assert {r[0] for r in rows} == set("abcde")
    assert {k for r in rows for k, _v in r[1] + r[2]} \
        == {"S0", "S1", "S2", "N0", "N1"}
    # nothing that was there has changed
    for p, content in before.items():
        with open(p, "rb") as f:
            assert f.read() == content, p


@pytest.mark.parametrize("generator,error,names", [
    (None, ValueError, "data.generator"),
    ("no_such_file", FileNotFoundError, "no_such_file.py"),
])
def test_a_configuration_that_names_no_generator_is_refused_by_name(
        tmp_path, generator, error, names):
    """No silent default: the key is asked for by its name (and a name
    with no file behind it by the file's), before a server is started."""
    root, bench = u.make_checkout(tmp_path)
    _five_way(root, bench, generator=generator)
    with pytest.raises(error, match=names):
        u.rehearse(root, "five_way.t_train")
    assert not os.path.exists(os.path.join(root, ".perfbench_run"))


def test_new_files_and_one_entry_make_a_cell(tmp_path):
    root, bench = u.make_checkout(tmp_path)
    before = _files(root)

    # a configuration: the same engine at another width, in a file of its own
    with open(os.path.join(root, "perfbench", "configs",
                           "criteo_arow.json")) as f:
        conf = json.load(f)
    conf["name"] = "narrow_arow"
    conf["rehearsal"]["hash_max_size"] = 1 << 14
    with open(os.path.join(root, "perfbench", "configs",
                           "narrow_arow.json"), "w") as f:
        json.dump(conf, f)
    bench["configs"].append({
        "name": "narrow_arow", "source": "a test",
        "file": "perfbench/configs/narrow_arow.json", "reduced": [],
        "why": "a test's configuration"})
    # a per-layer metric: a reader in a file of its own, and its entry
    with open(os.path.join(root, "perfbench", "per_layer",
                           "test.train_calls.py"), "w") as f:
        f.write(NEW_METRIC)
    bench["per_layer"].append({
        "name": "test.train_calls", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "train_rows_per_s", "workloads": ["narrow_arow.open"]})
    # a traffic mix: a data file (an open loop at a fixed rate, which no
    # shipped cell uses) and the workloads entry
    groups = [{"name": "train", "method": "train", "connections": 4,
               "rows_per_call": 50, "loop": "open", "rate_calls_per_s": 40.0,
               "pool_calls": 8, "server": "each"}]
    u.add_cell(root, bench, "narrow_arow.open", "narrow_arow", "open",
               u.small_traffic(groups), like="criteo_arow.train")

    res = u.rehearse(root, "narrow_arow.open", seconds=2.0)
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["train_rows_per_s"]["value"] > 0
    res = u.rehearse(root, "narrow_arow.open", seconds=1.0, trace=True)
    # (no bounds on the rate here: the tests share their cores, and a late
    # generator sends what is overdue at once)
    assert res["metrics"]["test.train_calls"]["value"] >= 1
    # an open loop has no turnaround to report: the reader finds nothing
    # to read and the metric is left out of the line
    assert "loadgen.turnaround_us" not in res["metrics"]
    assert res["metrics"]["coalescer.rows_per_flush"]["value"] >= 50

    # nothing that was there has changed
    for p, content in before.items():
        with open(p, "rb") as f:
            assert f.read() == content, p


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(u.REPO, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            u.BENCH, "traffic", w["traffic"] + ".json"))
        assert w["config"] in {c["name"] for c in bench["configs"]}
    from harness import cell

    e2e = cell.load_readers(os.path.join(u.BENCH, "end_to_end"))
    layer = cell.load_readers(os.path.join(u.BENCH, "per_layer"))
    # every metric has its reader, and no reader waits for a metric
    assert {m["name"] for m in bench["end_to_end"]} == set(e2e)
    assert {m["name"] for m in bench["per_layer"]} == set(layer)
    cells = {w["name"] for w in bench["workloads"]}
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in names
        assert set(m.get("workloads", cells)) <= cells


def test_benchmark_json_keeps_to_the_contracts_limits():
    """What the driver refuses before a single run: a line over 200
    characters or on two lines, a name with other characters, a key too
    many, a whole file over 64 KiB."""
    import re

    path = os.path.join(u.REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")

    def line(s):
        return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s

    assert 1 <= bench["run_seconds"] <= 51
    assert all(line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.fullmatch(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        assert all(name.fullmatch(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.fullmatch(w["name"]) and name.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"]), w["name"]
    keys = {"end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for kind, want in keys.items():
        for m in bench[kind]:
            assert set(m) - {"workloads"} == want, m["name"]
            assert name.fullmatch(m["name"]) and unit.fullmatch(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert kind == "end_to_end" or line(m["layer"])
            assert kind == "per_layer" or 0 < m["bound"] <= 0.1
    names = [m["name"] for k in keys for m in bench[k]]
    assert len(names) == len(set(names))
