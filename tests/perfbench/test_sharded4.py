"""The sharded deployment (``criteo_arow_sharded4``): one model over four
devices behind one server. Its file is ``criteo_arow``'s but for what the
deployment names; a real ``--shard-devices 4`` server on the CPU's virtual
devices holds every table in four shards and counts what the shards are
handed; the cell's kind of run is ``correct`` against the reference the one-chip
cells have, and is not where a shard drops its updates; the three readers
on a canned pair of status samples."""

import json
import os
import sys

import pytest

import pbtest_util as u
from test_rehearsal import BURST_PLAN, TRAIN

FAULTY = os.path.join(u.HERE, "faulty_sharded_server.py")
CELL = "criteo_arow_sharded4.train"


def test_the_configuration_is_criteo_arow_but_for_the_deployment():
    sharded, plain = u.load_config("criteo_arow_sharded4"), u.load_config()
    differ = {k for k in set(sharded) | set(plain)
              if sharded.get(k) != plain.get(k)}
    assert differ == {"name", "source", "deployment", "server_flags",
                      "model", "programs", "reduced_why", "guarantees"}
    conv = dict(sharded["model"]["converter"])
    assert conv.pop("hash_max_size") == 1 << 28
    assert conv == {k: v for k, v in plain["model"]["converter"].items()
                    if k != "hash_max_size"}
    assert {k: v for k, v in sharded["model"].items() if k != "converter"} \
        == {k: v for k, v in plain["model"].items() if k != "converter"}
    assert sharded["server_flags"] == plain["server_flags"] \
        + ["--shard-devices", "4"]
    # the guarantees of the one-chip deployment, and one line more
    extra = set(sharded["guarantees"]) - set(plain["guarantees"])
    assert extra == {"one_model"}
    assert all(sharded["guarantees"][k] == v
               for k, v in plain["guarantees"].items())
    assert sharded["reduced"] == ["hash_max_size"]
    assert sharded["reference"] == "linear_classifier"
    # the bytes the file reckons with: four f32 tables of 8 reserved rows
    rows = sharded["label_capacity_reserved_by_the_program"]
    assert 4 * rows * (1 << 28) * 4 // 4 == 8589934592
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "train"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_a_real_sharded_server_holds_four_shards_and_counts_what_they_own(
        tmp_path):
    """Through the entry point and the flag the deployment names: the
    tables lie in four shards on four devices, a train call stamps the
    plan and the shards' entries, and ``clear`` leaves the layout."""
    from harness.loadgen import Client
    from harness.servers import Fleet

    conf = u.load_config("criteo_arow_sharded4")
    dim = conf["rehearsal"]["hash_max_size"]
    fleet = Fleet(u.REPO, str(tmp_path / "run"), conf, 4, True, False)
    try:
        st = fleet.wait_for_status(0, 300.0)
        assert st["driver.shard.count"] == 4
        assert len(set(st["driver.shard.devices"])) == 4
        assert st["driver.shard.shard_shape"] == [8, dim // 4]
        assert st["driver.shard.bytes_per_shard"] == 4 * 8 * (dim // 4) * 4
        eng = u.subject(dim, conf).engine
        rows = u.make_rows(conf, 2200000123, 7, 300)
        with Client(fleet.address(0), timeout=300.0) as c:
            assert c.call_frame(eng.ENCODERS["train"](fleet.name, rows)) == 300
            st = fleet.status(0)
            count = {k[len("trace.counter.step.train."):]: v
                     for k, v in st.items()
                     if k.startswith("trace.counter.step.train.")}
            assert count.get("plan_packed", 0) \
                + count.get("plan_columns", 0) == 1
            # 512 padded rows on every [8, dim / 4] slice, at the one width
            # the flush was routed to: the rung of the fullest row a shard
            # holds, under the flush's own 40
            (ks,) = [int(k[len("shard_width_"):]) for k in count
                     if k.startswith("shard_width_")]
            assert count["shard_width_%d" % ks] == 1 and 8 <= ks <= 40
            assert count["shard_entries_issued"] == 4 * 512 * ks
            assert count["shard_entries"] == count["entries"]
            assert count["shard_entries"] / 4 \
                <= count["shard_entries_owned_max"] < count["shard_entries"]
            assert c.call("clear", fleet.name) is True
            assert c.call_frame(eng.ENCODERS["train"](fleet.name, rows)) == 300
        st = fleet.status(0)
        assert st["driver.shard.count"] == 4
        assert st["driver.shard.shard_shape"] == [8, dim // 4]
    finally:
        fleet.stop()


def _small_cell(tmp_path):
    root, bench = u.make_checkout(tmp_path)
    u.add_cell(root, bench, "criteo_arow_sharded4.t_train",
               "criteo_arow_sharded4", "t_train",
               u.small_traffic(TRAIN, plan=BURST_PLAN), like=CELL)
    return root, "criteo_arow_sharded4.t_train"


@pytest.mark.parametrize("fault", ["none", "shard_drops_updates"])
def test_the_rehearsal_is_correct_and_a_shard_that_drops_its_updates_is_not(
        tmp_path, fault):
    root, cell = _small_cell(tmp_path)
    res = u.rehearse(root, cell, trace=True,
                     server_entry=[sys.executable, FAULTY, fault])
    gap = res["compared"]["score_gap"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # a routed flush hands a shard the entries it owns: what is issued
    # and carries no feature is row padding, under the three quarters
    # that handing every shard every entry cost
    assert 40 <= m["step.train_shard_masked_share"] < 75
    assert 25 <= m["step.train_shard_owned_max_share"] < 100
    assert "step.train_hbm_roofline.mesh" not in m      # a device number
    if fault == "none":
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0
    else:
        assert res["correct"] is False
        assert not gap["value"] <= gap["limit"]


def _canned_run():
    from harness import cell

    run = cell.Run()
    run.workload = {"name": CELL, "chips": 4}
    run.config = u.load_config("criteo_arow_sharded4")
    run.peaks = cell.load_json(os.path.join(u.BENCH, "peaks.json"))
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    c = "trace.counter.step.train."
    q = "microbatch.train_raw."
    run.status0 = [{c + "shard_entries": 1000, c + "shard_entries_issued": 8000,
                    c + "shard_entries_owned_max": 400,
                    q + "flush_count": 10, q + "item_count": 80000}]
    # ten flushes of 8,000 rows x 39 features in 8,192 x 40, over four shards
    run.status1 = [{c + "shard_entries": 1000 + 10 * 8000 * 39,
                    c + "shard_entries_issued": 8000 + 10 * 4 * 8192 * 40,
                    c + "shard_entries_owned_max": 400 + 10 * 100000,
                    q + "flush_count": 20, q + "item_count": 160000}]
    # each of the four chips ran the program once a flush, 50 ms each
    run.trace = {"programs": {"jit_train_batch": {"events": 40,
                                                  "seconds": 2.0}}}
    return run


def test_the_three_readers_on_a_canned_status_pair():
    from harness import cell, needed

    readers = cell.load_readers(os.path.join(u.BENCH, "per_layer"))
    run = _canned_run()
    masked = readers["step.train_shard_masked_share"].read(run)
    assert masked == pytest.approx(100 * (1 - 8000 * 39 / (4 * 8192 * 40)))
    assert 75 < masked < 80
    assert readers["step.train_shard_owned_max_share"].read(run) \
        == pytest.approx(100 * 100000 / (8000 * 39))
    need = needed.train_flush_bytes(8000, 39, 2)
    mesh = readers["step.train_hbm_roofline.mesh"].read(run)
    assert mesh == pytest.approx(100 * need / (4 * 819e9) / 0.050)
    assert 0 < mesh < 100
    # the one-chip reader would divide by one chip's peak: four times it
    assert readers["step.train_hbm_roofline"].read(run) \
        == pytest.approx(4 * mesh)


def test_the_three_readers_find_nothing_where_nothing_is_stamped():
    """The parent's server, and a one-chip server, stamp no shard counter:
    the readers return nothing and do not raise."""
    from harness import cell

    readers = cell.load_readers(os.path.join(u.BENCH, "per_layer"))
    run = _canned_run()
    run.status0 = [{}]
    run.status1 = [{"microbatch.train_raw.flush_count": 3}]
    run.trace = None
    for name in ("step.train_shard_masked_share",
                 "step.train_shard_owned_max_share",
                 "step.train_hbm_roofline.mesh"):
        assert readers[name].read(run) is None, name
