"""The three readers of what the device is handed (PR 35), on the CPU
rehearsal of the three one-chip train cells at a small size, through the
real server: a flush of uneven documents goes to the device as slabs
(``news20_arow``: every flush of the window), rows that are alike as they
come (``criteo_arow``, ``criteo_arow_cross``: none), every flush counts
the one program that ran it, and each cell's traced line holds every
metric ``BENCHMARK.json`` lists for it."""

import json
import os

import pytest

import pbtest_util as u

READERS = ("step.train_slab_flush_share", "step.train_issued_pad_share",
           "step.train_programs_in_window")
CELLS = {
    "criteo_arow.train": ("criteo_arow", 100),
    "criteo_arow_cross.train": ("criteo_arow_cross", 60),
    "news20_arow.train": ("news20_arow", 200),
}
#: lone calls; in the text cell the first makes every label live
PLAN = [{"op": "clear"},
        {"op": "train", "calls": 1, "rows": 400},
        {"op": "train", "calls": 2, "rows": 300},
        {"op": "classify", "calls": 2, "rows": 50}]


def _bench():
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_three_readers_are_listed_for_the_one_chip_train_cells():
    by_name = {m["name"]: m for m in _bench()["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == list(CELLS)
        assert (m["layer"], m["moves"], m["source"]) == (
            "upload + device step", "train_rows_per_s", "program_counter")
        assert os.path.isfile(os.path.join(u.BENCH, "per_layer",
                                           name + ".py"))
    # additions at the end of the list, after everything PR 34 had
    assert [m["name"] for m in _bench()["per_layer"][-3:]] == list(READERS)


class _Run:
    """A window's two status samples, as a reader meets them."""

    def __init__(self, before, after):
        self.status0, self.status1 = [before], [after]


def test_a_program_without_the_counters_gives_the_readers_nothing():
    """The parent of PR 35 stages flushes and counts entries, but hands
    nothing over as slabs and names no program: each reader returns None
    there and does not raise; with the counters it reads their gain over
    the window, not their totals."""
    from harness import cell

    readers = [cell.load_module(os.path.join(u.BENCH, "per_layer"), name)
               for name in READERS]
    assert [r.NAME for r in readers] == list(READERS)
    old0 = {"trace.step.train.stage.count": 3,
            "trace.step.train.stage.mean_ms": 2.0,
            "trace.counter.step.train.entries": 100}
    old1 = {"trace.step.train.stage.count": 7,
            "trace.step.train.stage.mean_ms": 2.0,
            "trace.counter.step.train.entries": 500}
    assert [r.read(_Run(old0, old1)) for r in readers] == [None] * 3
    assert [r.read(_Run({}, {})) for r in readers] == [None] * 3
    new0 = dict(old0, **{
        "trace.counter.step.train.entries_issued": 1000,
        "trace.counter.step.train.slab_flushes": 1,
        "trace.counter.step.train.program_slabs_2048x32": 1,
        "trace.counter.step.train.program_rows_512x40": 2})
    new1 = dict(old1, **{
        "trace.counter.step.train.entries_issued": 1800,
        "trace.counter.step.train.slab_flushes": 4,
        "trace.counter.step.train.program_slabs_2048x32": 1,
        "trace.counter.step.train.program_slabs_32768x32": 3,
        "trace.counter.step.train.program_rows_512x40": 3})
    assert [r.read(_Run(new0, new1)) for r in readers] == [75.0, 50.0, 2]


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_rehearsal_reads_the_form_its_flushes_went_in(tmp_path, cell):
    config, rows = CELLS[cell]
    root, bench = u.make_checkout(tmp_path)
    small = config + ".t_train"
    u.add_cell(root, bench, small, config, "t_train", u.small_traffic([{
        "name": "train", "method": "train", "connections": 4,
        "rows_per_call": rows, "loop": "closed", "pool_calls": 8,
        "server": "each"}], plan=PLAN), like=cell)
    res = u.rehearse(root, small, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    listed = {x["name"] for x in bench["per_layer"]
              if cell in x.get("workloads", [cell])}
    # the device's metrics have nothing to read on the CPU
    device = {x["name"] for x in bench["per_layer"]
              if x["source"] == "device_trace" or x["layer"] == "device"}
    assert set(READERS) <= listed
    assert listed - device <= set(m), sorted(listed - device - set(m))
    assert all(isinstance(m[name], (int, float)) for name in READERS)
    assert m["step.train_programs_in_window"] >= 1
    if config == "news20_arow":
        # documents of 90 words in the mean at the 256, 512 or 1,024 their
        # calls were packed at: every flush cut, and what is issued holds
        # far less padding than what arrived
        assert m["step.train_slab_flush_share"] == 100
        assert 20 < m["step.train_issued_pad_share"] \
            < m["step.train_width_pad_share"] - 15
        assert m["step.train_width_pad_share"] > 70
    else:
        # rows that are alike run as they come: the rows' bucket of padding
        # on top of the width's
        assert m["step.train_slab_flush_share"] == 0
        assert 0 < m["step.train_issued_pad_share"] < 60
