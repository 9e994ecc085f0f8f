"""The text deployment (``news20_arow``): its file against ``criteo_arow``'s
key for key, its plain reference's ``space`` splitter against the
program's two converters (``core/fv/converter.py`` and the native parser)
on generated documents and on whitespace the generator never sends, what
the reference refuses, the cell's files as the harness loads them, the
cell's kind of run on the CPU through the real server (``correct``, twenty
labels answered, the four metrics the cell adds), and a fault of each of
the two guarantees the configuration adds that comes out not ``correct``."""

import json
import os
import sys

import numpy as np
import pytest

import pbtest_util as u

FAULTY = os.path.join(u.HERE, "faulty_text_server.py")
CELL = "news20_arow.train"
DIM = 1 << 16

TRAIN = [{"name": "train", "method": "train", "connections": 4,
          "rows_per_call": 60, "loop": "closed", "pool_calls": 8,
          "server": "each"}]
#: lone calls, as the cell's own plan is: the first makes every label live
PLAN = [{"op": "clear"},
        {"op": "train", "calls": 1, "rows": 400},
        {"op": "train", "calls": 2, "rows": 300},
        {"op": "classify", "calls": 2, "rows": 50}]


def test_the_configuration_states_source_departures_and_six_guarantees():
    text, plain = u.load_config("news20_arow"), u.load_config()
    assert set(plain) - set(text) == set()
    assert set(text) - set(plain) == {"features_per_row_from", "departures"}
    for k in ("engine", "cluster_name", "replicas", "deployment",
              "server_flags", "programs", "rehearsal"):
        assert text[k] == plain[k], k
    assert text["reference"] == "linear_classifier_text"
    assert text["model"]["method"] == "AROW"
    assert text["model"]["parameter"] == {"regularization_weight": 1.0}
    conv = text["model"]["converter"]
    assert conv["string_rules"] == [{
        "key": "message", "type": "space", "sample_weight": "bin",
        "global_weight": "bin"}]
    assert conv["hash_max_size"] == 1 << 23
    assert not any(conv[k] for k in conv
                   if k not in ("string_rules", "hash_max_size"))
    # criteo_arow's four guarantees letter for letter, and two more
    assert all(text["guarantees"][k] == v
               for k, v in plain["guarantees"].items())
    assert set(text["guarantees"]) - set(plain["guarantees"]) \
        == {"features", "labels"}
    assert text["reduced"] == [] and "nothing is cut" in text["reduced_why"]
    assert set(text["departures"]) == {"method", "filter"}
    data = text["data"]
    assert data["generator"] == "text_rows" and data["text_key"] == "message"
    assert len(data["labels"]) == len(set(data["labels"])) == 20 \
        == text["live_labels"]
    assert data["vocabulary"] == 62061
    # every number of data but labels and vocabulary is assumed, by name
    assumed = " ".join(text["assumed"])
    for k in set(data) - {"generator", "labels", "vocabulary"}:
        assert k in assumed, k
    assert {"hash_max_size", "method", "filter"} <= set(text["assumed"])
    # the bytes the file reckons with: four f32 tables of 32 reserved rows
    rows = text["label_capacity_reserved_by_the_program"]
    assert rows == 32 and 4 * rows * (1 << 23) * 4 == 4294967296
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "train_text", "news20_arow")
    entry = next(c for c in bench["configs"] if c["name"] == "news20_arow")
    assert entry["source"] == text["source"] and entry["reduced"] == []


def test_features_per_row_is_the_generators_mean_of_distinct_words():
    conf = u.load_config("news20_arow")
    rows = u.make_rows(conf, 2340000017, 10, 6000)
    distinct = np.array([len(set(r[1][0][1].split())) for r in rows])
    assert abs(distinct.mean() / conf["features_per_row"] - 1) < 0.03
    # heavy-tailed: the fullest row says nothing of the mean
    assert distinct.max() > 6 * conf["features_per_row"]
    assert distinct.max() <= 1024 < conf["data"]["length_max"]


def test_the_traffic_is_train_jsons_with_a_plan_of_lone_calls():
    def load(name):
        with open(os.path.join(u.BENCH, "traffic", name + ".json")) as f:
            return json.load(f)

    text, train, cross = load("train_text"), load("train"), load("train_cross")
    for k in ("groups", "run_past_s", "flush_gap_ms", "trace"):
        assert text[k] == train[k], k
    # the warm-up too, but for how long a stream that never settles is
    # waited for (a program that cannot compile the step ends sooner)
    warm = {k: v for k, v in text["warmup"].items()
            if k not in ("timeout_s", "why_timeout_s")}
    assert warm == {k: v for k, v in train["warmup"].items()
                    if k != "timeout_s"}
    assert 120 <= text["warmup"]["timeout_s"] < train["warmup"]["timeout_s"]
    steps = text["check"]["steps"]
    assert [s["op"] for s in steps] == ["clear", "train", "train", "classify"]
    assert steps[1:] == cross["check"]["steps"][1:]
    assert 0 < text["check"]["limits"]["score_gap"] <= 5e-5


#: whitespace the generator never sends, and a word that comes twice
ODD = [("a", [("message", "w1 w2  w2\tw3\nw1 　w4 ")], []),
       ("b", [("message", "   ")], []),
       ("c", [("message", "one")], []),
       ("d", [("other", "w1 w2")], [("n", 3.0)])]


def test_reference_converter_and_native_parser_agree_on_documents():
    """Identical columns and values from the three: distinct tokens, value
    1 however often a token occurs, cut where ``str.split()`` cuts."""
    import msgpack

    from harness import wire
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter
    from jubatus_tpu.native.ingest import IngestParser

    conf = u.load_config("news20_arow")
    rows = u.make_rows(conf, 2200000123, 1, 150) + ODD
    conv = dict(conf["model"]["converter"], hash_max_size=DIM)
    feat = u.subject(DIM, conf).featurize
    converter = make_fv_converter(conv)
    parser = IngestParser.from_converter_config(conv, 16)
    native = None
    if parser is not None:
        frame = bytes(wire.encode_request("train", ["x", [
            [label, wire.datum(s, nv)] for label, s, nv in rows]]))
        params = msgpack.packb(msgpack.unpackb(frame, raw=False)[3],
                               use_bin_type=True)
        _labels, idx, val, counts = parser.parse_indexed(params, counts=True)
        native = (idx, val)
        assert counts.tokens == sum(len(v.split()) for _l, s, _n in rows
                                    for k, v in s if k == "message")
        assert counts.terms == sum(len(set(v.split())) for _l, s, _n in rows
                                   for k, v in s if k == "message")
    repeated = collided = 0
    for r, row in enumerate(rows):
        want = sorted(feat(row).items())
        cols = [c for c, _v in want]
        vals = np.array([v for _c, v in want], np.float32)
        got = converter.convert(Datum(string_values=list(row[1]),
                                      num_values=list(row[2])))
        assert [i for i, _v in got] == cols, r
        assert np.array_equal(np.array([v for _i, v in got], np.float32),
                              vals), r
        if native is not None:
            n = len(cols)
            assert native[0][r, :n].tolist() == cols, r
            assert not native[0][r, n:].any()
            assert np.array_equal(native[1][r, :n], vals), r
        words = row[1][0][1].split()
        repeated += len(set(words)) < len(words)
        collided += len(cols) < len(set(words))
    assert repeated > 100 and collided > 3     # at 2^16 columns
    # the odd rows: five distinct tokens; no token; one; no matching key
    assert [len(feat(row)) for row in ODD] == [4, 0, 1, 0]
    assert set(feat(ODD[0]).values()) == {1.0}


@pytest.mark.parametrize("change,named", [
    ({"string_rules": [{"key": "*", "type": "str", "sample_weight": "bin",
                        "global_weight": "bin"}]}, "string rule"),
    ({"string_rules": [{"key": "*", "type": "space", "sample_weight": "tf",
                        "global_weight": "bin"}]}, "string rule"),
    ({"string_rules": [{"key": "*", "type": "space", "sample_weight": "bin",
                        "global_weight": "idf"}]}, "string rule"),
    ({"num_rules": [{"key": "*", "type": "num"}]}, "converter.num_rules"),
    ({"string_filter_rules": [{"key": "*", "type": "detag",
                               "suffix": "-detagged"}]},
     "converter.string_filter_rules"),
    ({"string_rules": []}, "converter.string_rules"),
])
def test_the_reference_refuses_by_name_what_it_does_not_state(change, named):
    conf = u.load_config("news20_arow")
    conv = dict(conf["model"]["converter"], **change)
    ref = u.subject(DIM, conf).ref
    with pytest.raises(NotImplementedError, match=named):
        ref.Featurizer(conv, DIM)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("linear_classifier_text", "linear_classifier"):
        with open(os.path.join(u.BENCH, "references", name + ".py")) as f:
            text = f.read()
        assert "jubatus_tpu" not in text and "import jax" not in text


def _small_cell(tmp_path):
    root, bench = u.make_checkout(tmp_path)
    u.add_cell(root, bench, "news20_arow.t_train", "news20_arow", "t_train",
               u.small_traffic(TRAIN, plan=PLAN), like=CELL)
    return root, "news20_arow.t_train"


def test_the_cells_kind_of_run_is_correct_and_reads_its_four_metrics(
        tmp_path):
    """The configuration through the real server, native transport and
    ingest, on the CPU at a small size: ``correct`` against
    ``linear_classifier_text``, twenty labels in every answer, label rows
    grown 8 -> 32, and every metric of the cell's list on the traced
    line."""
    root, cell = _small_cell(tmp_path)
    res = u.rehearse(root, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {x["name"] for x in bench["per_layer"]
              if CELL in x.get("workloads", [CELL])}
    # the device's metrics have nothing to read on the CPU
    device = {x["name"] for x in bench["per_layer"]
              if x["source"] == "device_trace" or x["layer"] == "device"}
    assert listed - device <= set(m), sorted(listed - device - set(m))
    assert "ingest.sparse_flush_share" not in listed
    assert {"ingest.tokens_per_row", "ingest.convert_us_per_token",
            "step.train_widths_in_window", "model.label_fill_share",
            "step.train_width_pad_share",
            "step.train_upload_mb_per_flush"} <= listed
    assert m["model.label_fill_share"] == 62.5
    assert 100 < m["ingest.tokens_per_row"] < 150
    assert m["ingest.convert_us_per_token"] > 0
    # uneven documents run at the power of two their calls were packed at
    # (60-document calls: 256 or 512, a flush of them the widest), never at
    # a rung of the ladder, and most of the entries are padding
    assert 1 <= m["step.train_widths_in_window"] <= 3
    assert 70 < m["step.train_width_pad_share"] < 95
    assert m["compile.in_window"] >= 0


@pytest.mark.parametrize("fault", [
    "none", "repeated_word_counted", "first_8_labels_only"])
def test_a_fault_of_either_new_guarantee_is_not_correct(tmp_path, fault):
    root, cell = _small_cell(tmp_path)
    res = u.rehearse(root, cell, server_entry=[sys.executable, FAULTY, fault])
    c = res["compared"]
    if fault == "none":
        assert res["correct"] is True, c
        return
    assert res["correct"] is False
    if fault == "repeated_word_counted":
        assert not c["score_gap"]["value"] <= c["score_gap"]["limit"]
        assert c["answers_wrong_shape"]["value"] == 0
    else:
        # an answer that lacks a trained label is of the wrong shape
        assert c["answers_wrong_shape"]["value"] > 0
