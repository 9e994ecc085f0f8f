"""The language-identification deployment (``wili2018_arow``): its file
against ``news20_arow``'s key for key, its plain reference's ``ngram``
windows with ``tf`` against the program's two converters
(``core/fv/converter.py`` and the native parser) on generated paragraphs
and on values the generator never sends (lone bytes that are no UTF-8,
values of one code point), what the reference refuses, the cell's files
as the harness loads them, the cell's kind of run on the CPU through the
real server (``correct``, 235 labels answered, the metrics listed for
the cell), and a fault of each guarantee the configuration adds that
comes out not ``correct``."""

import json
import os
import sys

import numpy as np
import pytest

import pbtest_util as u

FAULTY = os.path.join(u.HERE, "faulty_ngram_server.py")
CELL = "wili2018_arow.train"
DIM = 1 << 16

TRAIN = [{"name": "train", "method": "train", "connections": 4,
          "rows_per_call": 120, "loop": "closed", "pool_calls": 8,
          "server": "each"}]
#: lone calls, as the cell's own plan is: the first makes every label live
PLAN = [{"op": "clear"},
        {"op": "train", "calls": 1, "rows": 500},
        {"op": "train", "calls": 2, "rows": 300},
        {"op": "classify", "calls": 2, "rows": 50}]


def _bench():
    with open(os.path.join(u.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_states_source_departures_and_guarantees():
    lang, text = u.load_config("wili2018_arow"), u.load_config("news20_arow")
    assert set(lang) == set(text)
    for k in ("engine", "cluster_name", "replicas", "deployment",
              "server_flags", "programs", "rehearsal"):
        assert lang[k] == text[k], k
    assert lang["reference"] == "linear_classifier_ngram"
    assert lang["model"]["method"] == "AROW"
    assert lang["model"]["parameter"] == {"regularization_weight": 1.0}
    conv = lang["model"]["converter"]
    assert conv["string_types"] == {"bigram": {"method": "ngram",
                                               "char_num": "2"}}
    assert conv["string_rules"] == [{
        "key": "text", "type": "bigram", "sample_weight": "tf",
        "global_weight": "bin"}]
    assert conv["hash_max_size"] == 1 << 20
    assert not any(conv[k] for k in conv if k not in (
        "string_types", "string_rules", "hash_max_size"))
    # news20_arow's four guarantees of the model letter for letter, and
    # its two of features and labels restated for windows and 235 labels
    plain = u.load_config()["guarantees"]
    assert all(lang["guarantees"][k] == v for k, v in plain.items())
    assert set(lang["guarantees"]) == set(text["guarantees"])
    assert "every window of 2 consecutive code points" \
        in lang["guarantees"]["features"]
    assert "235 scores a row" in lang["guarantees"]["labels"]
    assert lang["reduced"] == [] and "nothing is cut" in lang["reduced_why"]
    data = lang["data"]
    assert data["generator"] == "lang_text" and data["text_key"] == "text"
    assert len(data["labels"]) == len(set(data["labels"])) == 235 \
        == lang["live_labels"] \
        == sum(s["languages"] for s in data["scripts"])
    assert data["labels"][0] == "L000" and data["labels"][-1] == "L234"
    # every number of data but the labels is assumed, by name
    assumed = " ".join(lang["assumed"])
    for k in set(data) - {"generator", "labels", "text_key"}:
        assert k in assumed, k
    assert {"hash_max_size", "label names", "source facts"} \
        <= set(lang["assumed"])
    # the bytes the file reckons with: four f32 tables of 256 rows
    rows = lang["label_capacity_reserved_by_the_program"]
    assert rows == 256 and 4 * rows * (1 << 20) * 4 == 4294967296
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "train_langid", "wili2018_arow")
    entry = next(c for c in bench["configs"] if c["name"] == "wili2018_arow")
    assert entry["source"] == lang["source"] and entry["reduced"] == []
    assert entry["file"] == "perfbench/configs/wili2018_arow.json"


def test_the_cell_is_listed_where_the_issue_lists_it():
    """On ``train_rows_per_s`` and the one-chip text cell's per-layer
    metrics, not on the three readers of PR 35, whose lists a test
    holds; and no new metric."""
    bench = _bench()
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    news = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if "news20_arow.train" in m.get("workloads", ())}
    slab = {"step.train_slab_flush_share", "step.train_issued_pad_share",
            "step.train_programs_in_window"}
    assert listed == news - slab
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "step.train_slab_flush_share", "step.train_issued_pad_share",
        "step.train_programs_in_window"]


def test_features_per_row_is_the_generators_mean_of_distinct_bigrams():
    conf = u.load_config("wili2018_arow")
    rows = u.make_rows(conf, 2380000017, 10, 6000)
    distinct = np.array([len({t[i:i + 2] for i in range(len(t) - 1)})
                         for t in (r[1][0][1] for r in rows)])
    assert abs(distinct.mean() / conf["features_per_row"] - 1) < 0.03
    # heavy-tailed: the fullest row says nothing of the mean
    assert distinct.max() > 5 * conf["features_per_row"]


def test_the_traffic_is_train_texts_with_a_pool_that_does_not_repeat():
    def load(name):
        with open(os.path.join(u.BENCH, "traffic", name + ".json")) as f:
            return json.load(f)

    lang, text = load("train_langid"), load("train_text")
    for k in ("run_past_s", "flush_gap_ms", "trace"):
        assert lang[k] == text[k], k
    (g,), (t,) = lang["groups"], text["groups"]
    assert {k: v for k, v in g.items() if k != "pool_calls"} \
        == {k: v for k, v in t.items() if k != "pool_calls"}
    assert g["pool_calls"] == 512 and g["rows_per_call"] == 500
    assert lang["warmup"]["calls"] == text["warmup"]["calls"]
    assert lang["warmup"]["stream_calls"] == text["warmup"]["stream_calls"]
    assert 60 <= lang["warmup"]["timeout_s"] <= 900
    steps = lang["check"]["steps"]
    assert steps == [{"op": "clear"},
                     {"op": "train", "calls": 1, "rows": 500},
                     {"op": "train", "calls": 2, "rows": 8000},
                     {"op": "classify", "calls": 2, "rows": 500}]
    # a lone call of 500 holds two aligned blocks of all 235 labels
    assert steps[1]["rows"] >= 2 * 235
    assert 0 < lang["check"]["limits"]["score_gap"] <= 5e-5


#: values the generator never sends: a lone byte that is no UTF-8 (the
#: wire's surrogate escape), a value of one code point (no window), an
#: empty one, a 4-byte code point, a bigram three times, a key the rule
#: does not match
ODD = [("a", [("text", "ab\udcffcd\udcffc")], []),
       ("b", [("text", "x")], []),
       ("c", [("text", "")], []),
       ("d", [("text", "\U0001F600中文中文中")], []),
       ("e", [("text", "aaaa")], []),
       ("f", [("other", "ab cd")], [("n", 3.0)])]


def _frame(rows):
    """The train params of ``rows`` as the wire carries them, lone
    surrogates back to the bytes they stand for."""
    import msgpack

    return msgpack.packb(["x", [[label, [[list(kv) for kv in s], [], []]]
                                for label, s, _n in rows]],
                         use_bin_type=True, unicode_errors="surrogateescape")


def test_reference_converter_and_native_parser_agree_on_paragraphs():
    """Identical columns and values from the three: every window of two
    code points, valued by its count, multi-byte code points and lone
    bytes slid over as one code point each; and the parser counts the
    windows as tokens and the code points it slid over."""
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter
    from jubatus_tpu.native.ingest import IngestParser

    conf = u.load_config("wili2018_arow")
    rows = u.make_rows(conf, 2200000123, 1, 150) + ODD
    conv = dict(conf["model"]["converter"], hash_max_size=DIM)
    feat = u.subject(DIM, conf).featurize
    converter = make_fv_converter(conv)
    parser = IngestParser.from_converter_config(conv, 16)
    native = None
    values = [v for _l, s, _n in rows for k, v in s if k == "text"]
    if parser is not None:
        _labels, idx, val, counts = parser.parse_indexed(_frame(rows),
                                                         counts=True)
        native = (idx, val)
        assert counts.tokens == sum(max(len(v) - 1, 0) for v in values)
        assert counts.codepoints == sum(len(v) for v in values)
    counted = 0
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
        counted += bool(len(vals)) and vals.max() > 1
    assert counted > 100           # tf: most paragraphs repeat a bigram
    # the odd rows: ab, b?, ?c, cd, d?, (?c once more); no window; none;
    # three distinct windows, two of them twice; aa three times; no key
    assert [len(feat(row)) for row in ODD] == [5, 0, 0, 3, 1, 0]
    assert sorted(feat(ODD[4]).values()) == [3.0]


@pytest.mark.parametrize("change,named", [
    ({"string_rules": [{"key": "*", "type": "space", "sample_weight": "tf",
                        "global_weight": "bin"}]}, "string rule"),
    ({"string_rules": [{"key": "*", "type": "bigram", "sample_weight": "tf",
                        "global_weight": "idf"}]}, "string rule"),
    ({"string_rules": [{"key": "*", "type": "bigram",
                        "sample_weight": "log_tf",
                        "global_weight": "bin"}]}, "string rule"),
    ({"num_rules": [{"key": "*", "type": "num"}]}, "converter.num_rules"),
    ({"string_filter_rules": [{"key": "*", "type": "detag",
                               "suffix": "-detagged"}]},
     "converter.string_filter_rules"),
    ({"string_rules": []}, "converter.string_rules"),
])
def test_the_reference_refuses_by_name_what_it_does_not_state(change, named):
    conf = u.load_config("wili2018_arow")
    conv = dict(conf["model"]["converter"], **change)
    ref = u.subject(DIM, conf).ref
    with pytest.raises(NotImplementedError, match=named):
        ref.Featurizer(conv, DIM)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(u.BENCH, "references",
                           "linear_classifier_ngram.py")) as f:
        text = f.read()
    assert "jubatus_tpu" not in text and "import jax" not in text


def test_the_references_flat_flush_is_its_siblings_padded_one():
    """The ngram reference's model over flat entries against the plain
    sibling's over padded [B, K] rows, on the same featurized rows: the
    same scores and tables to float32 rounding, through two flushes."""
    conf = u.load_config("wili2018_arow")
    sub = u.subject(DIM, conf)
    plain = sub.ref._plain
    rows = [u.make_rows(conf, 5, s, 240) for s in (1, 2)]
    probe = u.make_rows(conf, 5, 3, 40)
    flat = [sub.batch(r) for r in rows + [probe]]

    class Padded(plain.Batch):
        def __init__(self, rows):
            super().__init__(rows, sub.featurize)

    padded = [Padded(r) for r in rows + [probe]]
    universe = sub.ref.universe_of(flat)
    assert np.array_equal(universe, plain.universe_of(padded))
    a = sub.model(universe, "float32")
    b = plain.Model(universe, conf["model"], conf["data"]["labels"],
                    "float32")
    for fb, pb in zip(flat[:2], padded[:2]):
        a.train_flush(fb)
        b.train_flush(pb)
    for x, y in ((a.w, b.w), (a.dw, b.dw), (a.p, b.p), (a.dp, b.dp)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a.scores(flat[2]), b.scores(padded[2]),
                               rtol=1e-5, atol=1e-5)
    assert a.labels == b.labels and len(a.labels) == 235


def _small_cell(tmp_path):
    root, bench = u.make_checkout(tmp_path)
    u.add_cell(root, bench, "wili2018_arow.t_train", "wili2018_arow",
               "t_train", u.small_traffic(TRAIN, plan=PLAN), like=CELL)
    return root, "wili2018_arow.t_train"


def test_the_cells_kind_of_run_is_correct_and_reads_its_metrics(tmp_path):
    """The configuration through the real server, native transport and
    ingest, on the CPU at a small size: ``correct`` against
    ``linear_classifier_ngram``, 235 labels in every answer (label rows
    grown 8 -> 256 in the first call, the step at 30 label groups), and
    every metric of the cell's list on the traced line."""
    root, cell = _small_cell(tmp_path)
    res = u.rehearse(root, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    bench = _bench()
    listed = {x["name"] for x in bench["per_layer"]
              if CELL in x.get("workloads", [CELL])}
    # the device's metrics have nothing to read on the CPU
    device = {x["name"] for x in bench["per_layer"]
              if x["source"] == "device_trace" or x["layer"] == "device"}
    assert listed - device <= set(m), sorted(listed - device - set(m))
    assert m["model.label_fill_share"] == 100 * 235 / 256
    # a token is a bigram window: a paragraph of n code points has n - 1
    assert 250 < m["ingest.tokens_per_row"] < 350
    assert m["ingest.convert_us_per_token"] > 0


@pytest.mark.parametrize("fault", [
    "windows_counted_once", "trigrams", "first_8_labels_only"])
def test_a_fault_of_a_guarantee_is_not_correct(tmp_path, fault):
    root, cell = _small_cell(tmp_path)
    res = u.rehearse(root, cell, server_entry=[sys.executable, FAULTY, fault])
    c = res["compared"]
    assert res["correct"] is False
    if fault == "first_8_labels_only":
        # an answer that lacks a trained label is of the wrong shape
        assert c["answers_wrong_shape"]["value"] > 0
    else:
        assert not c["score_gap"]["value"] <= c["score_gap"]["limit"]
        assert c["answers_wrong_shape"]["value"] == 0


def test_lone_bytes_through_the_server_score_as_the_reference_says():
    """Paragraphs of every script and the odd values above (lone bytes
    that are no UTF-8, 4-byte code points), as raw frames through a real
    server at the rehearsal's width: one lone call trains every label,
    and the scores of probes holding lone bytes are the reference's to
    float32 rounding, 235 a row."""
    import msgpack

    from harness.loadgen import Client
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = u.load_config("wili2018_arow")
    sub = u.subject(DIM, conf)
    labels = conf["data"]["labels"]
    odd = [(labels[i], s, n) for i, (_l, s, n) in enumerate(ODD)]
    rows = u.make_rows(conf, 2200000123, 1, 480) + odd
    probe = u.make_rows(conf, 2200000123, 2, 20) + odd[:1] + odd[3:4]

    def frame(method, params):
        return bytearray(b"\x94\x00\xce\x00\x00\x00\x00" + msgpack.packb(
            method) + msgpack.packb(params, use_bin_type=True,
                                    unicode_errors="surrogateescape"))

    def datum(strings):
        return [[list(kv) for kv in strings], [], []]

    model = dict(conf["model"], converter=dict(conf["model"]["converter"],
                                               hash_max_size=DIM))
    srv = EngineServer("classifier", model,
                       args=ServerArgs(engine="classifier"))
    port = srv.start(0)
    try:
        with Client(("127.0.0.1", port)) as c:
            assert c.call_frame(frame("train", ["x", [
                [lb, datum(s)] for lb, s, _n in rows]])) == len(rows)
            got = c.call_frame(frame("classify", ["x", [
                datum(s) for _lb, s, _n in probe]]))
    finally:
        srv.stop()
    batches = [sub.batch(rows), sub.batch(probe)]
    ref = sub.model(sub.ref.universe_of(batches), "float32")
    ref.train_flush(batches[0])
    want = sub.ref.label_scores(ref, batches[1])
    scale = max(abs(v) for r in want for v in r.values())
    assert scale > 0 and len(got) == len(probe)
    for answer, row in zip(got, want):
        answer = {str(lb): sc for lb, sc in answer}
        assert set(answer) == set(row) and len(row) == 235
        assert max(abs(answer[lb] - v) for lb, v in row.items()) \
            <= 1e-5 * scale
