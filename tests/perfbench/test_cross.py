"""The combination-rule configuration (``criteo_arow_cross``): its plain
reference's converter against the program's two (``core/fv/converter.py``
and the native parser) on seeded Criteo rows, the cell's CPU rehearsal,
and faults of the cross product that come out not ``correct``."""

import json
import os
import sys

import numpy as np
import pytest

import pbtest_util as u

FAULTY = os.path.join(u.HERE, "faulty_cross_server.py")
DIM = 1 << 16

TRAIN = [{"name": "train", "method": "train", "connections": 4,
          "rows_per_call": 60, "loop": "closed", "pool_calls": 8,
          "server": "each"}]

ADD_FOR_MUL = '''"""A test's fault: the cross reference with add for mul."""
import importlib.util, os

spec = importlib.util.spec_from_file_location(
    "cross_for_a_fault", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "linear_classifier_cross.py"))
cross = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cross)
cross.OPS["mul"] = cross.OPS["add"]
Featurizer, Batch, Model = cross.Featurizer, cross.Batch, cross.Model
universe_of, label_scores = cross.universe_of, cross.label_scores
'''


def _rows(n, stream=1):
    conf = u.load_config("criteo_arow_cross")
    return conf, u.make_rows(conf, 2200000123, stream, n)


def test_the_configuration_is_criteo_arow_with_the_upstream_rule():
    cross, plain = u.load_config("criteo_arow_cross"), u.load_config()
    with open(os.path.join(u.REPO, "config", "classifier",
                           "arow_combinational_feature.json")) as f:
        upstream = json.load(f)["converter"]
    conv = dict(cross["model"]["converter"])
    for k in ("combination_types", "combination_rules"):
        assert conv.pop(k) == upstream[k]
    assert conv == plain["model"]["converter"]
    assert cross["model"]["method"] == plain["model"]["method"] == "AROW"
    for k in ("data", "guarantees", "programs", "server_flags", "replicas",
              "reduced", "live_labels"):
        assert cross[k] == plain[k], k
    fields = cross["data"]["integer_fields"] \
        + cross["data"]["categorical_cardinalities"]["fields"]
    assert cross["features_per_row"] == fields + fields * (fields - 1) // 2


def test_reference_converter_and_native_parser_agree_on_criteo_rows():
    """Identical columns and values from the three, on rows that have
    zero-valued integer fields and names that share a column."""
    import msgpack

    from harness import wire
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter
    from jubatus_tpu.native.ingest import IngestParser

    conf, rows = _rows(120)
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
        _labels, idx, val, cross = parser.parse_indexed(params, cross=True)
        assert cross.slots == len(rows) * 741
        assert cross.base_idx.shape == (len(rows), 64)
        native = (idx, val)
    zero_fields = merged = 0
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
        zero_fields += any(v == 0.0 for _k, v in row[2])
        merged += len(cols) < conf["features_per_row"]
    assert zero_fields > 10 and merged > 10
    # a zero-valued field is a feature: the row keeps its column
    assert all(len(feat.named(row)) == 39 for row in rows)


@pytest.mark.parametrize("left,right,method", [
    ("*", "*", "add"), ("I*", "C*", "mul"), ("*@num", "I1*", "mul")])
def test_the_references_rule_is_the_converters_for_other_patterns(
        left, right, method):
    """Matchers on the feature names, either way round, once a pair."""
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter

    conf, rows = _rows(12, stream=2)
    conv = dict(conf["model"]["converter"], hash_max_size=DIM,
                combination_types={"t": {"method": method}},
                combination_rules=[
                    {"key_left": left, "key_right": right, "type": "t"}])
    conf = dict(conf, model=dict(conf["model"], converter=conv))
    feat = u.subject(DIM, conf).featurize
    converter = make_fv_converter(conv)
    for row in rows:
        want = sorted(feat(row).items())
        got = converter.convert(Datum(string_values=list(row[1]),
                                      num_values=list(row[2])))
        assert [i for i, _v in got] == [c for c, _v in want]
        assert np.allclose([v for _i, v in got], [v for _c, v in want],
                           rtol=1e-6)
    assert 39 < len(want) < 780


def test_the_cells_rehearsal_is_correct():
    res = u.rehearse(u.REPO, "criteo_arow_cross.train", trace=True)
    assert res["correct"] is True, res["compared"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["ingest.cross_slots_per_row"] == 741
    assert m["ingest.cross_generic_share"] == 0
    assert m["ingest.cross_us_per_row"] > 0
    # 780 features less the merged ones, on the width ladder's rung of 832
    assert 6.2 <= m["step.train_width_pad_share"] < 8
    assert m["step.train_upload_mb_per_flush"] > 1
    assert m["compile.in_window"] == 0
    assert m["ingest.sparse_flush_share"] == 100


def _small_cell(tmp_path, reference=None):
    root, bench = u.make_checkout(tmp_path)
    if reference is not None:
        name, text = reference
        if text is not None:
            with open(os.path.join(root, "perfbench", "references",
                                   name + ".py"), "w") as f:
                f.write(text)
        path = os.path.join(root, "perfbench", "configs",
                            "criteo_arow_cross.json")
        with open(path) as f:
            conf = json.load(f)
        conf["reference"] = name
        with open(path, "w") as f:
            json.dump(conf, f)
    u.add_cell(root, bench, "criteo_arow_cross.t_train", "criteo_arow_cross",
               "t_train", u.small_traffic(TRAIN),
               like="criteo_arow_cross.train")
    return root, "criteo_arow_cross.t_train"


@pytest.mark.parametrize("fault", [
    "none", "reference_without_the_rule", "reference_adds_for_mul",
    "last_slot_dropped"])
def test_a_fault_of_the_cross_product_is_not_correct(tmp_path, fault):
    reference = {"reference_without_the_rule": ("linear_classifier", None),
                 "reference_adds_for_mul": ("cross_add", ADD_FOR_MUL)
                 }.get(fault)
    root, cell = _small_cell(tmp_path, reference)
    entry = [sys.executable, FAULTY, fault] \
        if fault == "last_slot_dropped" else None
    res = u.rehearse(root, cell, server_entry=entry)
    gap = res["compared"]["score_gap"]
    if fault == "none":
        assert res["correct"] is True, res["compared"]
    else:
        assert res["correct"] is False
        assert not gap["value"] <= gap["limit"]
