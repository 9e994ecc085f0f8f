"""Native ingest fast path (native/fast_ingest.cpp + rpc raw spans).

The C++ parser must be BIT-IDENTICAL to the Python converter pipeline
(feature names, crc32 hashing, dedupe/sort, f64 accumulation -> f32) —
these tests fuzz that parity and drive the full server fast path,
including fallback behavior for wire shapes the parser declines.
"""

from __future__ import annotations

import json
import random

import msgpack
import numpy as np
import pytest

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.core.fv.converter import make_fv_converter
from jubatus_tpu.native import ingest

pytestmark = pytest.mark.skipif(
    not ingest.available(), reason="native toolchain unavailable")

MIXED_CONV = {
    "string_rules": [
        {"key": "*", "type": "space", "sample_weight": "tf",
         "global_weight": "bin"},
        {"key": "s*", "type": "str", "sample_weight": "bin",
         "global_weight": "bin"},
    ],
    "num_rules": [
        {"key": "*", "type": "num"},
        {"key": "n*", "type": "log"},
        {"key": "*", "type": "str"},
    ],
}


def _rand_datum(rng):
    words = ["win", "money", "now", "meet", "lunch", "café", "日本語", ""]
    sv = [(rng.choice(["subject", "sbody", "txt"]),
           " ".join(rng.choice(words) for _ in range(rng.randint(0, 6))))
          for _ in range(rng.randint(0, 3))]
    nv = [(rng.choice(["n1", "num2", "f3"]),
           rng.choice([0.0, 1.0, -2.5, 3.25, 7, 123456, 0.1, 1e16,
                       -0.0001, rng.uniform(-10, 10)]))
          for _ in range(rng.randint(0, 4))]
    return Datum(string_values=sv, num_values=nv)


def _expected(pyconv, datum):
    return [(int(a), float(np.float32(b))) for a, b in pyconv.convert(datum)]


def _got(idx_row, val_row):
    return [(int(a), float(b)) for a, b in zip(idx_row, val_row) if a != 0]


def test_parity_mixed_workload():
    p = ingest.IngestParser(
        ingest.spec_from_converter_config(MIXED_CONV), 20)
    pyconv = make_fv_converter(MIXED_CONV, dim_bits=20)
    rng = random.Random(7)
    data = [("lab%d" % rng.randint(0, 3), _rand_datum(rng))
            for _ in range(400)]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
    labels, idx, val = p.parse(raw)
    for i, (l, d) in enumerate(data):
        assert labels[i] == l
        assert _got(idx[i], val[i]) == _expected(pyconv, d), (i, l)


def test_parity_legacy_wire_and_num_formats():
    conv = {"num_rules": [{"key": "*", "type": "str"}],
            "string_rules": [{"key": "*", "type": "space",
                              "sample_weight": "log_tf",
                              "global_weight": "bin"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 18)
    pyconv = make_fv_converter(conv, dim_bits=18)
    vals = [0.0, -0.0, 1.0, -1.0, 0.5, -0.0001, 0.0001, 1e-5, -1e-5, 1e16,
            1e15 + 0.5, 123456789.125, 3.141592653589793, 2.5e-10, 9.9e15,
            1.00000000001, 1e16 + 2.0, 4.5e18]
    rng = random.Random(9)
    vals += [rng.uniform(-1, 1) * 10 ** rng.randint(-15, 15)
             for _ in range(200)]
    data = [("x", Datum(num_values=[("k", v)],
                        string_values=[("t", "a b b a")])) for v in vals]
    for use_bin in (True, False):  # modern + legacy request wire
        raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]],
                            use_bin_type=use_bin)
        labels, idx, val = p.parse(raw)
        for i, (_, d) in enumerate(data):
            assert _got(idx[i], val[i]) == _expected(pyconv, d), vals[i]


def test_numeric_targets_regression_wire():
    conv = {"num_rules": [{"key": "*", "type": "num"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 16)
    data = [[1.5, Datum({"x": 2.0}).to_msgpack()],
            [-0.25, Datum({"x": -1.0}).to_msgpack()]]
    labels, idx, val = p.parse(msgpack.packb(["c", data]))
    assert isinstance(labels, np.ndarray)
    np.testing.assert_allclose(labels, [1.5, -0.25])
    assert idx.shape == (2, 8)


def test_huge_integral_and_mixed_labels_fall_back():
    conv = {"num_rules": [{"key": "*", "type": "str"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 16)
    raw = msgpack.packb(
        ["c", [["x", Datum(num_values=[("k", 1e100)]).to_msgpack()]]])
    assert p.parse(raw) is None  # str(int(1e100)) not reproducible in C++
    mixed = msgpack.packb(
        ["c", [["x", Datum({"k": 1.0}).to_msgpack()],
               [3, Datum({"k": 1.0}).to_msgpack()]]])
    assert p.parse(mixed) is None  # mixed label kinds


def test_spec_rejects_unsupported_configs():
    assert ingest.spec_from_converter_config(None) is None
    assert ingest.spec_from_converter_config({}) is None
    # idf IS supported since round 3 (the parser takes the WeightManager's
    # dense df tables); user "weight" still needs the user-weight map
    assert ingest.spec_from_converter_config({
        "string_rules": [{"key": "*", "type": "space",
                          "sample_weight": "bin",
                          "global_weight": "idf"}]}) is not None
    assert ingest.spec_from_converter_config({
        "string_rules": [{"key": "*", "type": "space",
                          "sample_weight": "bin",
                          "global_weight": "weight"}]}) is None
    # filters change the datum before rules run
    assert ingest.spec_from_converter_config({
        "num_rules": [{"key": "*", "type": "num"}],
        "num_filter_rules": [{"key": "*", "type": "x", "suffix": "y"}],
    }) is None
    # combination rules ARE supported since round 4 (named cross product
    # in C++); unknown combination methods still decline
    assert ingest.spec_from_converter_config({
        "num_rules": [{"key": "*", "type": "num"}],
        "combination_rules": [{"key_left": "*", "key_right": "*",
                               "type": "mul"}]}) is not None
    assert ingest.spec_from_converter_config({
        "num_rules": [{"key": "*", "type": "num"}],
        "combination_types": {"odd": {"method": "concat"}},
        "combination_rules": [{"key_left": "*", "key_right": "*",
                               "type": "odd"}]}) is None
    # ngram IS supported since round 3 (utf-8 code-point slicing in C++);
    # regexp splitters still are not
    assert ingest.spec_from_converter_config({
        "string_types": {"bigram": {"method": "ngram", "char_num": "2"}},
        "string_rules": [{"key": "*", "type": "bigram",
                          "sample_weight": "bin",
                          "global_weight": "bin"}]}) is not None
    assert ingest.spec_from_converter_config({
        "string_types": {"rx": {"method": "regexp", "pattern": "a+"}},
        "string_rules": [{"key": "*", "type": "rx",
                          "sample_weight": "bin",
                          "global_weight": "bin"}]}) is None


# -- server integration -------------------------------------------------------

SERVER_CONV = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_rules": [{"key": "*", "type": "space",
                          "sample_weight": "bin", "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
    },
}


def _train_data():
    return [["spam", Datum({"t": "win money now", "n": 1.0})],
            ["ham", Datum({"t": "meet at noon", "n": -1.0})]] * 8


def test_server_fast_path_matches_converter_path():
    """The same traffic through the fast server and a converter-only
    server must produce identical models (classify scores equal)."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    fast = EngineServer("classifier", SERVER_CONV,
                        args=ServerArgs(engine="classifier"))
    fast_port = fast.start(0)
    slow = EngineServer("classifier", SERVER_CONV,
                        args=ServerArgs(engine="classifier"))
    slow_port = slow.start(0)
    slow.rpc._raw_methods.clear()  # force the converter path
    try:
        with ClassifierClient("127.0.0.1", fast_port, "t") as cf, \
                ClassifierClient("127.0.0.1", slow_port, "t") as cs:
            assert cf.train(_train_data()) == 16
            assert cs.train(_train_data()) == 16
            probe = [Datum({"t": "win money", "n": 0.5})]
            (rf,), (rs,) = cf.classify(probe), cs.classify(probe)
            assert sorted(rf) == sorted(rs)
        st = next(iter(fast.get_status().values()))
        assert st["microbatch.train_raw.item_count"] == 16
        assert st["microbatch.train.item_count"] == 0
        st2 = next(iter(slow.get_status().values()))
        assert st2["microbatch.train.item_count"] == 16
    finally:
        fast.stop()
        slow.stop()


def test_server_fast_path_regression():
    from jubatus_tpu.client import RegressionClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "PA", "parameter": {"sensitivity": 0.1,
                                          "regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    srv = EngineServer("regression", conf,
                       args=ServerArgs(engine="regression"))
    port = srv.start(0)
    try:
        with RegressionClient("127.0.0.1", port, "t") as c:
            data = [[float(2 * x), Datum({"x": float(x)})]
                    for x in range(-8, 9)] * 4
            assert c.train(data) == len(data)
            (est,) = c.estimate([Datum({"x": 3.0})])
            assert 2.0 < est < 10.0
        st = next(iter(srv.get_status().values()))
        assert st["microbatch.train_raw.item_count"] == len(data) * 1
    finally:
        srv.stop()


def test_server_ineligible_config_uses_converter_path():
    """A config the parser cannot express (regexp splitter) must keep the
    converter path (no raw registration)."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "PA", "parameter": {},
            "converter": {
                "string_types": {"rx": {"method": "regexp",
                                        "pattern": "[a-z]+"}},
                "string_rules": [
                    {"key": "*", "type": "rx", "sample_weight": "tf",
                     "global_weight": "bin"}]}}
    srv = EngineServer("classifier", conf,
                       args=ServerArgs(engine="classifier"))
    port = srv.start(0)
    try:
        assert "train" not in srv.rpc._raw_methods
        with ClassifierClient("127.0.0.1", port, "t") as c:
            assert c.train([["a", Datum({"t": "x y"})],
                            ["b", Datum({"t": "y z"})]]) == 2
        st = next(iter(srv.get_status().values()))
        assert st["microbatch.train.item_count"] == 2
    finally:
        srv.stop()


def test_server_idf_fast_path_matches_converter_path():
    """An idf config rides the fast path now — and its model must stay
    IDENTICAL to a converter-only server fed the same traffic (df
    observation order and idf scaling replayed exactly in C++)."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
            "converter": {"string_rules": [
                {"key": "*", "type": "space", "sample_weight": "tf",
                 "global_weight": "idf"}]}}
    fast = EngineServer("classifier", conf,
                        args=ServerArgs(engine="classifier"))
    fast_port = fast.start(0)
    slow = EngineServer("classifier", conf,
                        args=ServerArgs(engine="classifier"))
    slow_port = slow.start(0)
    slow.rpc._raw_methods.clear()  # force the converter path
    try:
        assert "train" in fast.rpc._raw_methods
        data = [["spam", Datum({"t": "win money now now"})],
                ["ham", Datum({"t": "meet at noon"})],
                ["spam", Datum({"t": "money money fast"})],
                ["ham", Datum({"t": "noon lunch plan"})]]
        with ClassifierClient("127.0.0.1", fast_port, "t") as cf, \
                ClassifierClient("127.0.0.1", slow_port, "t") as cs:
            for _ in range(5):
                assert cf.train(data) == 4
                assert cs.train(data) == 4
            probe = [Datum({"t": "money now"}), Datum({"t": "noon plan"}),
                     Datum({"t": "unseen words"})]
            assert [sorted(r) for r in cf.classify(probe)] == \
                [sorted(r) for r in cs.classify(probe)]
        # fast server really used the raw path, and df state converged
        assert fast.coalescers["train_raw"].stats()["item_count"] == 20
        np.testing.assert_array_equal(
            fast.driver.converter.weights._df_diff,
            slow.driver.converter.weights._df_diff)
    finally:
        fast.stop()
        slow.stop()


def test_server_idf_fast_path_regression_matches_converter_path():
    """The same for numeric targets: a regression flush under a pure-idf
    config gets the flush-time observe + scale too (it was skipped until
    the raw path had one request shape), so both servers estimate alike."""
    from jubatus_tpu.client import RegressionClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "PA", "parameter": {"sensitivity": 0.1,
                                          "regularization_weight": 1.0},
            "converter": {"string_rules": [
                {"key": "*", "type": "space", "sample_weight": "tf",
                 "global_weight": "idf"}]}}
    fast = EngineServer("regression", conf,
                        args=ServerArgs(engine="regression"))
    fast_port = fast.start(0)
    slow = EngineServer("regression", conf,
                        args=ServerArgs(engine="regression"))
    slow_port = slow.start(0)
    slow.rpc._raw_methods.clear()  # force the converter path
    try:
        assert "train" in fast.rpc._raw_methods
        data = [[3.0, Datum({"t": "win money now now"})],
                [1.0, Datum({"t": "meet at noon"})],
                [4.0, Datum({"t": "money money fast"})],
                [2.0, Datum({"t": "noon lunch plan"})]]
        with RegressionClient("127.0.0.1", fast_port, "t") as cf, \
                RegressionClient("127.0.0.1", slow_port, "t") as cs:
            for _ in range(5):
                assert cf.train(data) == 4
                assert cs.train(data) == 4
            probe = [Datum({"t": "money now"}), Datum({"t": "noon plan"})]
            np.testing.assert_allclose(cf.estimate(probe), cs.estimate(probe),
                                       rtol=1e-5, atol=1e-6)
        assert fast.coalescers["train_raw"].stats()["item_count"] == 20
        np.testing.assert_array_equal(
            fast.driver.converter.weights._df_diff,
            slow.driver.converter.weights._df_diff)
    finally:
        fast.stop()
        slow.stop()


def test_server_fallback_on_undecodable_fast_wire():
    """A train request whose first slot kind defies the engine (numeric
    label on a classifier) must fall back to the generic path and behave
    exactly as before the fast path existed."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer("classifier", SERVER_CONV,
                       args=ServerArgs(engine="classifier"))
    port = srv.start(0)
    try:
        with ClassifierClient("127.0.0.1", port, "t") as c:
            n = c.client.call("train", "t", [[3, Datum({"n": 1.0}).to_msgpack()],
                                             [4, Datum({"n": -1.0}).to_msgpack()]])
            assert n == 2  # generic path accepts any hashable label
            labels = c.get_labels()
            assert set(labels) == {3, 4}
    finally:
        srv.stop()


def test_hostile_lengths_error_not_abort():
    """A tiny request claiming 2^32 array elements must return a parse
    error (-> RPC error reply), never bad_alloc/terminate (code-review:
    the pre-allocation aborted the whole server)."""
    conv = {"num_rules": [{"key": "*", "type": "num"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 16)
    # [name, [[label, [sv_claiming_4B_pairs ...]]]]
    hostile = (b"\x92\xa1c\x91\x92\xa1x\x92"
               b"\xdd\xff\xff\xff\xff")  # array32 len 0xffffffff, no body
    assert p.parse(hostile) is None
    hostile2 = b"\x92\xa1c\x91\x92\xa1x\x92\x90\xdd\xff\xff\xff\xff"
    assert p.parse(hostile2) is None
    # the handle still works afterwards
    ok = msgpack.packb(["c", [["x", Datum({"k": 1.0}).to_msgpack()]]])
    assert p.parse(ok) is not None


def test_unicode_whitespace_tokenizes_like_python():
    """str.split() splits on Unicode whitespace; the fast path must hash
    the same tokens (code-review: isspace over bytes diverged on NBSP,
    U+3000, \\x1c — silently different models per path)."""
    conv = {"string_rules": [{"key": "*", "type": "space",
                              "sample_weight": "tf", "global_weight": "bin"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 20)
    pyconv = make_fv_converter(conv, dim_bits=20)
    texts = ["a\x1cb", "a\xa0b", "a　b", "a b c", "x\x85y",
             " lead", "trail ", "mixed \t 　 runs",
             "café\xa0日本語", "plain space only"]
    data = [("t", Datum(string_values=[("k", s)])) for s in texts]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
    labels, idx, val = p.parse(raw)
    for i, (_, d) in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), repr(texts[i])


def test_fallback_counts_trace_span_once():
    """A RAW_FALLBACK request must appear once in trace.rpc.<m>.count
    (code-review: fast attempt + generic invoke double-counted)."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer("classifier", SERVER_CONV,
                       args=ServerArgs(engine="classifier"))
    port = srv.start(0)
    try:
        with ClassifierClient("127.0.0.1", port, "t") as c:
            # numeric labels -> parser declines -> generic path
            c.client.call("train", "t", [[3, Datum({"n": 1.0}).to_msgpack()]])
            (st,) = c.get_status().values()
        assert st["trace.rpc.train.count"] == 1
    finally:
        srv.stop()


def test_parse_datums_matches_converter():
    """The classify/estimate wire ([name, [datum, ...]]) parses to the
    same hashed batch the Python converter produces."""
    p = ingest.IngestParser(
        ingest.spec_from_converter_config(MIXED_CONV), 20)
    pyconv = make_fv_converter(MIXED_CONV, dim_bits=20)
    rng = random.Random(11)
    data = [_rand_datum(rng) for _ in range(100)]
    raw = msgpack.packb(["c", [d.to_msgpack() for d in data]])
    parsed = p.parse_datums(raw)
    assert parsed is not None
    idx, val = parsed
    for i, d in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), i
    # a train-shaped wire is NOT a datum list
    train_raw = msgpack.packb(["c", [["lb", data[0].to_msgpack()]]])
    assert p.parse_datums(train_raw) is None


def test_server_fast_classify_and_estimate_match_slow_path():
    from jubatus_tpu.client import ClassifierClient, RegressionClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer("classifier", SERVER_CONV,
                       args=ServerArgs(engine="classifier"))
    port = srv.start(0)
    slow = EngineServer("classifier", SERVER_CONV,
                        args=ServerArgs(engine="classifier"))
    sport = slow.start(0)
    slow.rpc._raw_methods.clear()
    try:
        assert "classify" in srv.rpc._raw_methods
        with ClassifierClient("127.0.0.1", port, "t") as cf, \
                ClassifierClient("127.0.0.1", sport, "t") as cs:
            cf.train(_train_data())
            cs.train(_train_data())
            probe = [Datum({"t": "win money", "n": 0.5}),
                     Datum({"t": "meet at noon"})]
            assert [sorted(r) for r in cf.classify(probe)] == \
                [sorted(r) for r in cs.classify(probe)]
    finally:
        srv.stop()
        slow.stop()

    conf = {"method": "PA", "parameter": {"sensitivity": 0.1,
                                          "regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    rsrv = EngineServer("regression", conf,
                        args=ServerArgs(engine="regression"))
    rport = rsrv.start(0)
    try:
        assert "estimate" in rsrv.rpc._raw_methods
        with RegressionClient("127.0.0.1", rport, "t") as c:
            c.train([[float(2 * x), Datum({"x": float(x)})]
                     for x in range(-8, 9)] * 4)
            ests = c.estimate([Datum({"x": 3.0}), Datum({"x": -2.0})])
            assert 2.0 < ests[0] < 10.0 and -8.0 < ests[1] < -1.0
    finally:
        rsrv.stop()


def test_parser_survives_mutation_fuzz():
    """Randomly mutated request bytes must yield a clean parse or a clean
    None — never a crash (the parser handles attacker-controlled bytes
    before any auth layer)."""
    p = ingest.IngestParser(
        ingest.spec_from_converter_config(MIXED_CONV), 16)
    rng = random.Random(13)
    base = msgpack.packb(
        ["c", [["lbl%d" % i, _rand_datum(rng).to_msgpack()]
               for i in range(8)]])
    for trial in range(1500):
        raw = bytearray(base)
        for _ in range(rng.randint(1, 6)):
            pos = rng.randrange(len(raw))
            raw[pos] = rng.randrange(256)
        if rng.random() < 0.3:
            raw = raw[:rng.randrange(len(raw))]
        out = p.parse(bytes(raw))
        if out is not None:
            labels, idx, val = out
            assert idx.shape == val.shape
        out2 = p.parse_datums(bytes(raw))
        if out2 is not None:
            assert out2[0].shape == out2[1].shape


def test_parity_ngram_splitter():
    """ngram string types (round-3 coverage extension): the C++ sliding
    window must match converter.py's text[i:i+n] over a surrogateescape-
    decoded str — code points, not bytes, including malformed UTF-8."""
    conv = {
        "string_types": {"bigram": {"method": "ngram", "char_num": "2"},
                         "tri": {"method": "ngram", "char_num": "3"}},
        "string_rules": [
            {"key": "*", "type": "bigram", "sample_weight": "tf",
             "global_weight": "bin"},
            {"key": "t*", "type": "tri", "sample_weight": "log_tf",
             "global_weight": "bin"},
        ],
    }
    spec = ingest.spec_from_converter_config(conv)
    assert spec is not None
    p = ingest.IngestParser(spec, 18)
    pyconv = make_fv_converter(conv, dim_bits=18)
    texts = ["", "a", "ab", "abc", "ababab", "café au lait", "日本語のテキスト",
             "mixed 日本 text", "aa" * 40,
             b"bad\xffutf8\xc3(seq".decode("utf-8", "surrogateescape"),
             b"\xe2\x82".decode("utf-8", "surrogateescape"),  # truncated
             # shortest-form violations: CPython decodes each byte as one
             # surrogate; the C++ walker must count the same code points
             b"\xc0\x80a".decode("utf-8", "surrogateescape"),   # overlong NUL
             b"\xe0\x80\x80b".decode("utf-8", "surrogateescape"),
             b"\xed\xa0\x80c".decode("utf-8", "surrogateescape"),  # surrogate
             b"\xf0\x80\x80\x80d".decode("utf-8", "surrogateescape"),
             b"\xf4\x90\x80\x80e".decode("utf-8", "surrogateescape"),  # >10FFFF
             b"\xf5\x80\x80\x80f".decode("utf-8", "surrogateescape"),
             b"a\xc2 b\xe1\x80 c\xf3\x80\x80".decode("utf-8",
                                                     "surrogateescape"),
             # overlong-encoded SPACE (0xC0 0xA0): must NOT split as space
             b"x\xc0\xa0y".decode("utf-8", "surrogateescape")]
    rng = random.Random(21)
    alphabet = "abφ語 \t"
    texts += ["".join(rng.choice(alphabet) for _ in range(rng.randint(0, 30)))
              for _ in range(120)]
    data = [("L", Datum(string_values=[(rng.choice(["txt", "body"]), t)]))
            for t in texts]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]],
                        use_bin_type=True, unicode_errors="surrogateescape")
    labels, idx, val = p.parse(raw)
    for i, (_, d) in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), texts[i]


def test_parity_space_splitter_hostile_utf8():
    """The SPACE splitter shares the validated decoder: overlong-encoded
    whitespace (e.g. 0xC0 0xA0 for SPACE) must be treated as non-space
    surrogates exactly like Python does."""
    conv = {"string_rules": [{"key": "*", "type": "space",
                              "sample_weight": "tf",
                              "global_weight": "bin"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 18)
    pyconv = make_fv_converter(conv, dim_bits=18)
    texts = [b"x\xc0\xa0y".decode("utf-8", "surrogateescape"),
             b"a\xe0\x80\x85b".decode("utf-8", "surrogateescape"),
             b"u\xc2\x85v".decode("utf-8", "surrogateescape"),  # real NEL
             b"q\xed\xa0\x80 r".decode("utf-8", "surrogateescape")]
    data = [("L", Datum(string_values=[("t", t)])) for t in texts]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]],
                        use_bin_type=True, unicode_errors="surrogateescape")
    labels, idx, val = p.parse(raw)
    for i, (_, d) in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), texts[i]


def test_ngram_bad_char_num_not_expressible():
    for bad in ("0", "-1", "x", None, "4294967297"):
        conv = {"string_types": {"g": {"method": "ngram", "char_num": bad}},
                "string_rules": [{"key": "*", "type": "g",
                                  "sample_weight": "bin",
                                  "global_weight": "bin"}]}
        assert ingest.spec_from_converter_config(conv) is None


def test_parity_idf_global_weight():
    """idf rides the fast path (round 3): jt_ingest_parse_w must replay
    converter.convert(update_weights=True)'s EXACT per-document protocol —
    observe distinct idf indices first, then scale by log(ndocs/df), then
    merge by hashed index — so a request-by-request sequence stays
    bit-identical to the Python converter fed the same stream."""
    conv = {"string_rules": [{"key": "*", "type": "space",
                              "sample_weight": "tf",
                              "global_weight": "idf"}],
            "num_rules": [{"key": "*", "type": "num"}]}
    spec = ingest.spec_from_converter_config(conv)
    assert spec is not None
    p = ingest.IngestParser(spec, 18)
    assert p.needs_weights
    pyconv = make_fv_converter(conv, dim_bits=18)
    fast = make_fv_converter(conv, dim_bits=18)  # owns the fast path's df

    rng = random.Random(33)
    words = ["alpha", "beta", "gamma", "delta", "eps", "zeta", "eta"]
    for req in range(6):
        data = []
        for _ in range(rng.randint(1, 30)):
            text = " ".join(rng.choice(words)
                            for _ in range(rng.randint(0, 8)))
            nv = [("n", rng.uniform(-2, 2))] if rng.random() < 0.5 else []
            data.append(("L%d" % rng.randint(0, 2),
                         Datum(string_values=[("t", text)], num_values=nv)))
        raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
        with fast.weights.lock:
            out = p.parse(raw, weights=fast.weights)
        assert out is not None
        labels, idx, val = out
        for i, (_, d) in enumerate(data):
            exp = [(int(a), float(np.float32(b)))
                   for a, b in pyconv.convert(d, update_weights=True)]
            assert _got(idx[i], val[i]) == exp, (req, i)
    # df state identical after the whole stream
    np.testing.assert_array_equal(fast.weights._df_diff,
                                  pyconv.weights._df_diff)
    assert fast.weights.ndocs == pyconv.weights.ndocs

    # the QUERY path reads idf without observing
    before = fast.weights.ndocs
    qraw = msgpack.packb(["c", [Datum({"t": "alpha beta"}).to_msgpack()]])
    with fast.weights.lock:
        qi, qv = p.parse_datums(qraw, weights=fast.weights)
    assert fast.weights.ndocs == before
    exp = [(int(a), float(np.float32(b)))
           for a, b in pyconv.convert(Datum({"t": "alpha beta"}))]
    assert _got(qi[0], qv[0]) == exp

    # an idf spec without weights must decline, not crash
    assert p.parse(raw) is None
    assert p.parse_datums(qraw) is None


def test_parity_num_filters():
    """num filters ride the fast path (round 3): every builtin transform,
    applied sequentially over the GROWING kv list (a later filter sees an
    earlier filter's appended output), bit-identical to converter.py."""
    conv = {
        "num_filter_types": {
            "a5": {"method": "add", "value": "5.5"},
            "lin": {"method": "linear_normalization", "min": "-2",
                    "max": "3"},
            "gz": {"method": "gaussian_normalization", "average": "0.5",
                   "standard_deviation": "2.0"},
            "sig": {"method": "sigmoid_normalization", "gain": "1.5",
                    "bias": "0.25"},
        },
        "num_filter_rules": [
            {"key": "x*", "type": "a5", "suffix": "+5"},
            {"key": "*+5", "type": "sig", "suffix": "$s"},  # chained
            {"key": "y", "type": "lin", "suffix": "_n"},
            {"key": "*", "type": "gz", "suffix": "@g"},
        ],
        "num_rules": [{"key": "*", "type": "num"},
                      {"key": "*_n", "type": "str"}],
    }
    spec = ingest.spec_from_converter_config(conv)
    assert spec is not None
    p = ingest.IngestParser(spec, 18)
    pyconv = make_fv_converter(conv, dim_bits=18)
    rng = random.Random(44)
    data = []
    for _ in range(150):
        nv = [(rng.choice(["x1", "x2", "y", "z"]),
               rng.choice([0.0, -3.0, 2.5, 7.25,
                           rng.uniform(-10, 10)]))
              for _ in range(rng.randint(0, 5))]
        data.append(("L", Datum(num_values=nv)))
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
    out = p.parse(raw)
    assert out is not None
    labels, idx, val = out
    for i, (_, d) in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), (i, d.num_values)


def test_num_filter_unknown_method_declines():
    conv = {"num_filter_types": {"w": {"method": "wavelet"}},
            "num_filter_rules": [{"key": "*", "type": "w", "suffix": "#"}],
            "num_rules": [{"key": "*", "type": "num"}]}
    assert ingest.spec_from_converter_config(conv) is None


def test_sigmoid_overflow_falls_back_like_python_raises():
    """math.exp raises OverflowError past ~709; the C++ path must decline
    (fall back) so both paths fail the request identically instead of the
    fast path silently emitting 0.0."""
    conv = {"num_filter_types": {"s": {"method": "sigmoid_normalization",
                                       "gain": "1.5", "bias": "0"}},
            "num_filter_rules": [{"key": "*", "type": "s", "suffix": "#"}],
            "num_rules": [{"key": "*", "type": "num"}]}
    p = ingest.IngestParser(ingest.spec_from_converter_config(conv), 16)
    pyconv = make_fv_converter(conv, dim_bits=16)
    ok = msgpack.packb(["c", [["x", Datum({"k": -400.0}).to_msgpack()]]])
    assert p.parse(ok) is not None  # exp(600) is finite
    bad = msgpack.packb(["c", [["x", Datum({"k": -500.0}).to_msgpack()]]])
    assert p.parse(bad) is None     # exp(750) overflows -> decline
    with pytest.raises(OverflowError):
        pyconv.convert(Datum({"k": -500.0}))


COMBO_CONV = {
    "string_rules": [
        {"key": "*", "type": "str", "sample_weight": "bin",
         "global_weight": "bin"},
    ],
    "num_rules": [{"key": "*", "type": "num"}],
    "combination_rules": [
        {"key_left": "*", "key_right": "*", "type": "mul"},
    ],
}


def test_parity_combination_rules():
    """The reference's arow_combinational_feature.json converter block
    rides the fast path bit-identically (VERDICT r3 item 6): cross
    product over named features, canonical pair order, mul values."""
    spec = ingest.spec_from_converter_config(COMBO_CONV)
    assert spec is not None and "combo\tmul" in spec
    p = ingest.IngestParser(spec, 20)
    pyconv = make_fv_converter(COMBO_CONV, dim_bits=20)
    rng = random.Random(11)
    data = [("l%d" % rng.randint(0, 2), _rand_datum(rng))
            for _ in range(200)]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
    labels, idx, val = p.parse(raw)
    for i, (l, d) in enumerate(data):
        assert labels[i] == l
        assert _got(idx[i], val[i]) == _expected(pyconv, d), (i, l)


def test_parity_combination_add_and_matchers():
    conv = {
        "string_rules": [
            {"key": "s*", "type": "space", "sample_weight": "tf",
             "global_weight": "bin"},
        ],
        "num_rules": [{"key": "*", "type": "num"}],
        "combination_types": {"plus": {"method": "add"}},
        "combination_rules": [
            {"key_left": "*@num", "key_right": "*", "type": "plus"},
            {"key_left": "s*", "key_right": "*#tf/bin", "type": "mul"},
        ],
    }
    spec = ingest.spec_from_converter_config(conv)
    assert spec is not None
    p = ingest.IngestParser(spec, 18)
    pyconv = make_fv_converter(conv, dim_bits=18)
    rng = random.Random(13)
    data = [("x", _rand_datum(rng)) for _ in range(200)]
    raw = msgpack.packb(["c", [[l, d.to_msgpack()] for l, d in data]])
    _labels, idx, val = p.parse(raw)
    for i, (_l, d) in enumerate(data):
        assert _got(idx[i], val[i]) == _expected(pyconv, d), i


def test_combo_with_idf_declines():
    conv = {
        "string_rules": [
            {"key": "*", "type": "space", "sample_weight": "tf",
             "global_weight": "idf"},
        ],
        "combination_rules": [
            {"key_left": "*", "key_right": "*", "type": "mul"},
        ],
    }
    assert ingest.spec_from_converter_config(conv) is None


def test_parity_combo_plan_replay_fixed_schema():
    """The combo plan (round 5): a request whose datums repeat one key
    schema replays the recorded cross product — names/hashes computed
    once — and must stay bit-identical to the Python converter for
    every datum, including across a mid-request schema CHANGE (plan
    rebuild) and a schema that collides a combined name with a base
    name (terms accumulate into the base slot)."""
    spec = ingest.spec_from_converter_config(COMBO_CONV)
    p = ingest.IngestParser(spec, 20)
    pyconv = make_fv_converter(COMBO_CONV, dim_bits=20)
    rng = random.Random(17)
    data = []
    # phase 1: fixed 6-key schema, varying values (plan hit after datum 0)
    for _ in range(60):
        data.append(("a", Datum(num_values=[
            (f"f{j}", rng.uniform(-5, 5)) for j in range(6)])))
    # phase 2: schema change (extra key) -> rebuild, then hits again
    for _ in range(60):
        data.append(("b", Datum(num_values=[
            (f"f{j}", rng.uniform(-5, 5)) for j in range(7)])))
    # phase 3: collision shape — a base key named like a combined pair
    # ("x@num&y@num" as a LITERAL key) plus x, y
    for _ in range(30):
        data.append(("c", Datum(num_values=[
            ("x", rng.uniform(-2, 2)), ("y", rng.uniform(-2, 2)),
            ("x@num&y@num", rng.uniform(-2, 2))])))
    raw = msgpack.packb(["c", [[lab, d.to_msgpack()] for lab, d in data]])
    labels, idx, val = p.parse(raw)
    for i, (lab, d) in enumerate(data):
        assert labels[i] == lab
        assert _got(idx[i], val[i]) == _expected(pyconv, d), i


TEXT_FILTER_CONV = {
    "string_filter_types": {
        "strip_digits": {"method": "regexp", "pattern": "[0-9]+",
                         "replace": ""}},
    "string_filter_rules": [
        {"key": "*", "type": "strip_digits", "suffix": "-nodigit"}],
    "string_rules": [
        {"key": "*", "type": "space", "sample_weight": "tf",
         "global_weight": "bin"}],
}


def test_parity_string_filters_hybrid():
    """String-filter configs ride the HYBRID fast path (round 5, VERDICT
    r4 #4): Python applies the regex (memoized per distinct input) by
    rewriting the request; tokenize/tf/hash stay in C++. Output must be
    bit-identical to the Python converter, including cascaded filters
    (a later rule matching an earlier rule's appended key)."""
    p = ingest.IngestParser.from_converter_config(TEXT_FILTER_CONV, 20)
    assert p is not None and p._prefilters is not None
    pyconv = make_fv_converter(TEXT_FILTER_CONV, dim_bits=20)
    rng = random.Random(23)
    words = ["abc123", "x9y", "2024", "plain", "日本7語", ""]
    data = []
    for _ in range(120):
        body = " ".join(rng.choice(words)
                        for _ in range(rng.randint(0, 8)))
        data.append((rng.choice("ab"), Datum({"body": body})))
    raw = msgpack.packb(["c", [[lab, d.to_msgpack()] for lab, d in data]])
    labels, idx, val = p.parse(raw)
    for i, (lab, d) in enumerate(data):
        assert labels[i] == lab
        assert _got(idx[i], val[i]) == _expected(pyconv, d), i
    # query path too
    rawq = msgpack.packb(["c", [d.to_msgpack() for _l, d in data]])
    qidx, qval = p.parse_datums(rawq)
    for i, (_lab, d) in enumerate(data):
        assert _got(qidx[i], qval[i]) == _expected(pyconv, d), i


def test_parity_cascaded_string_filters():
    conv = {
        "string_filter_types": {
            "strip_digits": {"method": "regexp", "pattern": "[0-9]+",
                             "replace": ""},
            "dash": {"method": "regexp", "pattern": " ",
                     "replace": "-"}},
        "string_filter_rules": [
            {"key": "*", "type": "strip_digits", "suffix": "-nd"},
            # matches the FIRST rule's appended key too (cascade)
            {"key": "*-nd", "type": "dash", "suffix": "-dashed"}],
        "string_rules": [
            {"key": "*", "type": "space", "sample_weight": "bin",
             "global_weight": "bin"}],
    }
    p = ingest.IngestParser.from_converter_config(conv, 18)
    assert p is not None
    pyconv = make_fv_converter(conv, dim_bits=18)
    d = Datum({"body": "a1 b2 c3"})
    raw = msgpack.packb(["c", [["x", d.to_msgpack()]]])
    _labels, idx, val = p.parse(raw)
    assert _got(idx[0], val[0]) == _expected(pyconv, d)


def test_string_filter_unknown_method_declines():
    conv = dict(TEXT_FILTER_CONV,
                string_filter_types={"odd": {"method": "mystery"}},
                string_filter_rules=[
                    {"key": "*", "type": "odd", "suffix": "-x"}])
    assert ingest.IngestParser.from_converter_config(conv, 20) is None
